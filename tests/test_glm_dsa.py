"""``model_type: glm_moe_dsa`` (GLM-5.x) on the serving path, at a tiny size on
the CPU: one dense layer with a full indexer and five expert layers (shared,
shared, full, shared, shared), 16 experts of which 4 are held, ``index_topk``
16, pages of 16, seeded random weights drawn by the benchmark's generator
(perfbench/generators/glm_moe_dsa.py).  ``ServingEngine`` through the SPARSE
latent pool is held to the benchmark's plain reference
(perfbench/references/glm_moe_dsa.py, which imports nothing of the program):
chunked prefill then decode, caches over several pages and trips, a row alone
and in a full batch.  Beside it what the family forced, each against its own
oracle: the selection against a plain sort, the scores against numpy, the
walk under a mask and the gather against dense attention over the selected
rows, the sharing of a selection, the pool's kinds of page, and the share
test."""

from __future__ import annotations

import contextlib
import dataclasses
import importlib
import logging
import os
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "perfbench")
for path in (BENCH, ROOT):
    if path not in sys.path:
        sys.path.insert(0, path)

import run  # noqa: E402
import weights  # noqa: E402

from ipex_llm_tpu import kv  # noqa: E402
from ipex_llm_tpu.models import decoder  # noqa: E402
from ipex_llm_tpu.models.families import FAMILIES  # noqa: E402
from ipex_llm_tpu.ops import attention  # noqa: E402
from ipex_llm_tpu.serving.engine import (EngineConfig, Request,  # noqa: E402
                                         ServingEngine, stream_tokens)

CONFIG = "glm-5.2.int4.ep16"
FAMILY = "glm_moe_dsa"
PAGE = 16
NEW = 6
# the prompts of the served cases: under index_topk (every token selected);
# a cache over several pages and trips, far past index_topk; two more to fill
# the batch (four rows joining together take 8-token chunks, whose edges fall
# inside pages)
PROMPTS = {"all_selected": 10, "several_pages": 70, "filler_a": 45,
           "filler_b": 33}


def published() -> dict:
    return run.hf_config(run.load_json(
        os.path.join(BENCH, "configs", CONFIG + ".json")))


@pytest.fixture(scope="module")
def tiny():
    """(hf config, ModelConfig, params, reference module) of the tiny model."""
    gen = weights.generator(FAMILY)
    hf = gen.tiny(published())
    cfg = FAMILIES[FAMILY].to_config(hf)
    return hf, cfg, gen.param_tree(cfg, hf, "sym_int4", 11), \
        importlib.import_module("references." + FAMILY)


def prompt(name: str, vocab: int = 256) -> list[int]:
    rng = np.random.default_rng(sorted(PROMPTS).index(name))
    return [int(i) for i in rng.integers(3, vocab, PROMPTS[name])]


ENGINE = dict(max_rows=4, max_seq_len=128, page_size=PAGE, prefill_bucket=32,
              step_token_budget=32)


@pytest.fixture(scope="module")
def served(tiny):
    """Every prompt served ALONE, then all four at once (a full batch)."""
    _, cfg, params, _ = tiny
    eng = ServingEngine(cfg, params, EngineConfig(**ENGINE)).start()
    try:
        out = {"alone": {}, "batch": {}}
        for name in PROMPTS:
            req = eng.submit(Request(prompt_ids=prompt(name),
                                     max_new_tokens=NEW))
            out["alone"][name] = (list(stream_tokens(req, timeout=300)),
                                  [float(x) for x in req.logprobs])
        out["metrics_alone"] = dict(eng.metrics)
        reqs = {name: eng.submit(Request(prompt_ids=prompt(name),
                                         max_new_tokens=NEW))
                for name in PROMPTS}
        for name, req in reqs.items():
            out["batch"][name] = (list(stream_tokens(req, timeout=300)),
                                  [float(x) for x in req.logprobs])
        out["kv"] = eng.kv_stats()
        out["metrics"] = dict(eng.metrics)
    finally:
        eng.stop()
    return out


@pytest.mark.parametrize("how", ["alone", "batch"])
@pytest.mark.parametrize("name", sorted(PROMPTS))
def test_served_through_the_sparse_pool_matches_the_reference(
        tiny, served, name, how):
    hf, _, params, ref = tiny
    tokens, lps = served[how][name]
    assert len(tokens) == NEW == len(lps)
    ids = prompt(name)
    res = ref.compare(ref.logprobs(hf, params, ids + tokens[:-1]), len(ids),
                      tokens, lps)
    assert res["ok"], res
    # inside the limits at this size too, where ONE position that falls the
    # other side of a selection's edge (index_ties; bf16 scores against
    # float32) is a sixteenth of what a head attends, not a 2048th
    assert res["logprob_err"] < 0.2, res


@pytest.mark.parametrize("name", sorted(PROMPTS))
def test_a_row_does_not_depend_on_its_batch(served, name):
    """Alone its prompt goes in 32-token chunks; in the batch of four the
    budget splits into 8-token chunks beside decoding rows: other chunk
    edges, another ``p_b``, the same tokens."""
    alone, batch = served["alone"][name], served["batch"][name]
    assert alone[0] == batch[0]
    np.testing.assert_allclose(alone[1], batch[1], atol=2e-2)


# -- the pool's kinds of page ------------------------------------------------------

def test_the_sparse_pool_holds_a_latent_row_and_an_index_key():
    cfg = FAMILIES[FAMILY].to_config(published())
    assert cfg.indexer_types == ("full", "shared", "shared", "shared", "full",
                                 "shared", "shared", "shared", "full")
    assert (cfg.index_layers, cfg.index_topk) == (3, 2048)
    # [c (512) ; k_r (64)] padded to whole lanes, and the index key
    assert kv.latent_row_width(cfg) == 640
    assert kv.pool_geometry(cfg) == (1, 640, 128)
    page = kv.model_page_bytes(cfg, 128)
    assert page == 128 * (9 * 640 + 3 * 128) * 2 == 1572864
    assert kv.index_page_bytes(cfg, 128) == 128 * 3 * 128 * 2
    pool = kv.PagedKVCache.for_model(cfg, 4, 2, 8, 128)
    assert pool.latent and pool.sparse
    assert pool.k.shape == (9, 4, 1, 128, 640)      # a row a token a layer
    assert pool.v.shape == (3, 4, 1, 128, 128)      # full layers only
    assert pool.page_bytes == page and pool.pool_bytes == 4 * page
    assert pool.k.nbytes + pool.v.nbytes == pool.pool_bytes
    # a model without an indexer keeps the pool it had
    axk1 = FAMILIES["axk1"].to_config(run.hf_config(run.load_json(
        os.path.join(BENCH, "configs", "a.x-k1.int4.ep16.json"))))
    dense = kv.PagedKVCache.for_model(axk1, 4, 2, 8, 128)
    assert dense.latent and not dense.sparse
    assert dense.v.shape == (8, 4, 1, 64, 128)
    assert kv.index_page_bytes(axk1, 128) == 0
    assert kv.state_counters(axk1) == kv.STATE_COUNTERS
    assert kv.state_counters(cfg) == kv.STATE_COUNTERS + kv.DSA_COUNTERS


def test_the_engine_counts_what_was_scored_seen_and_selected(tiny, served):
    _, cfg, _, _ = tiny
    stats = served["kv"]
    assert stats["index_cache_bytes"] == stats["pages_total"] * PAGE * 2 * 32 * 2
    assert stats["latent_bytes_per_token"] == 128 * 2     # 64 + 16 -> 128 lanes
    assert stats["page_bytes"] * stats["pages_total"] == stats["pool_bytes"]
    m = served["metrics_alone"]
    # the four prompts alone, NEW tokens each: a query at slot p sees p + 1
    # cached tokens and keeps min(p + 1, index_topk) of them, in every layer;
    # the two full layers score what they see
    seen = sel = 0
    for n in PROMPTS.values():
        slots = np.arange(n + NEW - 1) + 1
        seen += int(slots.sum())
        sel += int(np.minimum(slots, cfg.index_topk).sum())
    assert m["dsa_tokens_visible_total"] == seen * cfg.num_layers
    assert m["dsa_tokens_selected_total"] == sel * cfg.num_layers
    assert m["dsa_keys_scored_total"] == seen * cfg.index_layers
    calls = m["dsa_full_layer_calls_total"]
    assert calls > 0 and m["dsa_shared_layer_calls_total"] == 2 * calls


@pytest.mark.parametrize("what", ["fp8", "mesh", "dense_cache"])
def test_what_the_sparse_path_cannot_do_is_refused_by_name(tiny, what):
    _, cfg, params, _ = tiny
    if what == "fp8":
        with pytest.raises(ValueError,
                           match="latent and index-key pages are bf16 only"):
            ServingEngine(cfg, params, EngineConfig(
                max_rows=2, max_seq_len=64, page_size=PAGE,
                kv_storage="fp8"))
    elif what == "mesh":
        from jax.sharding import Mesh

        mesh = Mesh(np.asarray(jax.devices()[:2]), ("tp",))
        with pytest.raises(ValueError,
                           match="indexer's key.*neither tick shards them"):
            ServingEngine(cfg, params, EngineConfig(
                max_rows=2, max_seq_len=64, page_size=PAGE), mesh=mesh)
    else:
        # the dense generate() cache attends every token: exact up to
        # index_topk tokens, refused by name past them
        tokens = jnp.asarray([prompt("all_selected")], jnp.int32)
        pos = jnp.arange(tokens.shape[1])[None]

        def cache(n):
            return kv.KVCache.init(cfg.num_layers, 1, n, cfg.num_kv_heads,
                                   cfg.head_dim, v_head_dim=cfg.v_dim)

        logits, _ = decoder.decoder_forward(cfg, params, tokens, cache(16),
                                            pos)
        assert logits.shape == (1, 10, 256)
        with pytest.raises(ValueError, match="glm_moe_dsa attends over the "
                           "16 tokens its indexer selects"):
            decoder.decoder_forward(cfg, params, tokens, cache(32), pos)


# -- scoring and selection ---------------------------------------------------------

def _sparse_case(seed=0, rows=3, t=5, heads=4, rank=32, rope=16, pages=10,
                 ps=8, index_dim=16, index_heads=3):
    """A sparse latent pool of one layer (and one full layer) filled with
    bf16 values, the third row's cache over several trips of the walk."""
    rng = np.random.default_rng(seed)

    def draw(*shape):
        return jnp.asarray(rng.standard_normal(shape), jnp.float32).astype(
            jnp.bfloat16)

    width = -(-(rank + rope) // 128) * 128
    kv_len = np.asarray([t + 9, t, t + 57][:rows], np.int32)
    cache = kv.PagedKVCache.init(1, rows * pages + 1, rows, pages, 1, ps,
                                 width, v_head_dim=index_dim, latent=True,
                                 index_layers=1)
    cache = dataclasses.replace(cache, tables=jnp.asarray(
        1 + np.arange(rows * pages).reshape(rows, pages), jnp.int32))
    total = pages * ps
    rows_all = jnp.pad(draw(rows, total, rank + rope),
                       ((0, 0), (0, 0), (0, width - rank - rope)))
    keys_all = draw(rows, total, index_dim)
    zero = jnp.zeros((rows,), jnp.int32)
    k = cache.write_pool(cache.k, 0, rows_all[:, :, None, :], zero)
    v = cache.write_pool(cache.v, 0, keys_all[:, :, None, :], zero)
    q = jnp.pad(draw(rows, t, heads, rank + rope),
                ((0, 0),) * 3 + ((0, width - rank - rope),))
    slots = jnp.asarray(kv_len)[:, None] - t + jnp.arange(t)[None, :]
    return dict(cache=cache, k=k, v=v, rows_all=rows_all, keys_all=keys_all,
                kv_len=jnp.asarray(kv_len), q=q, q_slots=slots, t=t,
                rank=rank, scale=0.17,
                q_idx=draw(rows, t, index_heads, index_dim),
                w_idx=jnp.asarray(rng.standard_normal(
                    (rows, t, index_heads)), jnp.float32))


def _plain_selection(scores: np.ndarray, k: int) -> np.ndarray:
    """The k largest of each row of float scores (-inf = not visible), equal
    scores to the earlier position, by a plain sort."""
    mask = np.zeros(scores.shape, bool)
    for i in np.ndindex(scores.shape[:-1]):
        row = scores[i]
        order = sorted(range(row.size), key=lambda s: (-row[s], s))
        for s in order[: min(k, int(np.isfinite(row).sum()))]:
            mask[i + (s,)] = True
    return mask


@pytest.mark.parametrize("t", [1, 5], ids=["decode", "chunk"])
def test_the_scores_are_the_weighted_relu_sum_over_what_a_query_sees(t):
    case = _sparse_case(t=t)
    got = np.asarray(attention.index_scores(
        case["q_idx"], case["w_idx"], case["v"], case["cache"],
        case["q_slots"], case["kv_len"], layer=0))
    q = np.asarray(case["q_idx"], np.float64)
    keys = np.asarray(case["keys_all"], np.float64)
    want = np.einsum("btj,btjs->bts", np.asarray(case["w_idx"], np.float64),
                     np.maximum(np.einsum("btjd,bsd->btjs", q, keys), 0.0))
    seen = (np.arange(keys.shape[1])[None, None, :]
            <= np.asarray(case["q_slots"])[:, :, None])
    assert np.all(np.isneginf(got[~seen]))
    np.testing.assert_allclose(got[seen], want[seen], rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("k", [4, 16, 200])
def test_the_selection_is_the_k_largest_with_ties_to_the_earlier(k):
    """Scores rounded to one decimal (ties everywhere, zeros of both signs):
    the chunk's threshold search and the decode row's ``top_k`` choose the
    set a plain stable sort chooses."""
    rng = np.random.default_rng(k)
    b, t, s = 2, 6, 80
    scores = np.round(rng.standard_normal((b, t, s)), 1).astype(np.float32)
    scores[0, 0, :8] = -0.0
    slots = np.asarray([[30, 31, 32, 33, 34, 79], [0, 1, 2, 3, 4, 5]])
    seen = np.arange(s)[None, None, :] <= slots[:, :, None]
    masked = np.where(seen, np.where(scores == 0.0, 0.0, scores), -np.inf)
    want = _plain_selection(masked, k)
    mask = np.asarray(attention.select_topk(jnp.asarray(masked), k))
    assert np.array_equal(mask, want)
    for q in range(t):       # a decode call, one query a row
        idx, ok = attention.select_topk(jnp.asarray(masked[:, q:q + 1]), k)
        for r in range(b):
            got = set(np.asarray(idx[r])[np.asarray(ok[r])].tolist())
            assert got == set(np.nonzero(want[r, q])[0].tolist())


# -- the walk over a selection -----------------------------------------------------

def _selected_oracle(case, row, query, chosen):
    """Plain absorbed attention of one query over the chosen slots."""
    rows = np.asarray(case["rows_all"][row], np.float64)[chosen]
    q = np.asarray(case["q"][row, query], np.float64)
    s = q @ rows.T * case["scale"]
    p = np.exp(s - s.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    return p @ rows[:, : case["rank"]]


def _random_selection(case, k, seed=3):
    """A mask [B, T, S] of k visible slots a query (all of them where it
    sees fewer), and its decode form (idx, ok)."""
    rng = np.random.default_rng(seed)
    slots = np.asarray(case["q_slots"])
    s = case["rows_all"].shape[1]
    mask = np.zeros(slots.shape + (s,), bool)
    for i in np.ndindex(slots.shape):
        seen = slots[i] + 1
        mask[i + (rng.permutation(seen)[:k],)] = True
    return mask


@pytest.mark.parametrize("kernels", [False, True], ids=["xla", "kernel"])
def test_a_chunk_walks_its_pages_under_the_mask(kernels, monkeypatch):
    from ipex_llm_tpu.ops import dispatch

    monkeypatch.setattr(dispatch, "use_pallas", lambda op=None: kernels)
    case = _sparse_case(t=5)
    mask = _random_selection(case, 7)
    out = np.asarray(attention.sparse_latent_attention(
        case["q"], case["k"], case["cache"], jnp.asarray(mask),
        jnp.full((3,), 5, jnp.int32), case["kv_len"], rank=case["rank"],
        scale=case["scale"], layer=0), np.float32)
    for row in range(3):
        for query in range(5):
            want = _selected_oracle(case, row, query,
                                    np.nonzero(mask[row, query])[0])
            np.testing.assert_allclose(out[row, query], want, atol=2e-2,
                                       rtol=2e-2)


def test_a_decode_row_gathers_the_rows_it_selected():
    case = _sparse_case(t=1)
    mask = _random_selection(case, 7)
    k = 7
    idx = np.zeros((3, k), np.int32)
    ok = np.zeros((3, k), bool)
    for r in range(3):
        chosen = np.nonzero(mask[r, 0])[0]
        idx[r, : chosen.size], ok[r, : chosen.size] = chosen, True
    out = np.asarray(attention.sparse_latent_attention(
        case["q"], case["k"], case["cache"],
        (jnp.asarray(idx), jnp.asarray(ok)), jnp.asarray([1, 1, 0]),
        case["kv_len"], rank=case["rank"], scale=case["scale"], layer=0),
        np.float32)
    for r in range(2):
        want = _selected_oracle(case, r, 0, np.nonzero(mask[r, 0])[0])
        np.testing.assert_allclose(out[r, 0], want, atol=2e-2, rtol=2e-2)
    assert not out[2].any()        # an idle row attends nothing


def test_a_tokens_bits_do_not_depend_on_how_its_prompt_is_chunked():
    """The walk's kernel (interpret mode): the same 8 queries of one row as
    ONE chunk, as two chunks of 4, and as a chunk of 4 beside another row
    (another ``p_b``), give the same bits."""
    from ipex_llm_tpu.ops.pallas import ragged_paged_attention as rpa

    case = _sparse_case(rows=3, t=8)
    mask = _random_selection(case, 9)
    row = 2
    cache, lens = case["cache"], np.asarray(case["kv_len"])

    def walk(rows_, q0, q1):
        rows_ = np.asarray(rows_)
        return np.asarray(rpa.sparse_paged_sdpa(
            case["q"][rows_, q0:q1], case["k"], cache.tables[rows_],
            jnp.asarray(mask[rows_, q0:q1]),
            jnp.asarray(lens[rows_] - (8 - q1)),
            jnp.full((rows_.size,), q1 - q0, jnp.int32), rank=case["rank"],
            scale=case["scale"], layer=0), np.float32)

    whole = walk([row], 0, 8)[0]
    halves = np.concatenate([walk([row], 0, 4)[0], walk([row], 4, 8)[0]])
    beside = walk([0, row], 4, 8)[1]
    assert np.array_equal(whole, halves)
    assert np.array_equal(whole[4:], beside)


@pytest.mark.parametrize("t", [1, 5, 19])
def test_the_pool_write_kernel_puts_the_scatters_bytes(t):
    """``paged_pool_write`` (interpret mode) against the XLA scatter, for
    both kinds of page, chunks that start inside a page and cross pages."""
    from ipex_llm_tpu.ops.pallas import paged_kv_write as pkw

    rng = np.random.default_rng(t)
    cache = kv.PagedKVCache.init(2, 13, 3, 4, 1, 8, 128, v_head_dim=128,
                                 latent=True, index_layers=1)
    cache = dataclasses.replace(cache, tables=jnp.asarray(
        1 + np.arange(12).reshape(3, 4), jnp.int32))
    pos = jnp.asarray([0, 5, 13], jnp.int32)
    for pool, layer in ((cache.k, 1), (cache.v, 0)):
        pool = jnp.asarray(rng.standard_normal(pool.shape), jnp.bfloat16)
        new = jnp.asarray(rng.standard_normal((3, t, 1, 128)), jnp.bfloat16)
        want = cache.write_pool(pool, layer, new, pos)
        got = pkw.paged_pool_write(cache, pool, layer, new, pos)
        live = np.ones(pool.shape[1], bool)
        live[0] = False           # scratch page 0: whatever lands, lands
        assert np.array_equal(np.asarray(got, np.float32)[:, live],
                              np.asarray(want, np.float32)[:, live])


# -- the sharing of a selection ----------------------------------------------------

def _first_k(t: int, k: int) -> np.ndarray:
    """Another selection: every query's k EARLIEST visible positions."""
    pos = np.arange(t)
    return (pos[None, :] <= pos[:, None]) & (pos[None, :] < k)


def test_a_shared_layer_attends_what_the_full_layer_below_it_chose(
        tiny, monkeypatch):
    """The program with every indexer's choice REPLACED by the k earliest
    positions gives what the reference gives when its two full layers are
    handed that selection (so the shared layers follow the carried
    selection, not one of their own, and not every token), and another
    answer than with the indexers' own choice."""
    hf, cfg, params, ref = tiny
    ids = prompt("several_pages")
    t = len(ids)

    def earliest(scores, k):
        seen = scores > -jnp.inf
        return seen & (jnp.arange(scores.shape[-1]) < k)

    def forward():
        cache = kv.with_recurrent_state(kv.PagedKVCache.for_model(
            cfg, 8, 1, 5, PAGE), cfg, 1)
        cache = dataclasses.replace(
            cache, tables=jnp.asarray([[1, 2, 3, 4, 5]], jnp.int32))
        logits, _ = decoder.decoder_forward(
            cfg, params, jnp.asarray([ids], jnp.int32), cache,
            jnp.arange(t)[None], slot_offsets=jnp.zeros((1,), jnp.int32),
            chunk_lens=jnp.asarray([t], jnp.int32))
        return np.asarray(jax.nn.log_softmax(logits[0], -1))

    def apart(a, b):
        """Median over the positions of the error at b's likeliest token."""
        top = np.argmax(b, -1)
        return float(np.median(np.abs(a[np.arange(t), top]
                                      - b[np.arange(t), top])))

    own = forward()
    monkeypatch.setattr(decoder, "select_topk", earliest)
    other = forward()
    alt = _first_k(t, cfg.index_topk)
    want_own = ref.logprobs(hf, params, ids)[0]
    want_other = ref.logprobs(hf, params, ids, selections={0: alt, 3: alt})[0]
    # (read at this size: 0.011 and 0.015 where they agree, 0.19-0.22 across)
    assert apart(own, want_own) < 0.04 and apart(other, want_other) < 0.04
    assert apart(own, want_other) > 0.08 and apart(other, want_own) > 0.08
    # handed to the FIRST full layer alone, it reaches that layer and the two
    # shared ones after it, and no further: a third answer (0.15 and 0.10)
    first = ref.logprobs(hf, params, ids, selections={0: alt})[0]
    assert apart(first, want_own) > 0.05 and apart(first, want_other) > 0.05


# -- the share ---------------------------------------------------------------------

def test_the_shares_of_a_layer_add_up_to_the_uncut_layer(tiny):
    """THE SHARE TEST: an expert layer cut into 4 shares of 4 experts, each
    computed by the program's held layer (routing on score + bias over all
    16), the shared expert counted once: their sum is the uncut reference's
    layer output."""
    hf0, _, _, ref = tiny
    hf = dict(hf0, n_routed_experts=16)
    cfg = FAMILIES[FAMILY].to_config(hf)
    params = weights.generator(FAMILY).param_tree(cfg, hf, "sym_int4", 5)
    rng = np.random.default_rng(9)
    x = jnp.asarray(rng.standard_normal((1, 7, cfg.hidden_size)),
                    jnp.float32)
    tree = {k_: v_ for k_, v_ in params["layers"].items() if k_ != "indexer"}
    layer = 1
    lp = ref._layer_tree(tree, layer)
    moe = ref._moe_fn(
        cfg.num_experts_per_tok, 0, cfg.moe_router_scale, cfg.norm_eps,
        tuple(tuple(tree[m].shape) for m in (
            "moe_gate_up", "moe_down", "shared_gate_up", "shared_down")),
        None)
    with jax.default_matmul_precision("highest"):
        want = np.asarray(moe(x[0], lp)[0] - x[0])

    def take(a, lo):
        return jax.tree_util.tree_map(lambda p: p[layer, lo:lo + 4], a)

    total = np.zeros_like(want)
    valid = jnp.ones((1, 7), bool)
    for share in range(4):
        cut = dataclasses.replace(cfg, moe_first_expert=4 * share,
                                  moe_experts_held=4)
        lp_cut = {k_: (take(v_, 4 * share)
                       if k_ in ("moe_gate_up", "moe_down")
                       else jax.tree_util.tree_map(lambda p: p[layer], v_))
                  for k_, v_ in tree.items()}
        out, stats = decoder._held_moe_block(cut, lp_cut, x, valid,
                                             shared=share == 0)
        total += np.asarray(out[0], np.float32)
        assert int(stats[0]) == 7 * cfg.num_experts_per_tok
    # bf16 matmul operands in the program against float32 in the reference
    np.testing.assert_allclose(total, want, atol=0.04, rtol=0.04)
    assert np.abs(want).mean() > 0.05


# -- the family --------------------------------------------------------------------

def test_the_family_reads_its_layers_its_share_and_its_bias():
    hf = published()
    cfg = FAMILIES[FAMILY].to_config(hf)
    assert (cfg.num_experts, cfg.moe_experts_held, cfg.moe_first_expert) == (
        256, 16, 0)
    assert cfg.moe_score_bias and cfg.moe_score_func == "sigmoid"
    assert cfg.moe_n_group == 1 and cfg.moe_router_scale == 2.5
    assert (cfg.head_dim, cfg.v_dim, cfg.kv_lora_rank, cfg.q_lora_rank) == (
        256, 256, 512, 2048)
    assert cfg.rope.base == 8000000 and cfg.rope.kind == "default"
    assert cfg.attn_scale == 256 ** -0.5
    assert (cfg.index_n_heads, cfg.index_head_dim) == (32, 128)
    # the published 78 layers, from the frequency keys alone
    full = dict(hf, num_hidden_layers=78, first_k_dense_replace=3)
    del full["indexer_types"], full["mlp_layer_types"]
    types = FAMILIES[FAMILY].to_config(full).indexer_types
    assert types[:8] == ("full", "full", "full", "shared", "shared",
                         "shared", "full", "shared")
    assert types.count("full") == 3 + 18 and types[-3:] == ("shared",) * 3
    with pytest.raises(ValueError, match="noaux_tc"):
        FAMILIES[FAMILY].to_config(dict(hf, topk_method="none"))
    with pytest.raises(ValueError, match="start with a full one"):
        FAMILIES[FAMILY].to_config(dict(hf, indexer_types=["shared"] * 9))
    # and the family that has no bias points here
    axk1 = run.hf_config(run.load_json(
        os.path.join(BENCH, "configs", "a.x-k1.int4.ep16.json")))
    with pytest.raises(ValueError, match="glm_moe_dsa"):
        FAMILIES["axk1"].to_config(dict(axk1, topk_method="noaux_tc"))


@pytest.mark.parametrize("lower,passes", [("bf16", True), ("fp8", False),
                                          ("no_rope", False)])
def test_the_references_limits_tell_rounding_from_a_fault(tiny, served, lower,
                                                          passes):
    """What the served path returned, held to the reference computed one
    way lower: bf16 rounding between operations (the configuration's own)
    stays inside the limits, fp8 activations and a dropped rotary slice do
    not."""
    hf, _, params, ref = tiny
    name = "several_pages"
    tokens, lps = served["alone"][name]
    ids = prompt(name)
    res = ref.compare(ref.logprobs(hf, params, ids + tokens[:-1],
                                   lower=lower), len(ids), tokens, lps)
    assert res["ok"] == passes, res
    assert res["index_tie"] == ref.INDEX_TIE and res["index_ties"] >= 0


# -- pages under one block table ---------------------------------------------------

def _drive(eng, req, ticks=3000):
    """Synchronous engine drive (no thread): deterministic tick by tick."""
    eng.submit(req)
    for _ in range(ticks):
        eng._tick()
        if req.finish_reason is not None:
            return list(stream_tokens(req, timeout=5))
    raise AssertionError("request never finished")


def test_both_kinds_of_page_are_reused_spilled_and_rolled_back(tiny):
    """Prefix reuse, the host spill tier and a transient fault's rollback
    over a SPARSE latent pool: a prompt asked again rides its cached pages,
    index keys with latent rows; under pool pressure both demote to the host
    and come back byte-identical; a tick that faults is rolled back and
    retried to the same stream."""
    from ipex_llm_tpu.hostutil import d2h
    from ipex_llm_tpu.serving.engine import _chain_hashes
    from ipex_llm_tpu.serving.faults import FaultInjector, TransientFault

    _, cfg, params, _ = tiny
    ec = dict(max_rows=2, max_seq_len=128, page_size=PAGE, prefill_bucket=32,
              pool_pages=11, retry_backoff_s=0.001)
    ids = prompt("several_pages")
    rng = np.random.default_rng(21)
    others = [[int(i) for i in rng.integers(3, 256, 70)] for _ in range(4)]

    plain = ServingEngine(cfg, params, EngineConfig(**ec))
    want = _drive(plain, Request(prompt_ids=ids, max_new_tokens=NEW))
    again = _drive(plain, Request(prompt_ids=ids, max_new_tokens=NEW))
    assert again == want and plain.metrics["prefix_hits"] == 1
    assert plain.metrics["prefix_pages_shared"] == 4      # 64 of 70 tokens

    eng = ServingEngine(cfg, params,
                        EngineConfig(kv_spill_bytes=1 << 22, **ec))
    assert _drive(eng, Request(prompt_ids=ids, max_new_tokens=NEW)) == want

    def page_bytes(pid):
        k, v = eng.cache.gather_pages(np.asarray([pid], np.int32))
        return d2h(k).tobytes(), d2h(v).tobytes()

    keys = _chain_hashes(np.asarray(ids, np.int32), PAGE)
    before = {k: page_bytes(eng.alloc.prefix[k]) for k in keys[:2]}
    for rows, index in before.values():
        assert len(rows) == PAGE * cfg.num_layers * 128 * 2
        assert len(index) == PAGE * cfg.index_layers * 32 * 2
        assert any(rows) and any(index)
    for o in others:                      # pool pressure: demote them
        _drive(eng, Request(prompt_ids=o, max_new_tokens=NEW))
    assert eng.pagestore.stats()["spill_pages"] > 0
    assert all(k not in eng.alloc.prefix for k in before)
    assert _drive(eng, Request(prompt_ids=ids, max_new_tokens=NEW)) == want
    assert eng.pagestore.stats()["swap_ins"] >= len(before)
    for k, pair in before.items():
        assert page_bytes(eng.alloc.prefix[k]) == pair

    faulty = ServingEngine(
        cfg, params, EngineConfig(**ec),
        fault_injector=FaultInjector().inject("decode-dispatch",
                                              TransientFault, nth=3))
    assert _drive(faulty, Request(prompt_ids=ids, max_new_tokens=NEW)) == want
    assert faulty.metrics["retries"] >= 1
    # a rolled-back tick's device counters are dropped with it: 75 queries
    # at slots 0..74, six layers, counted once
    assert faulty.metrics["dsa_tokens_visible_total"] == sum(
        range(1, 76)) * cfg.num_layers
    # the hand-over's wire format is K/V-shaped: refused by name
    with pytest.raises(NotImplementedError, match="latent"):
        plain.export_prefix(ids)


# -- one tick program family -------------------------------------------------------

class _Compiles(logging.Handler):
    def __init__(self):
        super().__init__(logging.DEBUG)
        self.names = []

    def emit(self, record):
        m = re.match(r"Compiling (?:jit\()?([\w.<>]+)", record.getMessage())
        if m:
            self.names.append(m.group(1))


@contextlib.contextmanager
def _logged_compiles():
    """The names of the programs compiled inside the block, as they come."""
    seen = _Compiles()
    log = logging.getLogger("jax._src.interpreters.pxla")
    log.addHandler(seen)
    try:
        with jax.log_compiles():
            yield seen.names
    finally:
        log.removeHandler(seen)


def _programs_of_a_wave(cfg, params, vocab) -> list[str]:
    """The programs a fresh engine compiles for one scenario: three prompts
    joining at once, a fourth beside decoding rows, completions."""
    rng = np.random.default_rng(38)
    # (a table width no other test of this file uses: jit's cache is the
    # process's, and a program an earlier test compiled would not be counted)
    eng = ServingEngine(cfg, params, EngineConfig(
        **dict(ENGINE, max_seq_len=112)))
    with _logged_compiles() as names:
        reqs = [Request(prompt_ids=[int(i) for i in rng.integers(
            3, vocab, n)], max_new_tokens=m)
            for n, m in ((20, 12), (45, 6), (70, 9))]
        for r in reqs:
            eng.submit(r)
        for _ in range(4):
            eng._tick()
        reqs.append(Request(prompt_ids=[int(i) for i in rng.integers(
            3, vocab, 33)], max_new_tokens=5))
        eng.submit(reqs[-1])
        for _ in range(400):
            if all(r.finish_reason is not None for r in reqs):
                break
            eng._tick()
    assert all(r.finish_reason == "length" for r in reqs)
    return names


def test_the_indexer_rides_the_one_tick_program_and_adds_none(tiny):
    """The same scenario on a family WITHOUT an indexer (the A.X-K1 tiny
    model: the latent pool as it was) and on this one: the same programs by
    name and number (this scenario's 4 tick programs: three rows joining,
    one joining beside decoding rows, a lone chunk, the steady tick; and
    the unpack), nothing beside them."""
    _, cfg, params, _ = tiny
    gen = weights.generator("axk1")
    hf_a = gen.tiny(run.hf_config(run.load_json(
        os.path.join(BENCH, "configs", "a.x-k1.int4.ep16.json"))))
    cfg_a = FAMILIES["axk1"].to_config(hf_a)
    without = _programs_of_a_wave(cfg_a, gen.param_tree(cfg_a, hf_a,
                                                        "sym_int4", 11), 256)
    with_indexer = _programs_of_a_wave(cfg, params, 256)
    # (the unpack is one program a PROCESS: whichever engine meets this
    # layout first compiles it, an earlier test's perhaps)
    for names in (without, with_indexer):
        assert set(names) <= {"_ragged_tick", "unpack_rows"}
        assert names.count("_ragged_tick") == 4
        assert names.count("unpack_rows") <= 1
