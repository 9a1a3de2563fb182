"""``model_type: axk1`` (SKT A.X-K1) on the serving path, at a tiny size on
the CPU: one dense layer and two expert layers, 16 experts in 4 groups of
which 2 stay, seeded random weights drawn by the benchmark's generator
(perfbench/generators/axk1.py).  ``ServingEngine`` through the LATENT pool is
held to the benchmark's plain reference (perfbench/references/axk1.py, which
imports nothing of the program): prefill then decode, one page and several,
chunks across page edges, a row alone and in a full batch.  Beside it what
the family forced, each against its own oracle: absorbed = expanded on the
same latent, the latent page's bytes, group-limited routing against a
plain-numpy router, the YaRN softmax scale, and the share test."""

from __future__ import annotations

import dataclasses
import importlib
import math
import os
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

CONFIG = "a.x-k1.int4.ep16"
PAGE = 16
NEW = 6
# the prompts of the served cases: under one page; a cache over several
# pages (its 32-token chunks each span two pages, the last one partial); two
# more to fill the batch (four rows joining together take 8-token chunks,
# whose edges fall inside pages)
PROMPTS = {"one_page": 10, "several_pages": 70, "filler_a": 45,
           "filler_b": 33}


def published() -> dict:
    return run.hf_config(run.load_json(
        os.path.join(BENCH, "configs", CONFIG + ".json")))


@pytest.fixture(scope="module")
def tiny():
    """(hf config, ModelConfig, params, reference module) of the tiny model."""
    gen = weights.generator("axk1")
    hf = gen.tiny(published())
    cfg = FAMILIES["axk1"].to_config(hf)
    return hf, cfg, gen.param_tree(cfg, hf, "sym_int4", 11), \
        importlib.import_module("references.axk1")


def prompt(name: str, vocab: int = 256) -> list[int]:
    rng = np.random.default_rng(sorted(PROMPTS).index(name))
    return [int(i) for i in rng.integers(3, vocab, PROMPTS[name])]


@pytest.fixture(scope="module")
def served(tiny):
    """Every prompt served ALONE, then all four at once (a full batch)."""
    _, cfg, params, _ = tiny
    eng = ServingEngine(cfg, params, EngineConfig(
        max_rows=4, max_seq_len=128, page_size=PAGE, prefill_bucket=32,
        step_token_budget=32)).start()
    try:
        out = {"alone": {}, "batch": {}}
        for name in PROMPTS:
            req = eng.submit(Request(prompt_ids=prompt(name),
                                     max_new_tokens=NEW))
            out["alone"][name] = (list(stream_tokens(req, timeout=300)),
                                  [float(x) for x in req.logprobs])
        reqs = {name: eng.submit(Request(prompt_ids=prompt(name),
                                         max_new_tokens=NEW))
                for name in PROMPTS}
        for name, req in reqs.items():
            out["batch"][name] = (list(stream_tokens(req, timeout=300)),
                                  [float(x) for x in req.logprobs])
        out["kv"] = eng.kv_stats()
        out["metrics"] = dict(eng.metrics)
        out["state"] = jax.tree_util.tree_map(np.asarray, eng.cache.state)
    finally:
        eng.stop()
    return out


@pytest.mark.parametrize("how", ["alone", "batch"])
@pytest.mark.parametrize("name", sorted(PROMPTS))
def test_served_through_the_latent_pool_matches_the_reference(
        tiny, served, name, how):
    hf, _, params, ref = tiny
    tokens, lps = served[how][name]
    assert len(tokens) == NEW == len(lps)
    ids = prompt(name)
    res = ref.compare(ref.logprobs(hf, params, ids + tokens[:-1]), len(ids),
                      tokens, lps)
    assert res["ok"], res
    # far inside the limits at this size: float32 against bf16 activations
    assert res["logprob_err"] < 0.08, res


@pytest.mark.parametrize("name", sorted(PROMPTS))
def test_a_row_does_not_depend_on_its_batch(served, name):
    alone, batch = served["alone"][name], served["batch"][name]
    assert alone[0] == batch[0]
    np.testing.assert_allclose(alone[1], batch[1], atol=2e-2)


def test_the_latent_page_is_576_values_a_token_a_layer():
    hf = published()
    cfg = FAMILIES["axk1"].to_config(hf)
    assert kv.pool_geometry(cfg) == (1, 512, 64)
    page = kv.model_page_bytes(cfg, 128)
    assert page == 128 * 8 * (512 + 64) * 2 == 128 * 8 * 1152
    # 35.6 times fewer bytes than per-head K (192) and V (128)
    per_head = kv.paged_page_bytes(8, 64, 128, 192, v_head_dim=128)
    assert per_head / page == pytest.approx(35.56, abs=0.01)
    pool = kv.PagedKVCache.for_model(cfg, 4, 2, 8, 128)
    assert pool.latent and pool.k.shape == (8, 4, 1, 128, 512)
    # the rotated key token-minor: a page of it is whole tiles
    assert pool.v.shape == (8, 4, 1, 64, 128)
    assert pool.page_bytes == page and pool.pool_bytes == 4 * page
    assert pool.k.nbytes + pool.v.nbytes == pool.pool_bytes


def test_the_engine_accounts_for_latent_pages(tiny, served):
    _, cfg, _, _ = tiny
    stats = served["kv"]
    row = (cfg.kv_lora_rank + cfg.qk_rope_head_dim) * 2
    assert stats["latent_bytes_per_token"] == row
    assert stats["page_bytes"] == PAGE * cfg.num_layers * row
    assert stats["pool_bytes"] == stats["pages_total"] * stats["page_bytes"]
    assert stats["prefix_cache"] == "on"
    # the held layers' counters ride the tick: 2 expert layers a pass
    m = served["metrics"]
    assert m["moe_layer_calls_total"] > 0
    assert m["moe_pairs_routed_total"] % cfg.num_experts_per_tok == 0
    assert 0 < m["moe_pairs_local_total"] < m["moe_pairs_routed_total"]
    # off the chip a loop over the hit experts: each multiplies every row
    assert m["moe_pairs_local_total"] <= m["moe_rows_computed_total"]
    assert set(served["state"]) == {"stats"}


@pytest.mark.parametrize("what", ["fp8", "mesh"])
def test_what_a_latent_pool_cannot_do_is_refused_by_name(tiny, what):
    _, cfg, params, _ = tiny
    if what == "fp8":
        with pytest.raises(ValueError, match="latent.*bf16 only"):
            ServingEngine(cfg, params, EngineConfig(
                max_rows=2, max_seq_len=64, page_size=PAGE,
                kv_storage="fp8"))
        with pytest.raises(ValueError, match="unknown kv storage"):
            kv.pool_geometry(cfg, "int3")
    else:
        from jax.sharding import Mesh

        mesh = Mesh(np.asarray(jax.devices()[:2]), ("tp",))
        with pytest.raises(ValueError, match="latent.*mesh is not supported"):
            ServingEngine(cfg, params, EngineConfig(
                max_rows=2, max_seq_len=64, page_size=PAGE), mesh=mesh)


# -- the two forms of latent attention -------------------------------------------

def _latent_case(seed=0, rows=3, t=5, heads=4, rank=32, rope=16, nope=8,
                 vdim=8, pages=10, ps=8):
    rng = np.random.default_rng(seed)

    def draw(*shape):
        return jnp.asarray(rng.standard_normal(shape), jnp.float32)

    w_uk, w_uv = draw(rank, heads, nope), draw(rank, heads, vdim)
    # the third row's cache takes more than one trip of the walk (4 pages)
    kv_len = np.asarray([t + 9, t, t + 57][:rows], np.int32)
    cache = kv.PagedKVCache.init(1, rows * pages + 1, rows, pages, 1, ps,
                                 rank, v_head_dim=rope, latent=True)
    cache = dataclasses.replace(cache, tables=jnp.asarray(
        1 + np.arange(rows * pages).reshape(rows, pages), jnp.int32))
    total = pages * ps
    # (bf16 values: the pool holds them exactly)
    c_all, r_all = (draw(rows, total, d).astype(jnp.bfloat16).astype(
        jnp.float32) for d in (rank, rope))
    k, v = cache.write_layer(cache.k, cache.v, 0, c_all[:, :, None, :],
                             r_all[:, :, None, :],
                             jnp.zeros((rows,), jnp.int32))
    return dict(w_uk=w_uk, w_uv=w_uv, kv_len=jnp.asarray(kv_len),
                cache=cache, k=k, v=v, c_all=c_all, r_all=r_all,
                q_nope=draw(rows, t, heads, nope),
                q_rope=draw(rows, t, heads, rope), t=t, scale=0.17)


def _expanded_oracle(case, row, query, pos):
    """Plain per-head attention of one query at slot ``pos`` over the row's
    keys [0, pos], K and V expanded from the latent."""
    c = np.asarray(case["c_all"][row, : pos + 1], np.float64)
    r = np.asarray(case["r_all"][row, : pos + 1], np.float64)
    k_nope = np.einsum("sc,chd->shd", c, np.asarray(case["w_uk"], np.float64))
    val = np.einsum("sc,chd->shd", c, np.asarray(case["w_uv"], np.float64))
    qn = np.asarray(case["q_nope"][row, query], np.float64)
    qr = np.asarray(case["q_rope"][row, query], np.float64)
    s = (np.einsum("hd,shd->hs", qn, k_nope)
         + np.einsum("hr,sr->hs", qr, r)) * case["scale"]
    p = np.exp(s - s.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    return np.einsum("hs,shd->hd", p, val)


def _run_latent(case, kernels: bool, monkeypatch):
    from ipex_llm_tpu.ops import dispatch

    monkeypatch.setattr(dispatch, "use_pallas", lambda op=None: kernels)
    q_lat = jnp.einsum("bthd,chd->bthc", case["q_nope"], case["w_uk"])
    o_lat = attention.latent_attention(
        q_lat, case["q_rope"], case["k"], case["v"], case["cache"],
        case["kv_len"], jnp.full((q_lat.shape[0],), case["t"], jnp.int32),
        scale=case["scale"], layer=0)
    return np.asarray(jnp.einsum("bthc,chd->bthd",
                                 o_lat.astype(jnp.float32), case["w_uv"]))


@pytest.mark.parametrize("t", [1, 5], ids=["decode", "chunk"])
@pytest.mark.parametrize("kernels", [False, True],
                         ids=["xla", "pallas-interpret"])
def test_absorbed_equals_expanded_on_the_same_latent(kernels, t, monkeypatch):
    """The absorbed walk (a decode query over its whole cache; a chunk's
    queries over the cached prefix and the chunk's own keys, causal) against
    plain per-head attention with K and V expanded from the same latent."""
    case = _latent_case(seed=t, t=t)
    got = _run_latent(case, kernels, monkeypatch)
    tol = 3e-2 if kernels else 1e-4     # the kernels take bf16 operands
    for row in range(got.shape[0]):
        for j in range(t):
            pos = int(case["kv_len"][row]) - t + j
            np.testing.assert_allclose(
                got[row, j], _expanded_oracle(case, row, j, pos), atol=tol,
                rtol=tol)


def test_a_tokens_bits_do_not_depend_on_how_its_prompt_is_chunked(
        monkeypatch):
    """The walk's arithmetic for a query is a function of its own q and the
    pages before it: the last 3 tokens of a row attended as a chunk of 3, and
    the very last one as a chunk of 1, give the same BITS (interpret mode;
    on the chip an expanded form for the chunk's own keys broke this)."""
    from ipex_llm_tpu.ops.pallas import ragged_paged_attention as rpa

    case = _latent_case(seed=4, t=3)
    q_lat = jnp.einsum("bthd,chd->bthc", case["q_nope"],
                       case["w_uk"]).astype(jnp.bfloat16)
    q_rope = case["q_rope"].astype(jnp.bfloat16)
    args = (case["k"], case["v"], case["cache"].tables, case["kv_len"])
    three = rpa.latent_paged_sdpa(q_lat, q_rope, *args, scale=0.17, layer=0)
    one = rpa.latent_paged_sdpa(q_lat[:, 2:], q_rope[:, 2:], *args,
                                scale=0.17, layer=0)
    np.testing.assert_array_equal(np.asarray(three[:, 2], np.float32),
                                  np.asarray(one[:, 0], np.float32))


def test_a_held_layers_bits_do_not_depend_on_where_the_prompt_is_chunked(
        tiny, kernel_forced, monkeypatch):
    """The held expert layer has two forms, chosen by a block's static N:
    a prompt served alone prefills in chunks of 256 and 44 (rows grouped by
    expert, then one row tile), in a wave of three in chunks of 64 (one row
    tile) beside decoding rows.  Same tokens, same logprob bits, kernels
    forced; both forms were traced."""
    from ipex_llm_tpu.ops import moe as moe_ops
    from test_serving_mixed import _assert_rows_do_not_depend_on_batch

    _, cfg, params, _ = tiny
    seen = {"rows": set(), "slot": set()}
    for form, name in (("rows", "_held_by_rows"), ("slot", "_held_by_slot")):
        inner = getattr(moe_ops, name)

        def spy(h, *a, _inner=inner, _form=form, **kw):
            seen[_form].add(h.shape[0])
            return _inner(h, *a, **kw)

        monkeypatch.setattr(moe_ops, name, spy)
    rng = np.random.default_rng(41)
    wave = [dict(prompt_ids=[int(i) for i in rng.integers(3, 256, n)],
                 max_new_tokens=4) for n in (300, 210, 150)]
    eng, _ = _assert_rows_do_not_depend_on_batch(
        cfg, params, EngineConfig(
            max_rows=4, max_seq_len=384, page_size=PAGE, prefill_bucket=256,
            step_token_budget=256),
        {0: wave[:1], 1: wave[1:2], 2: wave[2:]})
    assert 256 in seen["rows"] and all(n > 128 for n in seen["rows"])
    assert seen["slot"] and all(n <= 128 for n in seen["slot"])
    m = eng.metrics
    assert 0 < m["moe_pairs_local_total"] <= m["moe_rows_computed_total"]


def _tick_programs(cfg, params, vocab) -> list[str]:
    """The programs a fresh engine compiles for one scenario: a 200-token
    prompt in one chunk, a second prompt joining beside the decoding row,
    completions."""
    from test_glm_dsa import _logged_compiles

    rng = np.random.default_rng(41)
    eng = ServingEngine(cfg, params, EngineConfig(
        max_rows=2, max_seq_len=304, page_size=PAGE, prefill_bucket=256,
        step_token_budget=256))
    with _logged_compiles() as names:
        reqs = [Request(prompt_ids=[int(i) for i in rng.integers(
            3, vocab, 200)], max_new_tokens=6)]
        eng.submit(reqs[0])
        for _ in range(3):
            eng._tick()
        reqs.append(Request(prompt_ids=[int(i) for i in rng.integers(
            3, vocab, 40)], max_new_tokens=4))
        eng.submit(reqs[1])
        for _ in range(200):
            if all(r.finish_reason is not None for r in reqs):
                break
            eng._tick()
    assert all(r.finish_reason == "length" for r in reqs)
    return names


def test_an_expert_layer_adds_no_tick_program(tiny, kernel_forced):
    """The same scenario on a dense family and on this one, kernels forced:
    the same programs by name and number.  The held layer's two forms ride
    the tick programs that were there (a chunk of 256: rows grouped by
    expert; the decode stage: one row tile), and a dense family's ticks
    never enter ops/moe.py."""
    from test_decoder import rand_params, tiny_cfg

    _, cfg, params, _ = tiny
    dense = tiny_cfg(vocab_size=256, hidden_size=48, intermediate_size=96,
                     num_heads=4, num_kv_heads=2, head_dim=12,
                     max_position_embeddings=512)
    without = _tick_programs(dense, rand_params(dense, qtype="sym_int4"), 256)
    with_experts = _tick_programs(cfg, params, 256)
    for names in (without, with_experts):
        assert set(names) <= {"_ragged_tick", "unpack_rows"}
        assert names.count("unpack_rows") <= 1
    assert without.count("_ragged_tick") == with_experts.count(
        "_ragged_tick") > 0


def test_an_idle_row_walks_nothing_and_gives_zeros():
    from ipex_llm_tpu.ops.pallas import ragged_paged_attention as rpa

    case = _latent_case(seed=2, t=1)
    q_lat = jnp.einsum("bthd,chd->bthc", case["q_nope"], case["w_uk"])
    o = rpa.latent_paged_sdpa(
        q_lat.astype(jnp.bfloat16), case["q_rope"].astype(jnp.bfloat16),
        case["k"], case["v"], case["cache"].tables, case["kv_len"],
        jnp.asarray([1, 0, 1], jnp.int32), scale=0.1, layer=0)
    assert not np.asarray(o[1]).any() and np.asarray(o[0]).any()


@pytest.mark.parametrize("t", [1, 5, 19])
def test_the_latent_write_kernels_put_the_scatters_bytes(t):
    """ops/pallas/paged_kv_write.py on a latent pool (interpret mode): ``c``
    rows like K rows, the rotated key along the lanes of its token-minor
    page, against kv.py's scatter."""
    from ipex_llm_tpu.ops.pallas import paged_kv_write as pkw

    rng = np.random.default_rng(t)
    rows, pages, ps, rank, rope = 3, 4, 8, 32, 16
    cache = kv.PagedKVCache.init(2, rows * pages + 1, rows, pages, 1, ps,
                                 rank, v_head_dim=rope, latent=True)
    tables = 1 + np.arange(rows * pages).reshape(rows, pages)
    tables[2, 3] = -1                     # an unbacked page: to scratch
    cache = dataclasses.replace(cache, tables=jnp.asarray(tables, jnp.int32),
                                k=cache.k + 1, v=cache.v + 2)
    new_c = jnp.asarray(rng.standard_normal((rows, t, 1, rank)), jnp.bfloat16)
    new_r = jnp.asarray(rng.standard_normal((rows, t, 1, rope)), jnp.bfloat16)
    pos = jnp.asarray([0, 5, 13], jnp.int32)
    want = cache.write_layer(cache.k, cache.v, 1, new_c, new_r, pos)
    got = pkw.paged_kv_write(cache, cache.k, cache.v, 1, new_c, new_r, pos)
    for w, g in zip(want, got):           # all but the scratch page
        np.testing.assert_array_equal(np.asarray(w[:, 1:], np.float32),
                                      np.asarray(g[:, 1:], np.float32))


# -- routing ----------------------------------------------------------------------

def _numpy_router(logits, n_group, topk_group, top_k, scaling):
    """The published rule, a token at a time in float64."""
    sigma = 1.0 / (1.0 + np.exp(-logits.astype(np.float64)))
    ids, ws = [], []
    for s in sigma:
        groups = s.reshape(n_group, -1)
        score = np.sort(groups, -1)[:, -2:].sum(-1)
        stay = np.argsort(-score, kind="stable")[:topk_group]
        eligible = np.zeros_like(groups)
        eligible[stay] = groups[stay]
        chosen = np.argsort(-eligible.reshape(-1), kind="stable")[:top_k]
        ids.append(np.sort(chosen))
        w = s[np.sort(chosen)]
        ws.append(w / w.sum() * scaling)
    return np.asarray(ids), np.asarray(ws)


@pytest.mark.parametrize("held", ["all", "share"])
def test_group_limited_routing_against_a_plain_numpy_router(tiny, held):
    hf, cfg, _, _ = tiny
    if held == "all":
        cfg = dataclasses.replace(cfg, moe_first_expert=0,
                                  moe_experts_held=cfg.num_experts)
    rng = np.random.default_rng(5)
    logits = rng.standard_normal((2, 9, cfg.num_experts)).astype(np.float32)
    w, idx = decoder._route(cfg, {}, jnp.asarray(logits))
    want_ids, want_w = _numpy_router(
        logits.reshape(-1, cfg.num_experts), cfg.moe_n_group,
        cfg.moe_topk_group, cfg.num_experts_per_tok, cfg.moe_router_scale)
    order = np.argsort(np.asarray(idx).reshape(-1, idx.shape[-1]), -1)
    got_ids = np.take_along_axis(
        np.asarray(idx).reshape(-1, idx.shape[-1]), order, -1)
    got_w = np.take_along_axis(
        np.asarray(w).reshape(-1, w.shape[-1]), order, -1)
    np.testing.assert_array_equal(got_ids, want_ids)
    np.testing.assert_allclose(got_w, want_w, rtol=1e-5)
    assert (cfg.moe_n_group, cfg.moe_topk_group) == (4, 2)
    # only experts of the groups that stay are chosen: 2 groups of 4
    assert all(len({i // 4 for i in row}) <= 2 for row in got_ids)


def _uncut(tiny_hf):
    """The tiny model holding ALL 16 experts, and its tree."""
    hf = dict(tiny_hf, n_routed_experts=16, n_router_experts=16,
              first_routed_expert=0)
    cfg = FAMILIES["axk1"].to_config(hf)
    return hf, cfg, weights.generator("axk1").param_tree(
        cfg, hf, "sym_int4", 3)


def test_the_shares_of_a_layer_add_up_to_the_uncut_layer(tiny):
    """THE SHARE TEST: an expert layer cut into 8 shares of 2 experts, each
    computed by the program's held layer, the shared expert counted once:
    their sum is the uncut reference's layer output."""
    hf0, _, _, ref = tiny
    hf, cfg, params = _uncut(hf0)
    rng = np.random.default_rng(9)
    x = jnp.asarray(rng.standard_normal((1, 7, cfg.hidden_size)),
                    jnp.float32)
    tree = params["layers"]
    layer = 1
    lp = ref._layer_tree(tree, layer)
    moe = ref._moe_fn(
        cfg.num_experts_per_tok, 0, cfg.moe_n_group, cfg.moe_topk_group,
        cfg.moe_router_scale, cfg.norm_eps,
        tuple(tuple(tree[m].shape) for m in (
            "moe_gate_up", "moe_down", "shared_gate_up", "shared_down")),
        None)
    with jax.default_matmul_precision("highest"):
        want = np.asarray(moe(x[0], lp)[0] - x[0])

    def take(a, lo):
        return jax.tree_util.tree_map(lambda p: p[layer, lo:lo + 2], a)

    total = np.zeros_like(want)
    valid = jnp.ones((1, 7), bool)
    for share in range(8):
        cut = dataclasses.replace(cfg, moe_first_expert=2 * share,
                                  moe_experts_held=2)
        lp_cut = {k_: (take(v_, 2 * share)
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


# -- YaRN ---------------------------------------------------------------------------

YARN = {"type": "yarn", "factor": 32, "mscale": 1, "mscale_all_dim": 1,
        "beta_fast": 32, "beta_slow": 1,
        "original_max_position_embeddings": 4096}


@pytest.mark.parametrize("family,scaling,cos_factor,scale_factor", [
    ("axk1", YARN, 1.0, (0.1 * math.log(32) + 1) ** 2),
    ("deepseek_v3", dict(YARN, factor=40), 1.0,
     (0.1 * math.log(40) + 1) ** 2),
    # DeepSeek-V2's published pair: 0.707 on both
    ("deepseek_v2", dict(YARN, factor=40, mscale=0.707, mscale_all_dim=0.707),
     1.0, (0.0707 * math.log(40) + 1) ** 2),
    # no mscale_all_dim: cos and sin carry m(mscale), the scale stays
    ("deepseek_v2", {k: v for k, v in dict(YARN, factor=40).items()
                     if k != "mscale_all_dim"},
     0.1 * math.log(40) + 1, 1.0),
    ("deepseek_v3", None, 1.0, 1.0),
])
def test_the_yarn_softmax_scale(family, scaling, cos_factor, scale_factor):
    hf = dict(published(), model_type=family, rope_scaling=scaling)
    if family != "axk1":
        hf.pop("n_router_experts"), hf.pop("first_routed_expert")
        hf["topk_method"] = ("noaux_tc" if family == "deepseek_v3"
                             else "group_limited_greedy")
    cfg = FAMILIES[family].to_config(hf)
    assert cfg.rope.mscale(131072) == pytest.approx(cos_factor)
    assert cfg.attn_scale == pytest.approx(192 ** -0.5 * scale_factor)
    if family == "axk1":
        # the reference builds the same numbers from the config by itself
        ref = importlib.import_module("references.axk1")
        inv, cos_f, scale_f = ref.yarn_frequencies(hf)
        assert (cos_f, scale_f) == (pytest.approx(cos_factor),
                                    pytest.approx(scale_factor))
        np.testing.assert_allclose(inv, cfg.rope.inv_freq(131072), rtol=1e-6)
        assert inv[0] == pytest.approx(1.0) and inv[-1] < 1e-4 / 32 * 1.4


def test_minicpm3_longrope_stays_as_it_was():
    hf = dict(published(), model_type="minicpm3", rope_scaling={
        "type": "longrope", "short_factor": [1.0] * 32,
        "long_factor": [2.0] * 32,
        "original_max_position_embeddings": 4096})
    for key in ("n_routed_experts", "n_router_experts", "first_routed_expert",
                "n_group", "topk_group"):
        hf.pop(key, None)
    cfg = FAMILIES["minicpm3"].to_config(hf)
    assert cfg.rope.kind == "longrope" and cfg.rope.attention_factor is None


# -- the family ---------------------------------------------------------------------

def test_the_family_reads_a_chips_share_and_refuses_a_score_bias():
    hf = published()
    cfg = FAMILIES["axk1"].to_config(hf)
    assert cfg.is_mla and (cfg.num_experts, cfg.moe_first_expert,
                           cfg.moe_experts_held) == (192, 0, 12)
    assert (cfg.moe_n_group, cfg.moe_topk_group, cfg.moe_score_func,
            cfg.moe_group_score, cfg.moe_score_bias) == (
        8, 4, "sigmoid", "top2sum", False)
    assert cfg.moe_layer_start == 1 and cfg.moe_router_scale == 2.5
    whole = FAMILIES["axk1"].to_config(
        {k: v for k, v in hf.items()
         if k not in ("n_router_experts", "first_routed_expert")})
    assert whole.num_experts == whole.moe_experts_held == 12
    with pytest.raises(ValueError, match="topk_method"):
        FAMILIES["axk1"].to_config(dict(hf, topk_method="noaux_tc"))


@pytest.mark.parametrize("lower,passes", [("bf16", True), ("fp8", False),
                                          ("no_rope", False)])
def test_the_references_limits_tell_rounding_from_a_fault(tiny, served, lower,
                                                         passes):
    """The reference against itself, playing the server greedily: with the
    activations rounded to bf16 where the served path rounds them it passes
    its own limits; with the rotated slice dropped it fails them."""
    hf, _, params, ref = tiny
    ids = prompt("several_pages")
    tokens, lps = [], []
    for _ in range(4):
        rows, _ = ref.logprobs(hf, params, ids + tokens, lower=lower)
        tokens.append(int(np.argmax(rows[-1])))
        lps.append(float(rows[-1][tokens[-1]]))
    res = ref.compare(ref.logprobs(hf, params, ids + tokens[:-1]), len(ids),
                      tokens, lps)
    assert res["ok"] is passes, res


# -- the pool's other users follow the geometry -------------------------------------

def _drive(eng, req, ticks=3000):
    """Synchronous engine drive (no thread): deterministic tick by tick."""
    eng.submit(req)
    for _ in range(ticks):
        eng._tick()
        if req.finish_reason is not None:
            return list(stream_tokens(req, timeout=5))
    raise AssertionError("request never finished")


def test_latent_pages_are_reused_spilled_and_swapped_in_byte_for_byte(tiny):
    """Prefix reuse, the host spill tier and a transient fault's rollback
    over a LATENT pool: a prompt asked again rides its cached pages; under
    pool pressure they demote to the host and come back byte-identical; a
    tick that faults is rolled back and retried to the same stream."""
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
    row = (cfg.kv_lora_rank + cfg.qk_rope_head_dim) * 2
    assert all(len(kb) + len(vb) == PAGE * cfg.num_layers * row
               for kb, vb in before.values())
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
    # the hand-over's wire format is K/V-shaped: refused by name
    with pytest.raises(NotImplementedError, match="latent"):
        plain.export_prefix(ids)


def test_a_client_that_leaves_during_prefill_frees_its_row_at_once():
    """api_server's wait for a stream's FIRST token polls the connection: a
    long prompt abandoned by its client is aborted there, not at the first
    write after the whole prompt was prefilled for nobody (the warm-up's cut
    replay of 64 long prompts held every row of the engine for a minute)."""
    import asyncio
    import types

    from ipex_llm_tpu.serving.api_server import OpenAIServer

    aborted = []
    req = Request(prompt_ids=[1, 2, 3], max_new_tokens=4)

    class Engine:
        def abort(self, r):
            aborted.append(r)
            r.stream_queue.put(None)      # as the engine ends a stream

    server = types.SimpleNamespace(engine=Engine())

    async def next_tok(r):                 # the blocking fetch, as a thread
        loop = asyncio.get_running_loop()
        return await loop.run_in_executor(None, r.stream_queue.get)

    server._next_tok = next_tok
    closing = types.SimpleNamespace(
        transport=types.SimpleNamespace(is_closing=lambda: True))
    with pytest.raises(ConnectionResetError, match="prefilling"):
        asyncio.run(OpenAIServer._first_tok(server, closing, req))
    assert aborted == [req]
    # a connection that stays gets its token
    live = types.SimpleNamespace(
        transport=types.SimpleNamespace(is_closing=lambda: False))
    req2 = Request(prompt_ids=[1], max_new_tokens=1)
    req2.stream_queue.put(7)
    assert asyncio.run(OpenAIServer._first_tok(server, live, req2)) == 7
