"""``model_type: glm5_next_text`` (GLM-5.3) on the serving path, at a tiny size
on the CPU: a KDA layer with a dense MLP, then a sparse-attention layer and a
KDA layer with expert layers (16 experts, 4 held), four hyper-connected
streams, ``index_topk`` 16 over blocks of 4, pages of 16, seeded random
weights drawn by the benchmark's generator
(perfbench/generators/glm5_next_text.py).  ``ServingEngine`` through the one
layer walker (``decoder.run_layers``: a mixer kind a layer), the sparse latent
pool and the two-buffer recurrent state is held to the benchmark's plain
reference (perfbench/references/glm5_next_text.py, which imports nothing of
the program and computes the recurrence token by token): chunked prefill then
decode, LOGITS compared, contexts past ``index_topk / index_kpool`` blocks so
that a selection and a tail both occur, a row alone and in a full batch.
Beside it what the family forced, each against its own oracle."""

from __future__ import annotations

import dataclasses
import importlib
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
from ipex_llm_tpu.ops import hyper, kda  # noqa: E402
from ipex_llm_tpu.serving.engine import (EngineConfig, Request,  # noqa: E402
                                         ServingEngine, stream_tokens)

CONFIG = "glm-5.3-flash.int4.ep8"
FAMILY = "glm5_next_text"
PAGE = 16
NEW = 6
# under one block of the recurrence and under index_topk / kpool blocks; two
# blocks and a ragged end, past the selection; a whole number of blocks; one
# more to fill the batch
PROMPTS = {"short": 10, "ragged": 150, "blocks": 128, "filler": 70}
ENGINE = dict(max_rows=4, max_seq_len=256, page_size=PAGE, prefill_bucket=256,
              step_token_budget=256)


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


def _serve(eng, names) -> dict:
    reqs = {name: eng.submit(Request(prompt_ids=prompt(name),
                                     max_new_tokens=NEW)) for name in names}
    return {name: (list(stream_tokens(req, timeout=600)),
                   [float(x) for x in req.logprobs])
            for name, req in reqs.items()}


@pytest.fixture(scope="module")
def served(tiny):
    """Every prompt served ALONE, then all four at once (a full batch: the
    budget gives two rows two 64-token blocks each a tick, the others
    wait)."""
    _, cfg, params, _ = tiny
    eng = ServingEngine(cfg, params, EngineConfig(**ENGINE)).start()
    try:
        out = {"alone": {}}
        for name in PROMPTS:
            out["alone"].update(_serve(eng, [name]))
        out["batch"] = _serve(eng, list(PROMPTS))
        out["kv"] = eng.kv_stats()
        out["metrics"] = dict(eng.metrics)
    finally:
        eng.stop()
    return out


@pytest.mark.parametrize("how", ["alone", "batch"])
@pytest.mark.parametrize("name", sorted(PROMPTS))
def test_served_prefill_then_decode_matches_the_reference(tiny, served, name,
                                                          how):
    hf, _, params, ref = tiny
    tokens, lps = served[how][name]
    assert len(tokens) == NEW == len(lps)
    ids = prompt(name)
    res = ref.compare(ref.logprobs(hf, params, ids + tokens[:-1]), len(ids),
                      tokens, lps)
    assert res["ok"], res
    assert res["logprob_err"] < 0.1, res


@pytest.mark.parametrize("name", sorted(PROMPTS))
def test_a_row_does_not_depend_on_its_batch(served, name):
    """Alone a prompt goes in one piece; in the batch of four in 128-token
    pieces beside decoding rows, after waiting its turn: other ticks, another
    ``p_b``, the same blocks of the recurrence, the same tokens."""
    alone, batch = served["alone"][name], served["batch"][name]
    assert alone[0] == batch[0]
    np.testing.assert_allclose(alone[1], batch[1], atol=2e-2)


def test_the_engine_counts_the_recurrence_and_the_selection(tiny, served):
    _, cfg, _, _ = tiny
    m, total = served["metrics"], sum(PROMPTS.values())
    # every prompt twice, through the chunked form in both KDA layers
    assert m["kda_chunk_tokens_total"] == 2 * total * cfg.kda_layers
    assert m["kda_step_rows_total"] == 2 * len(PROMPTS) * (NEW - 1) * 2
    assert m["ssm_state_resets_total"] == 2 * len(PROMPTS)
    assert 0 < m["dsa_tokens_selected_total"] < m["dsa_tokens_visible_total"]
    assert m["dsa_shared_layer_calls_total"] == 0
    leaves = served["kv"]["state_leaves"]
    assert leaves["kda_s"] == [[2, 4, 2, 128, 128], "float32"]
    assert leaves["kda_conv"][0] == [2, 4, 3, 3 * 2 * 128]
    assert leaves["ix_tail"] == [[1, 4, 3, 32], "bfloat16"]
    assert served["kv"]["prefix_cache"] == "off: recurrent state"


# -- the chunked form against the sequential one ----------------------------------

def _kda_case(t: int, seed: int = 0, strength: float = 1.0, rows: int = 2,
              heads: int = 2, d: int = 128):
    ks = jax.random.split(jax.random.key(seed), 6)

    def l2(a):
        return a / jnp.linalg.norm(a, axis=-1, keepdims=True)

    shape = (rows, t, heads, d)
    return dict(
        s0=0.1 * jax.random.normal(ks[5], (rows, heads, d, d)),
        q=l2(jax.random.normal(ks[0], shape)),
        k=l2(jax.random.normal(ks[1], shape)),
        v=jax.random.normal(ks[2], shape),
        g=-strength * jax.random.uniform(ks[3], shape),
        beta=jax.nn.sigmoid(jax.random.normal(ks[4], (rows, t, heads))))


@pytest.mark.parametrize("strength", [0.05, 1.0, 5.0])
def test_the_chunked_form_is_the_sequential_recurrence(strength):
    """Over two blocks and a ragged end, from a state that is not zero, at
    mild decays and at the gate's lower bound (where a quotient of cumulative
    decays would overflow float32): outputs and state to float32 rounding."""
    c = _kda_case(150, strength=strength)
    o_seq, s_seq = kda.sequential(**c)
    o, s = jax.jit(kda.chunk)(c["s0"], c["q"], c["k"], c["v"], c["g"],
                              c["beta"])
    np.testing.assert_allclose(o, o_seq, atol=5e-6)
    np.testing.assert_allclose(s, s_seq, atol=5e-6)


@pytest.mark.parametrize("cuts", [(64,), (128,), (64, 128), (64, 128, 192)])
def test_a_prompt_cut_at_multiples_of_the_block_gives_the_same_bits(cuts):
    """Block by block: a 200-token prompt whole, and cut at several multiples
    of 64 (its ragged end apart) with the state handed on, gives bit-identical
    outputs and state."""
    c = _kda_case(200, seed=3)
    run_ = jax.jit(kda.chunk)
    whole_o, whole_s = run_(c["s0"], c["q"], c["k"], c["v"], c["g"],
                            c["beta"])
    s, outs = c["s0"], []
    for lo, hi in zip((0, *cuts), (*cuts, 200)):
        o, s = run_(s, *(c[n][:, lo:hi] for n in ("q", "k", "v", "g", "beta")))
        outs.append(o)
    assert np.array_equal(np.concatenate(outs, axis=1), whole_o)
    assert np.array_equal(s, whole_s)


def test_padding_leaves_the_state_as_it_is():
    c = _kda_case(64, seed=5)
    zeros = dict(g=jnp.zeros_like(c["g"]), beta=jnp.zeros_like(c["beta"]))
    _, s = jax.jit(kda.chunk)(c["s0"], c["q"], c["k"], c["v"], zeros["g"],
                              zeros["beta"])
    assert np.array_equal(s, c["s0"])
    _, s = kda.step(c["s0"], c["q"][:, 0], c["k"][:, 0], c["v"][:, 0],
                    zeros["g"][:, 0], zeros["beta"][:, 0])
    assert np.array_equal(s, c["s0"])


def test_the_kernel_runs_the_block_arithmetic_of_the_xla_form(monkeypatch):
    """``kda_chunk`` in interpret mode against the ``vmap`` form: the body is
    the same function, so the results agree to the last bits of a float32
    product's reordering."""
    from ipex_llm_tpu.ops.pallas import kda_chunk

    c = _kda_case(128, seed=7, rows=1)
    kb = c["k"] * c["beta"][..., None]
    ops = [x.transpose(0, 2, 1, 3) for x in (c["q"], c["k"], kb, c["v"],
                                             c["g"])]
    o, s = kda_chunk.chunk_rows(c["s0"], *ops)
    o_x, s_x = kda._chunk_xla(c["s0"], *ops)
    np.testing.assert_allclose(o, o_x, atol=1e-6)
    np.testing.assert_allclose(s, s_x, atol=1e-6)


def test_mosaic_takes_the_chunk_kernel(monkeypatch):
    """``kda_chunk`` compiled for a DESCRIBED v5e (none attached) at the
    cell's shapes: what Mosaic refuses shows here, at no chip time."""
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from ipex_llm_tpu.ops.pallas import kda_chunk

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    chip = SingleDeviceSharding(topo.devices[0])
    monkeypatch.setattr(kda_chunk, "_interpret", lambda: False)

    def arg(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=chip)

    for rows, t in ((1, 512), (8, 64)):
        compiled = jax.jit(kda_chunk.chunk_rows).lower(
            arg(rows, 64, 128, 128), *[arg(rows, 64, t, 128)] * 5).compile()
        assert compiled.as_text().count("tpu_custom_call") == 1
        assert compiled.memory_analysis().temp_size_in_bytes < 2 ** 20


# -- the pooled index cache ---------------------------------------------------------

def test_the_pooled_index_cache_is_the_keys_pooled_from_scratch(tiny):
    """A row's index keys written a 64-token chunk, a ragged chunk and then
    token by token: the pool holds, for every complete block of 4 positions,
    the mean of its four keys, and the tail the keys of the incomplete one."""
    _, cfg, _, _ = tiny
    rows, maxp, n_pages, d = 2, 8, 12, cfg.index_head_dim
    cache = kv.with_recurrent_state(kv.PagedKVCache.for_model(
        cfg, n_pages, rows, maxp, PAGE), cfg, rows)
    tables = np.full((rows, maxp), -1, np.int32)
    tables[1, :6] = [5, 3, 7, 1, 9, 2]
    cache = cache.with_tables(jnp.asarray(tables))
    assert cache.v.shape == (1, n_pages, 1, PAGE // 4, d)
    keys = jax.random.normal(jax.random.key(1), (90, d)).astype(jnp.bfloat16)
    vl, state = cache.v, {"ix_tail": cache.state["ix_tail"]}
    fi = jnp.asarray(0, jnp.int32)

    def call(k_i, base, n, rowmap=None):
        nonlocal vl, state
        b, t = k_i.shape[:2]
        q_slots = base[:, None] + jnp.arange(t)[None, :]
        c = cache if rowmap is None else cache.with_tables(
            cache.tables[jnp.clip(rowmap, 0, rows - 1)])
        vl, state = decoder._pool_index_keys(
            cfg, c, vl, fi, k_i, q_slots, n, None, rowmap,
            None if rowmap is None else base == 0, state)

    # a prefill block of one slot: row 1's first 64 tokens, then 21 more
    one = jnp.asarray([1], jnp.int32)
    call(keys[None, :64], jnp.asarray([0]), jnp.asarray([64]), one)
    pad = jnp.zeros((1, 64, d), jnp.bfloat16).at[0, :21].set(keys[64:85])
    call(pad, jnp.asarray([64]), jnp.asarray([21]), one)
    # then decode steps for row 1 (row 0 idle)
    for p in range(85, 90):
        call(jnp.stack([jnp.zeros((1, d), jnp.bfloat16), keys[p][None]]),
             jnp.asarray([0, p]), jnp.asarray([0, 1]))
    got = np.asarray(cache.with_tables(cache.tables).gather_layer(
        vl, 0)[1, 0], np.float32)                       # [blocks, d]
    want = np.asarray(keys[:88].astype(jnp.float32).reshape(22, 4, d)
                      .mean(1).astype(jnp.bfloat16), np.float32)
    np.testing.assert_array_equal(got[:22], want)
    tail = np.asarray(state["ix_tail"][0, 1], np.float32)
    np.testing.assert_array_equal(tail[:2],
                                  np.asarray(keys[88:90], np.float32))
    assert not np.asarray(state["ix_tail"][0, 0]).any()


def test_a_query_scores_whole_blocks_before_it_and_keeps_its_tail(tiny):
    """``index_scores`` over pooled keys sees block j where ``j < t // 4``,
    and the reference's selection always holds the tail's positions."""
    hf, cfg, params, ref = tiny
    from ipex_llm_tpu.ops import attention

    rows, maxp = 1, 4
    cache = kv.PagedKVCache.for_model(cfg, 6, rows, maxp, PAGE).with_tables(
        jnp.asarray([[1, 2, 3, 4]], jnp.int32))
    q = jnp.ones((1, 5, cfg.index_n_heads, cfg.index_head_dim), jnp.bfloat16)
    w = jnp.ones((1, 5, cfg.index_n_heads), jnp.float32)
    pool = jnp.ones_like(cache.v)
    q_slots = jnp.asarray([[3, 4, 7, 8, 21]])
    s = attention.index_scores(q, w, pool, cache, q_slots, q_slots[:, -1] + 1,
                               layer=0, kpool=4)
    seen = np.isfinite(np.asarray(s[0]))
    assert [int(r.sum()) for r in seen] == [0, 1, 1, 2, 5]
    mask, _ = ref.pooled_selection(
        jnp.ones((30, cfg.hidden_size)), jnp.ones((30, cfg.q_lora_rank)),
        jax.jit(lambda t_: t_)(ref._entry(params["layers"]["indexer"], 0)),
        n_heads=cfg.index_n_heads,
        dim=cfg.index_head_dim, top=cfg.index_topk, pool=4,
        shapes=ref._shapes(params["layers"]["indexer"], "wq_b", "wk"),
        lower=None)
    mask = np.asarray(mask)
    for t in range(30):
        assert mask[t, 4 * (t // 4):t + 1].all() and not mask[t, t + 1:].any()
        assert mask[t].sum() == min(t + 1, 16 + t % 4 + 1)


# -- the share ---------------------------------------------------------------------

def test_the_shares_of_a_layer_add_up_to_the_uncut_layer(tiny):
    """THE SHARE TEST: an expert layer of the deployment, each of the 4
    chips' quarter of the 16 experts computed by the program's held layer
    behind the program's hyper-connection (routing on score + bias over all
    16, the clamp inside every expert); the residual mix and the shared expert
    counted ONCE, the other chips' routed parts crossing the post-mix alone:
    their sum is the uncut reference's layer, all four streams of it."""
    hf0, _, _, ref = tiny
    hf = dict(hf0, n_routed_experts=16)
    cfg = FAMILIES[FAMILY].to_config(hf)
    params = weights.generator(FAMILY).param_tree(cfg, hf, "sym_int4", 5)
    tree, layer, t = params["layers"], 1, 7
    x = jax.random.normal(jax.random.key(9), (cfg.hc_mult, 1, t,
                                              cfg.hidden_size), jnp.float32)
    lp = jax.jit(lambda t_: t_)(ref._entry(tree, layer))
    st = ref.settings(hf)
    ffn = dict(st["moe"], eps=st["eps"], limit=st["limit"],
               shapes=ref._shapes(tree, "moe_gate_up", "moe_down",
                                  "shared_gate_up", "shared_down"))
    with jax.default_matmul_precision("highest"):
        want, _ = ref._connect(
            jnp.moveaxis(x[:, 0], 0, 1), lp, "mlp", st["hyper"], None,
            lambda h: ref._moe(h, lp, lower=None, **ffn))
    want = np.moveaxis(np.asarray(want), 1, 0)            # [n, T, C]

    coef = hyper.coefficients(
        cfg, {k_: v_[layer] for k_, v_ in tree.items()
              if k_.startswith("hc_mlp_")}, "mlp", x)
    h = hyper.pre_mix(coef, x)
    valid = jnp.ones((1, t), bool)
    flat = {k_: v_ for k_, v_ in tree.items() if not isinstance(v_, dict)}
    total = None
    for share in range(4):
        cut = dataclasses.replace(cfg, moe_first_expert=4 * share,
                                  moe_experts_held=4)
        lp_cut = {k_: jax.tree_util.tree_map(
            (lambda p, lo=4 * share: p[layer, lo:lo + 4])
            if k_ in ("moe_gate_up", "moe_down") else (lambda p: p[layer]),
            v_) for k_, v_ in flat.items()}
        out, stats = decoder._held_moe_block(cut, lp_cut, h, valid,
                                             shared=share == 0)
        assert int(stats[0]) == t * cfg.num_experts_per_tok
        if share == 0:
            total = np.array(hyper.post_mix(coef, x, out)[:, 0], np.float32)
        else:
            total += np.asarray(coef[1][:, 0, :, None] * out[0], np.float32)
    np.testing.assert_allclose(total, want, atol=0.04, rtol=0.04)
    assert np.abs(want - np.asarray(x[:, 0])).mean() > 0.05


def test_the_clamp_bites_inside_the_gated_activation():
    from ipex_llm_tpu.ops import mlp

    gate = jnp.asarray([-20.0, 3.0, 12.0, 30.0])
    up = jnp.asarray([15.0, -12.0, 2.0, -1.0])
    got = mlp.gated_act_mul(gate, up, "silu", 10.0)
    want = jax.nn.silu(jnp.minimum(gate, 10.0)) * jnp.clip(up, -10.0, 10.0)
    np.testing.assert_allclose(got, want, rtol=1e-6)
    assert not np.allclose(got, mlp.gated_act_mul(gate, up, "silu"))


# -- float32 where the configuration states it ---------------------------------------

def _tick_jaxpr(cfg, params, rows: int):
    from ipex_llm_tpu.serving.engine import _ragged_tick

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype)

    i32 = lambda *shape: sds(shape, jnp.int32)             # noqa: E731
    f32 = lambda *shape: sds(shape, jnp.float32)           # noqa: E731
    cache = kv.with_recurrent_state(
        kv.PagedKVCache.for_model(cfg, 4, rows, 8, PAGE), cfg, rows)
    args = (cfg, params, cache, i32(rows), i32(rows), sds((rows,), jnp.bool_),
            f32(rows), f32(rows), jax.eval_shape(lambda: jax.random.PRNGKey(0)),
            i32(rows), i32(rows), i32(rows), i32(rows, 2), i32(rows))
    return _ragged_tick.trace(*args, prefill=None, horizon=1,
                              with_decode=True, mesh=None,
                              state_src=cache.state).jaxpr.jaxpr


def _scoped_eqns(jaxpr, scope: str) -> list:
    from ipex_llm_tpu.analysis.trace.tracer import _iter_subjaxprs

    found = [e for e in jaxpr.eqns if scope in str(e.source_info.name_stack)]
    for eqn in jaxpr.eqns:
        for sub in eqn.params.values():
            for j in _iter_subjaxprs(sub):
                found += _scoped_eqns(j, scope)
    return found


@pytest.mark.parametrize("scope", ["mhc/coef", "kda/step"])
def test_the_tick_holds_the_coefficients_and_the_state_to_float32(tiny, scope):
    """In ``_ragged_tick``'s jaxpr every floating value under ``mhc/coef``
    (the hyper-connections' coefficients) and under ``kda/step`` (the state,
    its decay and its correction) is float32."""
    _, cfg, params, _ = tiny
    eqns = _scoped_eqns(_tick_jaxpr(cfg, params, 2), scope)
    assert eqns
    other = [(e.primitive.name, str(v.aval.dtype)) for e in eqns
             for v in e.outvars
             if jnp.issubdtype(v.aval.dtype, jnp.floating)
             and v.aval.dtype != jnp.float32]
    assert not other, other


# -- the family -------------------------------------------------------------------------

def test_the_family_reads_its_layers_its_state_and_its_pool():
    cfg = FAMILIES[FAMILY].to_config(published())
    assert cfg.mixer_types == ("kda", "mla", "kda", "kda", "kda", "mla",
                               "kda", "kda", "kda")
    assert (cfg.kda_layers, cfg.kv_layers, cfg.index_layers) == (7, 2, 2)
    assert cfg.rope is None and cfg.qk_rope_head_dim == 0
    assert cfg.index_kpool == 4 and cfg.mlp_act_limit == 10.0
    assert (cfg.num_experts, cfg.moe_experts_held) == (288, 36)
    assert cfg.chunk_grid == kda.CHUNK == 64
    assert kv.pool_geometry(cfg) == (1, 512, 128)
    page = kv.model_page_bytes(cfg, 128)
    assert page == 2 * (128 * 512 * 2 + 32 * 128 * 2) == 278528
    state = jax.eval_shape(lambda: kv.recurrent_state(cfg, 32))
    assert state["kda_s"].shape == (7, 32, 64, 128, 128)
    assert state["kda_conv"].shape == (7, 32, 3, 24576)
    assert state["ix_tail"].shape == (2, 32, 3, 128)
    assert kv.state_counters(cfg)[6:8] == kv.KDA_COUNTERS
    assert kv.state_counters(cfg)[-5:] == kv.DSA_COUNTERS


@pytest.mark.parametrize("key, value, says", [
    ("layer_types", ["sliding_attention"] * 9, "layer_types"),
    ("mhc", False, "hyper-connected"),
    ("topk_method", "greedy", "noaux_tc"),
    ("indexer_types", ["full"] * 8 + ["shared"], "indexer_types"),
    ("qk_rope_head_dim", 64, "position-free"),
])
def test_what_the_family_does_not_build_is_refused_by_name(key, value, says):
    with pytest.raises(ValueError, match=says):
        FAMILIES[FAMILY].to_config(dict(published(), **{key: value}))


@pytest.mark.parametrize("what", ["spec_k", "kv_spill_bytes", "dense cache"])
def test_what_the_state_cannot_do_is_refused_by_name(tiny, what):
    _, cfg, params, _ = tiny
    if what == "dense cache":
        cache = kv.KVCache.init(cfg.kv_layers, 1, 32, 1, 128)
        with pytest.raises(ValueError, match="serving path"):
            decoder.decoder_forward(cfg, params, jnp.zeros((1, 4), jnp.int32),
                                    cache, jnp.arange(4)[None])
        return
    ec = EngineConfig(**dict(ENGINE, **{what: 2 if what == "spec_k"
                                        else 1 << 20}))
    with pytest.raises(ValueError,
                       match="2 Kimi Delta Attention layers"):
        ServingEngine(cfg, params, ec)
