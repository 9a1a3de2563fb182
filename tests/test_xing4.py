"""``model_type: xing4_0`` (Xing4.0) on the serving path, at a tiny size on the
CPU: two dense layers and three expert layers on FOUR hyper-connected residual
streams, 16 experts of which 8 are held, pages of 16, seeded random weights
drawn by the benchmark's generator (perfbench/generators/xing4_0.py).
``ServingEngine`` through the latent pool is held to the benchmark's plain
reference (perfbench/references/xing4_0.py, which imports nothing of the
program): chunked prefill then decode, caches over several pages, a row alone
and in a full batch.  Beside it what the family forced, each against its own
oracle: the coefficients against the reference's equations (and a bfloat16
control that misses them), the Sinkhorn mix's sums and its clamp, a dropped
dynamic term, the share test, the refusals, and the count of programs."""

from __future__ import annotations

import dataclasses
import importlib
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
from tests.test_glm_dsa import _logged_compiles  # noqa: E402

from ipex_llm_tpu import kv  # noqa: E402
from ipex_llm_tpu.models import decoder  # noqa: E402
from ipex_llm_tpu.models.families import FAMILIES  # noqa: E402
from ipex_llm_tpu.ops import hyper  # noqa: E402
from ipex_llm_tpu.serving.engine import (EngineConfig, Request,  # noqa: E402
                                         ServingEngine, stream_tokens)

CONFIG = "xing4.0-29b-a4b.int4.ep2"
FAMILY = "xing4_0"
PAGE = 16
NEW = 6
# the prompts of the served cases: under a page; a cache over several pages
# and chunks; two more to fill the batch (four rows joining together take
# 8-token chunks, whose edges fall inside pages)
PROMPTS = {"one_page": 10, "several_pages": 70, "filler_a": 45,
           "filler_b": 33}
ENGINE = dict(max_rows=4, max_seq_len=128, page_size=PAGE, prefill_bucket=32,
              step_token_budget=32)


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


def _serve(cfg, params, names=PROMPTS) -> dict:
    """Every prompt served ALONE, then all of them at once."""
    eng = ServingEngine(cfg, params, EngineConfig(**ENGINE)).start()
    try:
        out = {"alone": {}, "batch": {}}
        for name in names:
            req = eng.submit(Request(prompt_ids=prompt(name),
                                     max_new_tokens=NEW))
            out["alone"][name] = (list(stream_tokens(req, timeout=300)),
                                  [float(x) for x in req.logprobs])
        out["metrics_alone"] = dict(eng.metrics)
        reqs = {name: eng.submit(Request(prompt_ids=prompt(name),
                                         max_new_tokens=NEW))
                for name in names}
        for name, req in reqs.items():
            out["batch"][name] = (list(stream_tokens(req, timeout=300)),
                                  [float(x) for x in req.logprobs])
        out["kv"] = eng.kv_stats()
    finally:
        eng.stop()
    return out


@pytest.fixture(scope="module")
def served(tiny):
    _, cfg, params, _ = tiny
    return _serve(cfg, params)


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
    # (read at this size: means of 0.008-0.011 under the limit of 0.045,
    # largest errors of 0.017-0.031 under 0.2)
    assert res["ok"], res


@pytest.mark.parametrize("name", sorted(PROMPTS))
def test_a_row_does_not_depend_on_its_batch(served, name):
    """Alone its prompt goes in 32-token chunks; in the batch of four the
    budget splits into 8-token chunks beside decoding rows: other chunk
    edges, another ``p_b``, the same tokens."""
    alone, batch = served["alone"][name], served["batch"][name]
    assert alone[0] == batch[0]
    np.testing.assert_allclose(alone[1], batch[1], atol=2e-2)


def test_the_engine_counts_the_tokens_it_mixes(tiny, served):
    """``mhc_tokens_mixed_total``: every token a tick runs, prompt or decoded,
    in each of a layer's two sublayers; a latent pool as the other latent
    families hold it (the streams are activations and cache nothing)."""
    _, cfg, _, _ = tiny
    # (a lower bound, as ``attn_pages_live``: the host counts before the
    # dispatch, and the row that ends its prompt in a tick decodes its second
    # token inside that tick, uncounted)
    tokens = sum(n + NEW - 2 for n in PROMPTS.values())
    assert served["metrics_alone"]["mhc_tokens_mixed_total"] == (
        tokens * 2 * cfg.num_layers)
    stats = served["kv"]
    assert stats["latent_bytes_per_token"] == (64 + 32) * 2
    pool = kv.PagedKVCache.for_model(cfg, 4, 2, 8, PAGE)
    assert pool.latent and not pool.sparse
    assert pool.k.shape == (5, 4, 1, PAGE, 64)


@pytest.mark.parametrize("lower,passes", [
    ("bf16", True), ("coef_bf16", True), ("fp8", False),
    ("static_coef", False), ("expert_shift", False)])
def test_the_references_limits_tell_rounding_from_a_fault(tiny, served, lower,
                                                          passes):
    """What the served path returned, held to the reference computed one way
    lower, by the reference's OWN limits (read at this size, the mean error:
    the served path 0.011): bf16 rounding between operations (the
    configuration's own, 0.016) stays inside; fp8 streams and activations
    (0.154), a dropped dynamic term (0.106) and the held experts taken for
    their neighbours (0.119) do not.  The hyper-connections' coefficients in
    bfloat16 stay inside too (0.011): on the log-probabilities they cannot be
    told from the streams' own rounding, which is why the coefficients are
    held to float32 in the tick's program below."""
    hf, _, params, ref = tiny
    name = "several_pages"
    tokens, lps = served["alone"][name]
    ids = prompt(name)
    res = ref.compare(ref.logprobs(hf, params, ids + tokens[:-1],
                                   lower=lower), len(ids), tokens, lps)
    assert res["ok"] == passes, res
    assert (res["logprob_err_mean"] <= ref.MEAN_TOL) == passes, res


def test_a_dropped_dynamic_term_fails_the_comparison(tiny):
    """The SAME engine on a tree whose ``P_*`` are zeroed (coefficients from
    the biases alone, the same for every token) against the reference on the
    true tree: not correct."""
    hf, cfg, params, ref = tiny
    static = {k_: (v_ if not isinstance(v_, dict) else {
        n_: (jnp.zeros_like(a) if re.fullmatch(r"hc_\w+_proj", n_) else a)
        for n_, a in v_.items()}) for k_, v_ in params.items()}
    got = _serve(cfg, static, names=("several_pages",))
    tokens, lps = got["alone"]["several_pages"]
    ids = prompt("several_pages")
    res = ref.compare(ref.logprobs(hf, params, ids + tokens[:-1]), len(ids),
                      tokens, lps)
    # (read at this size: a mean error of 0.146, over ten times the served
    # path's own; at the published depth:
    # scripts/xing4_reasoning_check.py, ``static_coef``)
    assert not res["ok"] and res["logprob_err_mean"] > 2 * ref.MEAN_TOL, res
    # and the reference's own planted fault reads the same way (0.011)
    low = ref.compare(ref.logprobs(hf, params, ids + tokens[:-1],
                                   lower="static_coef"), len(ids), tokens, lps)
    assert low["ok"], low


# -- the hyper-connection against its equations -------------------------------------

def _streams(cfg, t=9, seed=4, dtype=jnp.bfloat16):
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.standard_normal(
        (cfg.hc_mult, 1, t, cfg.hidden_size)), dtype)


def _coef_pair(tiny, lower=None, x=None):
    """(the program's coefficients, the reference's) for one sublayer's
    tensors of the tiny tree, both as [T, ...] numpy."""
    hf, cfg, params, ref = tiny
    lp = {k_: v_[1] for k_, v_ in params["layers"].items()
          if k_.startswith("hc_mlp_")}
    x = _streams(cfg) if x is None else x
    with jax.default_matmul_precision("highest"):
        want = ref.hyper_coefficients(
            jnp.moveaxis(x[:, 0], 0, 1).astype(jnp.float32),
            lp["hc_mlp_proj"], lp["hc_mlp_scale"], lp["hc_mlp_bias"],
            *ref.settings(hf, params)["hyper"], lower)
    pre, post, res = hyper.coefficients(cfg, lp, "mlp", x)
    got = (np.asarray(pre[:, 0]).T, np.asarray(post[:, 0]).T,
           np.moveaxis(np.asarray(res[:, :, 0]), -1, 0))
    return got, tuple(np.asarray(w) for w in want)


@pytest.mark.parametrize("lower,agrees", [(None, True), ("coef_bf16", False)])
def test_the_coefficients_are_the_references_in_float32(tiny, lower, agrees):
    """The program's three coefficient sets against the reference's equations
    to float32 rounding (2e-5); the reference's own coefficients computed in
    bfloat16, one precision below what the configuration states, miss that
    by two orders of magnitude."""
    got, want = _coef_pair(tiny, lower)
    worst = max(float(np.max(np.abs(g - w))) for g, w in zip(got, want))
    assert (worst < 2e-5) == agrees, worst
    if not agrees:
        assert worst > 1e-3, worst
    # the token-dependent term moves every pre-activation by order 1:
    # sigmoid's output spreads over the tokens
    assert np.std(want[0], axis=0).min() > 0.05


def test_the_residual_mix_is_doubly_stochastic_and_clamped(tiny):
    _, cfg, _, _ = tiny
    got, _ = _coef_pair(tiny)
    res = got[2]                                          # [T, i, j]
    # rows normalised last: exact to hc_eps; columns to the 20-round error
    np.testing.assert_allclose(res.sum(2), 1.0, atol=1e-5)
    np.testing.assert_allclose(res.sum(1), 1.0, atol=1e-3)
    assert res.min() >= 0
    # the clamp: logits far outside +-30 give what +-30 gives, finite
    big = jnp.asarray([[1e4, -1e4], [-50.0, 50.0]], jnp.float32)[..., None]
    at = jnp.clip(big, -30.0, 30.0)
    out = hyper.sinkhorn(big, cfg.hc_sinkhorn_iters, cfg.hc_eps,
                         cfg.hc_res_clamp)
    assert np.isfinite(np.asarray(out)).all()
    np.testing.assert_array_equal(
        np.asarray(out), np.asarray(hyper.sinkhorn(
            at, cfg.hc_sinkhorn_iters, cfg.hc_eps, cfg.hc_res_clamp)))
    assert cfg.hc_res_clamp == (-30.0, 30.0) and cfg.hc_sinkhorn_iters == 20


# -- the coefficients' precision, in the TICK's own program ----------------------------

def _tick_jaxpr(cfg, params, rows: int):
    """The steady tick's jaxpr for ``rows`` rows of the tiny model: the
    program the engine dispatches (``_ragged_tick``), traced, not run."""
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
                              with_decode=True, mesh=None).jaxpr.jaxpr


def _scoped_eqns(jaxpr, scope: str) -> list:
    """Every equation of a program under name scope ``scope``, a scan's or a
    loop's body counted once."""
    from ipex_llm_tpu.analysis.trace.tracer import _iter_subjaxprs

    found = [e for e in jaxpr.eqns if scope in str(e.source_info.name_stack)]
    for eqn in jaxpr.eqns:
        for sub in eqn.params.values():
            for j in _iter_subjaxprs(sub):
                found += _scoped_eqns(j, scope)
    return found


def _not_float32(eqns) -> list:
    return [(e.primitive.name, str(v.aval.dtype)) for e in eqns
            for v in e.outvars
            if jnp.issubdtype(v.aval.dtype, jnp.floating)
            and v.aval.dtype != jnp.float32]


@pytest.mark.parametrize("planted", [False, True])
def test_the_tick_program_computes_the_coefficients_in_float32(
        tiny, monkeypatch, planted):
    """The configuration states float32 coefficients, and the cell's
    log-probabilities cannot tell bfloat16 ones from the served path's own
    rounding (the reference's limits say why).  So the TICK's program is held
    to it where it is traced: in ``_ragged_tick``'s jaxpr every floating
    value under scope ``mhc/coef`` is float32 and each sublayer's product is
    float32 x float32 at precision "highest", in both stacks.  Planted: a
    ``coefficients`` that hands bfloat16 back is seen."""
    _, cfg, params, _ = tiny
    if planted:
        exact = hyper.coefficients

        @jax.named_scope("mhc/coef")
        def rounded(*args):
            return tuple(c.astype(jnp.bfloat16) for c in exact(*args))

        monkeypatch.setattr(decoder.hyper_ops, "coefficients", rounded)
    # (a row count of its own a case: jit keeps a traced program by its
    # arguments' shapes, and the planted function is not one of them)
    eqns = _scoped_eqns(_tick_jaxpr(cfg, params, 3 if planted else 2),
                        "mhc/coef")
    dots = [e for e in eqns if e.primitive.name == "dot_general"]
    # two stacks (dense prefix, expert layers) x two sublayers
    assert len(dots) == 4
    for e in dots:
        assert {str(v.aval.dtype) for v in e.invars} == {"float32"}
        assert e.params["preferred_element_type"] == jnp.float32
        assert set(e.params["precision"]) == {jax.lax.Precision.HIGHEST}
    assert bool(_not_float32(eqns)) == planted, _not_float32(eqns)


# -- the share ---------------------------------------------------------------------

def test_the_two_shares_of_a_layer_add_up_to_the_uncut_layer(tiny):
    """THE SHARE TEST: an expert layer of the 2-chip deployment, each chip's
    half of the 16 experts computed by the program's held layer behind the
    program's hyper-connection (routing on score + bias over all 16); the
    residual mix and the shared expert counted ONCE, the other chip's routed
    part crossing the post-mix alone: their sum is the uncut reference's
    layer, all four streams of it."""
    hf0, _, _, ref = tiny
    hf = dict(hf0, n_routed_experts=16)
    cfg = FAMILIES[FAMILY].to_config(hf)
    params = weights.generator(FAMILY).param_tree(cfg, hf, "sym_int4", 5)
    tree, layer, t = params["layers"], 1, 7
    x = _streams(cfg, t=t, seed=9, dtype=jnp.float32)
    # through a jit, as the reference hands it on: the scale views cross as
    # their bits and come out as fp16 planes
    lp = jax.jit(lambda t_: t_)(ref._layer_tree(tree, layer))
    st = ref.settings(hf, params)
    connect = ref._hyper_fn(*st["hyper"], None)
    with jax.default_matmul_precision("highest"):
        want, _ = connect(
            jnp.moveaxis(x[:, 0], 0, 1), lp["hc_mlp_proj"],
            lp["hc_mlp_scale"], lp["hc_mlp_bias"],
            lambda h: ref._moe(h, lp, lower=None, **dict(st["moe"][1])))
    want = np.moveaxis(np.asarray(want), 1, 0)            # [n, T, C]

    coef = hyper.coefficients(
        cfg, {k_: v_[layer] for k_, v_ in tree.items()
              if k_.startswith("hc_mlp_")}, "mlp", x)
    h = hyper.pre_mix(coef, x)
    valid = jnp.ones((1, t), bool)
    outs = []
    for share in range(2):
        cut = dataclasses.replace(cfg, moe_first_expert=8 * share,
                                  moe_experts_held=8)
        lp_cut = {k_: jax.tree_util.tree_map(
            (lambda p, lo=8 * share: p[layer, lo:lo + 8])
            if k_ in ("moe_gate_up", "moe_down") else (lambda p: p[layer]),
            v_) for k_, v_ in tree.items()}
        out, stats = decoder._held_moe_block(cut, lp_cut, h, valid,
                                             shared=share == 0)
        outs.append(out)
        assert int(stats[0]) == t * cfg.num_experts_per_tok
    total = np.array(hyper.post_mix(coef, x, outs[0])[:, 0], np.float32)
    total += np.asarray(coef[1][:, 0, :, None] * outs[1][0], np.float32)
    # bf16 matmul operands in the program against float32 in the reference
    np.testing.assert_allclose(total, want, atol=0.04, rtol=0.04)
    assert np.abs(want - np.asarray(x[:, 0])).mean() > 0.05


# -- the family --------------------------------------------------------------------

def test_the_family_reads_its_streams_its_share_and_its_bias():
    hf = published()
    cfg = FAMILIES[FAMILY].to_config(hf)
    assert (cfg.num_layers, cfg.moe_layer_start, cfg.hidden_size) == (
        40, 2, 3584)
    assert (cfg.num_experts, cfg.moe_experts_held, cfg.moe_first_expert) == (
        64, 32, 0)
    assert cfg.moe_score_bias and cfg.moe_score_func == "sigmoid"
    assert cfg.moe_n_group == 1 and cfg.moe_router_scale == 2
    assert (cfg.head_dim, cfg.v_dim, cfg.kv_lora_rank, cfg.q_lora_rank) == (
        192, 128, 512, 768)
    # the DeepSeek YaRN convention: the softmax scale carries m(1)^2
    m = 0.1 * np.log(64) + 1
    assert cfg.rope.kind == "yarn" and abs(
        cfg.attn_scale - 192 ** -0.5 * m * m) < 1e-9
    assert (cfg.hc_mult, cfg.hc_sinkhorn_iters, cfg.hc_eps) == (4, 20, 1e-6)
    assert kv.pool_geometry(cfg) == (1, 512, 64)
    assert kv.model_page_bytes(cfg, 128) == 128 * 40 * 576 * 2 == 5898240
    # one stream everywhere else: the programs other families compile read
    # none of this
    others = [FAMILIES["axk1"].to_config(run.hf_config(run.load_json(
        os.path.join(BENCH, "configs", "a.x-k1.int4.ep16.json"))))]
    assert all(c.hc_mult == 1 and c.hc_sinkhorn_iters == 0 for c in others)
    with pytest.raises(ValueError, match="noaux_tc"):
        FAMILIES[FAMILY].to_config(dict(hf, topk_method="none"))
    with pytest.raises(ValueError, match="hyper-connected streams"):
        FAMILIES[FAMILY].to_config(dict(hf, hc_mult=1))
    # and the family that has no bias points here too
    with pytest.raises(ValueError, match="glm_moe_dsa and xing4_0"):
        FAMILIES["axk1"].to_config(dict(
            run.hf_config(run.load_json(os.path.join(
                BENCH, "configs", "a.x-k1.int4.ep16.json"))),
            topk_method="noaux_tc"))


@pytest.mark.parametrize("what", ["pipeline", "manual_tp", "mesh", "fp8"])
def test_what_four_streams_cannot_do_is_refused_by_name(tiny, what):
    _, cfg, params, _ = tiny
    from jax.sharding import Mesh

    if what == "pipeline":
        from ipex_llm_tpu.parallel import pipeline

        mesh = Mesh(np.asarray(jax.devices()[:2]), ("pp",))
        with pytest.raises(NotImplementedError,
                           match="'xing4_0' carries 4 hyper-connected"):
            pipeline.pipeline_forward(cfg, params, jnp.zeros((2, 8),
                                                             jnp.int32),
                                      mesh, n_micro=2)
    elif what == "manual_tp":
        from ipex_llm_tpu.parallel import manual

        mesh = Mesh(np.asarray(jax.devices()[:2]), ("tp",))
        assert re.search(r"'xing4_0': 4 hyper-connected residual streams",
                         manual.ineligible_reason(cfg, params, mesh))
    elif what == "mesh":
        mesh = Mesh(np.asarray(jax.devices()[:2]), ("tp",))
        with pytest.raises(ValueError, match="latent .MLA. pool: a mesh is "
                           "not supported"):
            ServingEngine(cfg, params, EngineConfig(
                max_rows=2, max_seq_len=64, page_size=PAGE), mesh=mesh)
    else:
        with pytest.raises(ValueError, match="latent page is bf16 only"):
            ServingEngine(cfg, params, EngineConfig(
                max_rows=2, max_seq_len=64, page_size=PAGE,
                kv_storage="fp8"))


def test_the_dense_cache_runs_the_same_streams(tiny):
    """``generate()``'s dense cache walks the same layer step: its prefill
    logits are the paged pool's."""
    hf, cfg, params, ref = tiny
    ids = prompt("filler_b")
    tokens = jnp.asarray([ids], jnp.int32)
    cache = kv.KVCache.init(cfg.num_layers, 1, 64, cfg.num_kv_heads,
                            cfg.head_dim, v_head_dim=cfg.v_dim)
    logits, _ = decoder.decoder_forward(cfg, params, tokens, cache,
                                        jnp.arange(len(ids))[None])
    got = np.asarray(jax.nn.log_softmax(logits[0], -1))
    want, _ = ref.logprobs(hf, params, ids)
    top = np.argmax(want, -1)
    at = np.arange(len(ids))
    assert np.median(np.abs(got[at, top] - want[at, top])) < 0.03


# -- one tick program family -------------------------------------------------------

def _programs_of_a_wave(cfg, params, vocab) -> list[str]:
    """The programs a fresh engine compiles for one scenario: three prompts
    joining at once, a fourth beside decoding rows, completions."""
    rng = np.random.default_rng(38)
    # (a table width no other test of this file OR of tests/test_glm_dsa.py,
    # which runs the one-stream model through the same scenario, uses: jit's
    # cache is the process's, and a program an earlier test compiled would
    # not be counted)
    eng = ServingEngine(cfg, params, EngineConfig(
        **dict(ENGINE, max_seq_len=96)))
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


def test_four_streams_ride_the_one_tick_program_and_add_none(tiny):
    """The same scenario on a family of ONE stream (the A.X-K1 tiny model:
    the latent pool and the held layers as they were) and on this one: the
    same programs by name and number (this scenario's tick programs: three
    rows joining, one joining beside decoding rows, a lone chunk, the steady
    tick; and the unpack), nothing beside them."""
    _, cfg, params, _ = tiny
    gen = weights.generator("axk1")
    hf_a = gen.tiny(run.hf_config(run.load_json(
        os.path.join(BENCH, "configs", "a.x-k1.int4.ep16.json"))))
    cfg_a = FAMILIES["axk1"].to_config(hf_a)
    one_stream = _programs_of_a_wave(cfg_a, gen.param_tree(
        cfg_a, hf_a, "sym_int4", 11), 256)
    four_streams = _programs_of_a_wave(cfg, params, 256)
    # (the unpack is one program a PROCESS: whichever engine meets this
    # layout first compiles it, an earlier test's perhaps)
    for names in (one_stream, four_streams):
        assert set(names) <= {"_ragged_tick", "unpack_rows"}
        assert names.count("unpack_rows") <= 1
    # (five at this table width of 6 pages; four at test_glm_dsa.py's 7)
    assert one_stream.count("_ragged_tick") == four_streams.count(
        "_ragged_tick") == 5
