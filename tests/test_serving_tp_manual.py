"""Manual-mesh tensor parallelism for the fused serving tick (PR 14).

The serving engine on a pure-tp mesh routes the WHOLE fused tick through
one fully-manual shard_map region (parallel/manual.py): per-shard paged
pools, the unmodified single-chip decoder body over a shard-local config,
and explicit collectives (ops/collectives.py) at the row-parallel combine
points.  Gates:

- tp2 == tp1 BIT identity — tokens AND logprobs, greedy and seeded —
  under the exact ("bf16") collective family; tp4/tp8 token-identical
  with reduction-order-level logprob noise only;
- JP106's ==1 dispatch per tick holds at every tp degree AT RUNTIME
  (the static audit covers the lowerings; this measures the engine);
- quantized wire families (EQuARX e5m2/int8) pass a bounded-error gate:
  sliding-ppl ratio < 1.25 vs the exact family, greedy token-match rate
  reported;
- the engine's eligibility routing falls back to GSPMD with a recorded
  reason where the manual layout does not apply.

Engine-level tests are slow-tier (each compiles the sharded tick on the
8-virtual-device mesh); the collective/relayout unit tests ride the
fast tier — scripts/run-fast-tests names this split.
"""

import math

import numpy as np
import pytest

from ipex_llm_tpu.parallel import MeshSpec, make_mesh
from ipex_llm_tpu.serving.engine import (
    EngineConfig,
    Request,
    ServingEngine,
    stream_tokens,
)
from tests.test_decoder import rand_params, tiny_cfg

RNG = np.random.default_rng(91)


def _prompts(cfg, lens=(7, 19, 41), seed=77):
    # HERMETIC per-test draws (the test_decoder rule): the bit-identity
    # gate compares two engine runs on FIXED prompts, so the draw must
    # not depend on test execution order
    rng = np.random.default_rng(seed)
    return [list(rng.integers(0, cfg.vocab_size, n)) for n in lens]


@pytest.fixture(scope="module")
def cfg_params():
    # every sharded axis divides by 8: q/kv heads, the packed qkv/gate_up
    # widths, and the vocab (128, so the col-parallel lm head + in-region
    # logits all-gather is exercised at every degree)
    cfg = tiny_cfg(vocab_size=128, hidden_size=64, intermediate_size=128,
                   num_heads=8, num_kv_heads=8, head_dim=8,
                   max_position_embeddings=512)
    return cfg, rand_params(cfg, qtype="bf16")


def _run_engine(cfg, params, prompts, *, mesh=None, n_out=10, seeded=False,
                collective_qtype="bf16", expect_manual=None):
    eng = ServingEngine(
        cfg, params,
        EngineConfig(max_rows=len(prompts), max_seq_len=256,
                     prefill_bucket=32, collective_qtype=collective_qtype),
        mesh=mesh,
    ).start()
    try:
        if expect_manual is not None:
            assert eng._tp_manual == expect_manual, eng._tp_fallback_reason
        reqs = [eng.submit(Request(
                    prompt_ids=p, max_new_tokens=n_out,
                    temperature=0.9 if seeded else 0.0,
                    top_p=0.95 if seeded else 1.0,
                    seed=42 + i if seeded else None))
                for i, p in enumerate(prompts)]
        toks = [list(stream_tokens(r, timeout=600)) for r in reqs]
        m = dict(eng.metrics)
        ring = [dict(r) for r in eng.flight.ring]
        lps = [list(r.logprobs) for r in reqs]
    finally:
        eng.stop()
    return toks, lps, m, ring


# --------------------------------------------------------------------------
# engine-level gates (slow: each compiles the sharded tick on the mesh)
# --------------------------------------------------------------------------

@pytest.mark.slow
@pytest.mark.parametrize("seeded", [False, True])
def test_tp2_bit_identity_tokens_and_logprobs(cfg_params, seeded):
    """THE acceptance gate: tp2 == tp1, tokens and logprobs, bit-exact,
    greedy and seeded, through the real engine (admission wave + decode
    both inside the manual region)."""
    cfg, params = cfg_params
    prompts = _prompts(cfg)
    want_t, want_lp, _, _ = _run_engine(cfg, params, prompts, seeded=seeded)
    got_t, got_lp, _, _ = _run_engine(
        cfg, params, prompts, mesh=make_mesh(MeshSpec(tp=2)),
        seeded=seeded, expect_manual=True)
    assert got_t == want_t
    for g, w in zip(got_lp, want_lp):
        # bit identity, not allclose: the exact family accumulates at f32
        # and the per-shard decoder is the same program, so the sharded
        # tick must reproduce the single-chip floats exactly
        assert g == w


@pytest.mark.slow
@pytest.mark.parametrize("tp", [4, 8])
def test_tp_degrees_token_identity_and_one_dispatch(cfg_params, tp):
    """tp4/tp8: greedy tokens identical to single-chip; logprobs within
    reduction-order noise (tp>2 reassociates the o/down psums); the
    dispatch-per-tick ratio — JP106's runtime twin — is exactly 1."""
    cfg, params = cfg_params
    prompts = _prompts(cfg)
    want_t, want_lp, _, _ = _run_engine(cfg, params, prompts)
    got_t, got_lp, m, ring = _run_engine(
        cfg, params, prompts, mesh=make_mesh(MeshSpec(tp=tp)),
        expect_manual=True)
    assert got_t == want_t
    np.testing.assert_allclose(
        np.concatenate([np.asarray(x) for x in got_lp]),
        np.concatenate([np.asarray(x) for x in want_lp]),
        atol=2e-2, rtol=2e-2)
    # JP106's runtime twin off the flight ring: every working tick
    # dispatched exactly ONE device program, at this tp degree too
    assert ring and all(r["dispatches"] <= 1 for r in ring), ring
    assert any(r["dispatches"] == 1 for r in ring)
    assert all(r["dispatches"] == 1 for r in ring if r.get("tokens")), ring


@pytest.mark.slow
def test_lm_head_bias_shards_with_col_lm_head(cfg_params):
    """A model with a head bias: the col-sharded lm head's [V] bias
    splits with it (a replicated bias would broadcast-clash with the
    [R, V/tp] logits shard inside the manual region) and the greedy
    stream still matches single-chip exactly."""
    cfg, params = cfg_params
    rng = np.random.default_rng(5)
    params = dict(params)
    import jax.numpy as jnp
    params["lm_head_bias"] = jnp.asarray(
        rng.standard_normal(cfg.vocab_size) * 0.1, jnp.float32)
    prompts = _prompts(cfg, lens=(7, 19))
    want_t, _, _, _ = _run_engine(cfg, params, prompts, n_out=6)
    got_t, _, _, _ = _run_engine(
        cfg, params, prompts, mesh=make_mesh(MeshSpec(tp=4)),
        n_out=6, expect_manual=True)
    assert got_t == want_t


@pytest.mark.slow
def test_quantized_collectives_bounded_error(cfg_params):
    """EQuARX wire families: greedy decode under e5m2/int8 AllReduce
    payloads must stay within the bounded-error gate — sliding-ppl ratio
    (engine-reported logprobs of each family's own greedy stream) below
    1.25 vs the exact family, with the token-match rate reported."""
    cfg, params = cfg_params
    prompts = _prompts(cfg, lens=(11, 21))
    mesh = make_mesh(MeshSpec(tp=4))
    base_t, base_lp, _, _ = _run_engine(
        cfg, params, prompts, mesh=mesh, n_out=12, expect_manual=True)

    def ppl(lps):
        flat = [x for row in lps for x in row]
        return math.exp(-sum(flat) / max(len(flat), 1))

    base_ppl = ppl(base_lp)
    for cq in ("e5m2", "int8"):
        got_t, got_lp, _, _ = _run_engine(
            cfg, params, prompts, mesh=mesh, n_out=12,
            collective_qtype=cq, expect_manual=True)
        ratio = ppl(got_lp) / base_ppl
        pairs = [(g, b) for gr, br in zip(got_t, base_t)
                 for g, b in zip(gr, br)]
        match = sum(1 for g, b in pairs if g == b) / len(pairs)
        print(f"collective_qtype={cq}: ppl_ratio={ratio:.4f} "
              f"greedy_token_match={match:.3f}")
        assert ratio < 1.25, (cq, ratio)


@pytest.mark.slow
def test_spec_and_horizon_ride_the_manual_tick(cfg_params):
    """Speculative decoding and the fused horizon both execute INSIDE the
    manual region: greedy streams match the single-chip engine exactly
    and the dispatch ratio stays 1."""
    cfg, params = cfg_params
    prompt = [3, 5, 7, 9, 11, 13, 15]

    def run(mesh):
        eng = ServingEngine(
            cfg, params,
            EngineConfig(max_rows=2, max_seq_len=256, prefill_bucket=32,
                         spec_k=3, decode_horizon=4),
            mesh=mesh,
        ).start()
        try:
            if mesh is not None:
                assert eng._tp_manual, eng._tp_fallback_reason
            req = eng.submit(Request(prompt_ids=prompt, max_new_tokens=12))
            toks = list(stream_tokens(req, timeout=600))
            ring = [dict(r) for r in eng.flight.ring]
            return toks, dict(eng.metrics), ring
        finally:
            eng.stop()

    want, _, _ = run(None)
    got, m, ring = run(make_mesh(MeshSpec(tp=2)))
    assert got == want
    assert m.get("spec_steps", 0) > 0
    assert ring and all(r["dispatches"] <= 1 for r in ring), ring


# --------------------------------------------------------------------------
# unit tier (fast): collectives, relayout, eligibility routing
# --------------------------------------------------------------------------

def _psum_families(x, tp):
    import jax
    from jax.sharding import PartitionSpec as P

    from ipex_llm_tpu.ops import collectives

    mesh = make_mesh(MeshSpec(tp=tp))
    out = {}
    for q in collectives.ALLREDUCE_QTYPES:
        fn = jax.jit(jax.shard_map(
            lambda v, q=q: collectives.all_reduce(v, "tp", qtype=q),
            mesh=mesh, in_specs=P("tp", None), out_specs=P(),
            axis_names={"tp"}, check_vma=False))
        out[q] = np.asarray(fn(x))
    return out


def test_collective_families_exact_and_bounded():
    """bf16 family at tp=2 == the f32 two-operand sum bitwise (order-free
    at two shards — the bit-identity gate's footing); at tp=4 it matches
    the f64 oracle to f32 round-off while the quantized wires diverge
    from it by exactly their code's error envelope; unknown family
    raises."""
    import jax.numpy as jnp

    from ipex_llm_tpu.ops import collectives

    x = RNG.standard_normal((8, 4, 64)).astype(np.float32)
    # per-shard rows: in_specs P("tp", None) splits axis 0 into tp
    # shards; all_reduce sums ACROSS shards
    got2 = _psum_families(jnp.asarray(x), tp=2)
    np.testing.assert_array_equal(got2["bf16"], x[:4] + x[4:])
    want = x.reshape(4, 2, 4, 64).astype(np.float64).sum(axis=0)
    got = _psum_families(jnp.asarray(x), tp=4)
    np.testing.assert_allclose(got["bf16"], want, rtol=1e-6, atol=1e-6)
    # per-element error envelope: each coded term carries at most ~12.5%
    # relative error (e5m2: 2 mantissa bits; int8 blockwise: amax/127 is
    # finer), so the summed error is bounded by 1/8 of the sum of
    # absolute terms — the bound is per-element, not a flat atol, because
    # cancelling sums legitimately blow up the relative error
    envelope = np.abs(x).reshape(4, 2, 4, 64).sum(axis=0) / 8 + 1e-3
    for q in ("e5m2", "int8"):
        err = np.abs(got[q] - want)
        assert np.all(err <= envelope), (q, float(err.max()))
        assert not np.array_equal(got[q], got["bf16"]), (
            f"{q} wire produced bit-identical sums — the quantizer "
            "is not actually coding the payload")
    with pytest.raises(ValueError, match="unknown collective qtype"):
        collectives.all_reduce(jnp.ones((2,)), "tp", qtype="fp4")
    with pytest.raises(ValueError, match="unknown collective qtype"):
        collectives.resolve_qtype("nope")


def test_quantized_codecs_saturate_not_poison():
    """Overflow-range partials must SATURATE, never code to inf/NaN: an
    inf on the wire spreads over the whole hidden state after the
    reduce, which is exactly not 'bounded error'.  (e5m2's finite max is
    57344; int8's f16 block scale overflows past amax ~8.3e6.)"""
    import jax.numpy as jnp

    from ipex_llm_tpu.ops.collectives import _e5m2_code, _int8_code

    big = jnp.full((2, 64), 9e6, jnp.float32)
    for name, coded in (("e5m2", _e5m2_code(big)), ("int8", _int8_code(big))):
        arr = np.asarray(coded)
        assert np.isfinite(arr).all(), name
        assert (arr > 0).all(), name
    assert float(np.asarray(_e5m2_code(jnp.full((1, 4), 1e5))).max()) <= 57344.0


def test_resolve_qtype_precedence(cfg_params, monkeypatch):
    from ipex_llm_tpu.ops import collectives

    assert collectives.resolve_qtype() == "bf16"
    monkeypatch.setenv("IPEX_LLM_TPU_COLLECTIVE_QTYPE", "int8")
    assert collectives.resolve_qtype() == "int8"
    assert collectives.resolve_qtype("e5m2") == "e5m2"  # arg wins over env
    # and the ENGINE honors the chain (the documented operator surface):
    # env applies when the config leaves the family unset, an explicit
    # config value wins over the env
    cfg, params = cfg_params
    ec = EngineConfig(max_rows=2, max_seq_len=256, prefill_bucket=32)
    assert ServingEngine(cfg, params, ec)._collective_qtype == "int8"
    from dataclasses import replace as _dc_replace

    assert ServingEngine(
        cfg, params, _dc_replace(ec, collective_qtype="e5m2"),
    )._collective_qtype == "e5m2"


def test_relayout_packed_is_a_column_permutation(cfg_params):
    """relayout_packed: tp=1 is the identity; at tp>1 the packed qkv /
    gate_up out-columns permute blockwise so a contiguous shard holds its
    heads of every section — same multiset of columns, each column's dot
    product untouched."""
    from ipex_llm_tpu.parallel.manual import _block_perm, relayout_packed
    from ipex_llm_tpu.quantize.core import dequantize

    cfg, params = cfg_params
    assert relayout_packed(params, cfg, 1) is params

    out = relayout_packed(params, cfg, 4)
    idx = _block_perm((cfg.q_dim, cfg.kv_dim, cfg.kv_dim), 4)
    assert sorted(idx) == list(range(cfg.q_dim + 2 * cfg.kv_dim))
    w0 = np.asarray(dequantize(params["layers"]["qkv"]), np.float32)
    w1 = np.asarray(dequantize(out["layers"]["qkv"]), np.float32)
    np.testing.assert_array_equal(w1, w0[..., idx])


def test_ineligible_reasons(cfg_params):
    """The manual-tick routing: every unsupported shape falls back with a
    WRITTEN reason (the engine records it for /health-side debugging)."""
    from dataclasses import replace as _dc_replace

    from ipex_llm_tpu.parallel.manual import ineligible_reason

    cfg, params = cfg_params
    tp8 = make_mesh(MeshSpec(tp=8))
    assert ineligible_reason(cfg, params, tp8) is None
    assert "no tp axis" in ineligible_reason(
        cfg, params, make_mesh(MeshSpec(tp=1)))
    assert "composed mesh" in ineligible_reason(
        cfg, params, make_mesh(MeshSpec(dp=2, tp=4)))
    odd = _dc_replace(cfg, num_heads=6, num_kv_heads=6)
    assert "divide tp" in ineligible_reason(odd, params, tp8)


def test_engine_records_fallback_reason(cfg_params):
    """A composed mesh keeps the GSPMD path and the engine says why."""
    cfg, params = cfg_params
    eng = ServingEngine(
        cfg, params,
        EngineConfig(max_rows=2, max_seq_len=256, prefill_bucket=32),
        mesh=make_mesh(MeshSpec(dp=2, tp=2)),
    )
    assert not eng._tp_manual
    assert "composed mesh" in eng._tp_fallback_reason
    with pytest.raises(ValueError, match="unknown collective qtype"):
        ServingEngine(cfg, params,
                      EngineConfig(max_rows=2, max_seq_len=256,
                                   collective_qtype="fp4"))
