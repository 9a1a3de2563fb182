"""int4 weight pool behind the one-dispatch serving engine — the
``EngineConfig.weight_qtype`` axis.

The contracts under test (the low-bit-serving PR, ROADMAP item 3):

- **repack mechanics**: ``weight_qtype="sym_int4"`` re-packs every
  native-width linear weight in the stacked layer params (qkv/o/gate_up/
  down stacks, the lm head) into block-quantized QTensor planes through
  the real ``quantize/core.py`` codecs, leaves the embed table and norms
  alone, passes an already-packed tree through untouched, and is
  deterministic (two independently-built engines hold bit-identical
  planes);
- **engine-path bit-identity under int4** (the PR 5 fp8 pattern: lossy
  vs bf16, self-consistent across tick shapes): a row in a wave ≡ the
  row alone, and fused H8 ≡ H1, both over int4 weights — token streams
  AND logprobs;
- **qmatmul ≡ dequant-reference on the real layer body**: the decoder
  forward over packed planes is bitwise the forward over the
  pre-dequantized bf16 tree (the packing moved bytes, not math);
- **fault-domain composition**: a transient fault mid-tick over int4
  weights rolls back and retries bit-identically;
- **byte accounting**: ``weight_stats()``/the ``/health`` weights block
  report packed bytes, bf16-equivalent bytes, and the savings the KV
  pool is co-budgeted with.

Plus a slow-marked quality gate mirroring PR 5's fp8 gate: a >=64-step
greedy stream is self-consistent across horizons, and the int4
sliding-ppl ratio vs bf16 stays < 1.25.
"""

import json
import os
import sys
import urllib.request

import jax.numpy as jnp
import numpy as np
import pytest

from ipex_llm_tpu.kv import KVCache
from ipex_llm_tpu.models.build import (
    dequantize_params,
    param_bytes,
    requantize_params,
)
from ipex_llm_tpu.models.decoder import decoder_forward
from ipex_llm_tpu.quantize.core import QTensor
from ipex_llm_tpu.serving.engine import (
    EngineConfig,
    Request,
    ServingEngine,
    stream_tokens,
)
from ipex_llm_tpu.serving.faults import FaultInjector, TransientFault
from tests.test_decoder import rand_params, tiny_cfg
from tests.test_serving_mixed import (_assert_rows_do_not_depend_on_batch,
                                      _drive)

RNG = np.random.default_rng(93)

EC = dict(max_rows=4, max_seq_len=256, page_size=32, prefill_bucket=32)


@pytest.fixture(scope="module")
def cfg_params():
    cfg = tiny_cfg(vocab_size=131, hidden_size=48, intermediate_size=96,
                   num_heads=4, num_kv_heads=2, head_dim=12,
                   max_position_embeddings=512)
    return cfg, rand_params(cfg, qtype="bf16")


# -- repack mechanics --------------------------------------------------------

def test_repack_packs_linear_stacks_and_lm_head(cfg_params):
    """The weight axis re-packs exactly the linear weights: stacked layer
    QTensors and the lm head become uint8 int4 planes (packed rows = half
    the padded contraction rows), embed/norms keep their width, and the
    byte accounting shows the ~4.5 bits/weight the format promises."""
    cfg, params = cfg_params
    eng = ServingEngine(cfg, params,
                        EngineConfig(weight_qtype="sym_int4", **EC))
    lt = eng.params["layers"]
    for key in ("qkv", "o", "gate_up", "down"):
        qt = lt[key]
        assert isinstance(qt, QTensor) and qt.qtype == "sym_int4", key
        assert qt.data.dtype == jnp.uint8
        # stacked planes: [L, in_pad/2, out] with the logical shape intact
        in_pad = -(-qt.in_features // qt.block_size) * qt.block_size
        assert qt.data.shape == (cfg.num_layers, in_pad // 2,
                                 qt.out_features), key
    head = eng.params["lm_head"]
    assert isinstance(head, QTensor) and head.qtype == "sym_int4"
    assert eng.params["embed"].dtype == jnp.bfloat16   # gather path, untouched
    assert eng.params["final_norm"].dtype == jnp.float32

    ws = eng.weight_stats()
    assert ws["qtype"] == "sym_int4"
    assert ws["packed_qtypes"] == ["sym_int4"]
    assert ws["weight_bytes"] + ws["bytes_saved"] == ws["dense_bytes"]
    # the linear weights dominate this tree: packed must be well under
    # half the bf16 footprint (int4 codes + fp16 scales ~ 4.5/16 bits)
    assert ws["weight_bytes"] < ws["dense_bytes"] * 0.5, ws


def test_repack_deterministic_and_packed_tree_passes_through(cfg_params):
    """Two independently-built int4 engines hold bit-identical planes
    (the repack is a pure function of the tree), and handing the engine
    an ALREADY-packed tree is a pass-through — requantizing packed codes
    would stack quantization error, so it must not happen."""
    cfg, params = cfg_params
    e1 = ServingEngine(cfg, params,
                       EngineConfig(weight_qtype="sym_int4", **EC))
    e2 = ServingEngine(cfg, params,
                       EngineConfig(weight_qtype="sym_int4", **EC))
    for key in ("qkv", "down"):
        np.testing.assert_array_equal(
            np.asarray(e1.params["layers"][key].data),
            np.asarray(e2.params["layers"][key].data))
        np.testing.assert_array_equal(
            np.asarray(e1.params["layers"][key].scale_bits),
            np.asarray(e2.params["layers"][key].scale_bits))
    # pass-through: repacking e1's already-int4 tree (even at a DIFFERENT
    # requested width) returns the identical leaf objects
    repacked = requantize_params(e1.params, "nf4")
    assert repacked["layers"]["qkv"] is e1.params["layers"]["qkv"]
    assert repacked["lm_head"] is e1.params["lm_head"]
    # and a codec-less width on an ALREADY-packed tree is a pass-through
    # too, not a startup crash — build_server threads --low-bit q4_k into
    # weight_qtype for GGUF kquant checkpoints, whose leaves are packed
    # (requantize has nothing to do); only a full-width leaf that would
    # actually need the missing codec raises (covered below)
    assert requantize_params(e1.params, "q4_k")["layers"]["qkv"] \
        is e1.params["layers"]["qkv"]


def test_mismatched_width_on_packed_tree_warns_and_reports_served(cfg_params):
    """An explicit width over an already-packed tree is a by-design
    pass-through, but never a silent one: the build warns, and /health's
    weights.qtype reports the width actually served (the planes), with
    the ignored request echoed in requested_qtype."""
    cfg, params = cfg_params
    p4 = requantize_params(params, "sym_int4")
    with pytest.warns(UserWarning, match="already packed"):
        eng = ServingEngine(cfg, p4, EngineConfig(weight_qtype="nf4", **EC))
    ws = eng.weight_stats()
    assert ws["qtype"] == "sym_int4"          # the truth
    assert ws["requested_qtype"] == "nf4"     # the ignored ask
    assert ws["packed_qtypes"] == ["sym_int4"]
    # a tree packed at MORE THAN ONE width (mixed-precision: int8 head
    # over an int4 body) reports "mixed" with packed_qtypes carrying the
    # list — even when the request matches ONE of the planes, a single
    # name would claim a uniformity the tree does not have
    p_mixed = dict(p4, lm_head=requantize_params(
        {"lm_head": params["lm_head"]}, "sym_int8")["lm_head"])
    with pytest.warns(UserWarning, match="already packed"):
        eng2 = ServingEngine(cfg, p_mixed,
                             EngineConfig(weight_qtype="nf4", **EC))
    assert eng2.weight_stats()["qtype"] == "mixed"
    eng3 = ServingEngine(cfg, p_mixed,
                         EngineConfig(weight_qtype="sym_int4", **EC))
    ws3 = eng3.weight_stats()
    assert ws3["qtype"] == "mixed"            # matching request: still mixed
    assert ws3["requested_qtype"] == "sym_int4"
    assert ws3["packed_qtypes"] == ["sym_int4", "sym_int8"]


def test_plain_array_tree_warns_and_reports_unpacked(cfg_params):
    """A packed width requested over a tree with NO QTensor leaves (a
    dequantized dense twin — bare arrays, which the repack cannot tell
    apart from embed tables) must not let /health claim a width nothing
    serves: the build warns and qtype reports None."""
    cfg, params = cfg_params
    dense = dequantize_params(requantize_params(params, "sym_int4"))
    with pytest.warns(UserWarning, match="no quantizable"):
        eng = ServingEngine(cfg, dense,
                            EngineConfig(weight_qtype="sym_int4", **EC))
    ws = eng.weight_stats()
    assert ws["qtype"] is None
    assert ws["requested_qtype"] == "sym_int4"
    assert ws["packed_qtypes"] == [] and ws["bytes_saved"] == 0


def test_alias_width_reports_canonical(cfg_params):
    """A registered alias axis ("woq_int4" -> sym_int4) packs — and
    reports — the canonical format; the raw alias survives only in
    requested_qtype."""
    cfg, params = cfg_params
    eng = ServingEngine(cfg, params,
                        EngineConfig(weight_qtype="woq_int4", **EC))
    ws = eng.weight_stats()
    assert ws["qtype"] == "sym_int4"
    assert ws["requested_qtype"] == "woq_int4"
    assert ws["packed_qtypes"] == ["sym_int4"]


def test_engine_rejects_unknown_and_unrequantizable_qtype(cfg_params):
    cfg, params = cfg_params
    with pytest.raises(ValueError, match="load_in_low_bit"):
        ServingEngine(cfg, params, EngineConfig(weight_qtype="int3", **EC))
    with pytest.raises(ValueError, match="requantize"):
        ServingEngine(cfg, params, EngineConfig(weight_qtype="q4_k", **EC))
    # a native width is a no-op, not an error
    eng = ServingEngine(cfg, params, EngineConfig(weight_qtype="bf16", **EC))
    assert eng.weight_stats()["packed_qtypes"] == []


# -- qmatmul ≡ dequant-reference on the real layer body ----------------------

def test_layer_body_matches_dequant_reference_bitwise(cfg_params):
    """The real decoder forward over int4 planes produces bitwise the
    logits of the same forward over the pre-dequantized bf16 tree
    (models/build.dequantize_params, the full-width twin): the qmatmul
    path (dequant fused next to the matmul) moves HBM bytes, not math.
    This is the oracle the Pallas kernel path is also held to (ops-level
    kernel equivalence lives in test_pallas/test_quantize)."""
    cfg, params = cfg_params
    p4 = requantize_params(params, "sym_int4")
    dense = dequantize_params(p4)
    b, t = 2, 12
    tokens = jnp.asarray(RNG.integers(0, cfg.vocab_size, (b, t)), jnp.int32)
    pos = jnp.broadcast_to(jnp.arange(t)[None], (b, t))

    def run(p):
        cache = KVCache.init(cfg.num_layers, b, t, cfg.num_kv_heads,
                             cfg.head_dim)
        logits, _ = decoder_forward(cfg, p, tokens, cache, pos)
        return np.asarray(logits)

    np.testing.assert_array_equal(run(p4), run(dense))


# -- engine-path bit-identity under int4 -------------------------------------

def _wave_specs(cfg):
    p1 = list(RNG.integers(0, cfg.vocab_size, 40))
    p2 = list(RNG.integers(0, cfg.vocab_size, 70))
    p3 = list(RNG.integers(0, cfg.vocab_size, 24))
    return [
        dict(prompt_ids=p1, max_new_tokens=12),
        dict(prompt_ids=p2, max_new_tokens=12, temperature=0.8, top_p=0.9,
             top_k=40, seed=123),
        dict(prompt_ids=p3, max_new_tokens=12),
    ]


def test_a_row_in_a_wave_matches_the_row_alone_int4(cfg_params):
    """Batch invariance survives the weight width: over int4 weights a
    request inside a staggered wave emits the exact token AND logprob
    streams it emits served alone (both lossy vs bf16 in the same way)."""
    cfg, params = cfg_params
    specs = _wave_specs(cfg)
    eng, _ = _assert_rows_do_not_depend_on_batch(
        cfg, params, EngineConfig(weight_qtype="sym_int4", **EC),
        {0: [specs[0]], 1: [specs[1]], 3: [specs[2]]})
    assert eng.metrics["mixed_steps"] > 0
    assert eng.params["layers"]["qkv"].qtype == "sym_int4"


# -- the packed planes are read where they lie --------------------------------
#
# With the kernel on (interpreted here) a layer scan hands each quantized
# linear its stack and the layer's index, and nothing in the program takes a
# plane of packed codes out of its stack: no dynamic_slice of one, no scan
# that carries one as ``xs``.  The widths below leave every plane at the
# kernel's tiles (a stack that is not is sliced: tests/test_pallas.py).

def _tiled_cfg_params():
    cfg = tiny_cfg(vocab_size=128, hidden_size=128, intermediate_size=128,
                   num_heads=12, num_kv_heads=2, head_dim=8,
                   max_position_embeddings=512)
    return cfg, rand_params(cfg, qtype="bf16")


def _paged_forward_jaxpr(cfg, params, state=None):
    from dataclasses import replace

    import jax

    from ipex_llm_tpu.kv import PagedKVCache

    rows, width = 2, 6
    cache = replace(
        PagedKVCache.init(cfg.kv_layers, 8, rows, 3, cfg.num_kv_heads, 16,
                          cfg.head_dim),
        tables=jnp.asarray([[1, 2, 3], [4, 5, -1]], jnp.int32), state=state)
    base = jnp.asarray([0, 14], jnp.int32)
    pos = base[:, None] + jnp.arange(width)[None, :]
    return jax.make_jaxpr(lambda p, c: decoder_forward(
        cfg, p, jnp.zeros((rows, width), jnp.int32), c, pos,
        slot_offsets=base, chunk_lens=jnp.asarray([6, 4], jnp.int32),
        gather_positions=jnp.asarray([5, 3], jnp.int32)))(params, cache)


@pytest.mark.parametrize("family", ["dense", "hybrid"])
def test_layer_scan_takes_no_packed_plane_out_of_its_stack(
        kernel_forced, monkeypatch, family):
    """The int4 forward over the paged pool, kernel forced: the dense
    families' layer scan and the hybrid ``unit`` scan (Mamba, attention
    under its ``cond``, the shared expert) hold one kernel call a linear
    and no copy of a packed plane.  With the kernel off the same scans
    slice the layer's planes, which feed XLA operations there."""
    from ipex_llm_tpu import kv
    from ipex_llm_tpu.ops import dispatch
    from tests.test_pallas import copies_of_packed_planes, kernel_calls

    if family == "dense":
        cfg, params = _tiled_cfg_params()
        params, state = requantize_params(params, "sym_int4"), None
        linears = 4 + 1           # the scanned layer's four, and the head
    else:
        from ipex_llm_tpu.models.families import FAMILIES
        from tests.test_nemotron_h import gen, tiny_hf

        hf = tiny_hf()
        cfg = FAMILIES["nemotron_h"].to_config(hf)
        params = gen.param_tree(cfg, hf, "sym_int4", 7)
        state = kv.recurrent_state(cfg, 2)
        # a unit's Mamba in/out, attention qkv/o, shared expert up/down;
        # the head (the routed experts are ``qmatmul_expert`` calls)
        linears = 6 + 1
    closed = _paged_forward_jaxpr(cfg, params, state)
    assert copies_of_packed_planes(closed.jaxpr) == []
    assert kernel_calls(closed.jaxpr, "qmatmul") - kernel_calls(
        closed.jaxpr, "qmatmul_expert") == linears
    # the guard sees the defect it guards against
    monkeypatch.setattr(dispatch, "_use_pallas_env", lambda op=None: False)
    assert copies_of_packed_planes(
        _paged_forward_jaxpr(cfg, params, state).jaxpr)


def test_a_row_in_a_wave_matches_the_row_alone_on_the_kernel(kernel_forced,
                                                             monkeypatch):
    """The batch-invariance oracle with the dequant-matmul kernel on
    (interpreted), so the view of the stack travels the whole tick: every
    tick variant the wave compiles reads its packed planes in place, and a
    request's tokens and logprob bits are those it gets alone."""
    from ipex_llm_tpu.serving import engine as engine_mod
    from tests.test_pallas import copies_of_packed_planes

    cfg, params = _tiled_cfg_params()
    real, seen = engine_mod._ragged_tick, {}

    def recorded(*args, **kw):
        key = (kw.get("horizon"), kw.get("with_decode"),
               None if kw.get("prefill") is None
               else (kw["epoch"].p_b, kw["epoch"].width))
        if key not in seen:
            seen[key] = real.trace(*args, **kw).jaxpr
        return real(*args, **kw)

    monkeypatch.setattr(engine_mod, "_ragged_tick", recorded)
    specs = _wave_specs(cfg)
    eng, _ = _assert_rows_do_not_depend_on_batch(
        cfg, params, EngineConfig(weight_qtype="sym_int4", **EC),
        {0: [specs[0]], 1: [specs[1]], 3: [specs[2]]})
    assert eng.metrics["mixed_steps"] > 0
    assert any(k[2] is None for k in seen) and any(
        k[2] is not None for k in seen)          # steady and mixed ticks
    for key, closed in seen.items():
        assert "qmatmul" in str(closed), key
        assert copies_of_packed_planes(closed.jaxpr) == [], key


@pytest.mark.parametrize("family", ["dense", "held-expert"])
def test_the_tick_reads_its_scale_planes_where_they_lie(kernel_forced,
                                                        monkeypatch, family):
    """The lowered ``_ragged_tick`` of a stacked dense model and of one with
    held experts, kernel forced: every scale plane in the tree is uint16 bit
    patterns; the EXPERT kernel takes the whole stack of them (rank 3) and
    nothing stands between the program's parameter and that call (no slice,
    gather, pad or conversion of an expert stack's planes); the DENSE kernel
    takes the layer's float32 plane (PERF.md section 6, PR 47, says why)."""
    import jax

    from ipex_llm_tpu.serving import engine as engine_mod
    from tests.test_pallas import kernel_scale_operands, scale_plane_passes

    if family == "dense":
        cfg, params = _tiled_cfg_params()
        ec = EngineConfig(weight_qtype="sym_int4", **EC)
    else:
        from ipex_llm_tpu.models.families import FAMILIES
        from tests.test_nemotron_h import gen, tiny_hf

        hf = tiny_hf()
        cfg = FAMILIES["nemotron_h"].to_config(hf)
        params = gen.param_tree(cfg, hf, "sym_int4", 7)
        ec = EngineConfig(max_rows=2, max_seq_len=256, page_size=16,
                          prefill_bucket=16)
    real, seen = engine_mod._ragged_tick, {}

    def recorded(*args, **kw):
        key = (kw.get("horizon"), kw.get("with_decode"),
               kw.get("prefill") is None)
        if key not in seen:
            seen[key] = real.trace(*args, **kw).jaxpr
        return real(*args, **kw)

    monkeypatch.setattr(engine_mod, "_ragged_tick", recorded)
    eng = ServingEngine(cfg, params, ec).start()
    try:
        req = eng.submit(Request(
            prompt_ids=list(RNG.integers(0, cfg.vocab_size, 20)),
            max_new_tokens=3))
        assert len(list(stream_tokens(req, timeout=300))) == 3
    finally:
        eng.stop()
    held = [q for q in jax.tree_util.tree_leaves(
        eng.params, is_leaf=lambda a: isinstance(a, QTensor))
        if isinstance(q, QTensor) and q.scale_bits is not None]
    assert held and all(q.scale_bits.dtype == jnp.uint16 for q in held)
    # an expert stack's planes: [L, E, K/32, N] in the tree, [L x E, ...] at
    # the kernel
    expert = {shape for q in held if q.data.ndim == 4
              for shape in (q.scale_bits.shape, (
                  q.scale_bits.shape[0] * q.scale_bits.shape[1],
                  *q.scale_bits.shape[2:]))}
    assert bool(expert) == (family == "held-expert")
    assert any(k[2] for k in seen) and any(not k[2] for k in seen)
    for key, closed in seen.items():
        assert set(kernel_scale_operands(closed.jaxpr, "qmatmul")) - {
            ("uint16", 3)} == {("float32", 2)}, key
        if expert:
            assert set(kernel_scale_operands(
                closed.jaxpr, "qmatmul_expert")) == {("uint16", 3)}, key
        assert [p for p in scale_plane_passes(closed.jaxpr)
                if p[1] in expert] == [], key


def test_fused_h8_bit_identical_to_h1_int4(cfg_params):
    """The PR 1 equivalence contract over packed weights: H=8 fused
    decode emits the H=1 int4 engine's exact streams (greedy and seeded
    sampled)."""
    cfg, params = cfg_params
    p1 = list(RNG.integers(0, cfg.vocab_size, 9))
    p2 = list(RNG.integers(0, cfg.vocab_size, 17))
    specs = [
        dict(prompt_ids=p1, max_new_tokens=16),
        dict(prompt_ids=p2, max_new_tokens=16, temperature=0.8,
             top_p=0.9, top_k=40, seed=123),
    ]

    def run(h):
        sched = {0: [Request(**s) for s in specs]}
        eng = ServingEngine(cfg, params, EngineConfig(
            weight_qtype="sym_int4", decode_horizon=h, **EC))
        streams = _drive(eng, sched)
        return [r for rs in sched.values() for r in rs], streams, eng

    r1, s1, _ = run(1)
    r8, s8, e8 = run(8)
    for a, b in zip(s1, s8):
        assert a == b, (a, b)
    for a, b in zip(r1, r8):
        assert a.finish_reason == b.finish_reason
        np.testing.assert_array_equal(
            np.asarray(a.logprobs, np.float32),
            np.asarray(b.logprobs, np.float32))
    assert e8.metrics["decode_horizon_effective"] == 8
    assert e8.metrics["host_syncs"] < e8.metrics["steps"]


# -- fault-domain composition ------------------------------------------------

def _drive_ticks(eng, reqs, max_ticks=3000):
    for r in reqs:
        eng.submit(r)
    for _ in range(max_ticks):
        eng._tick()
        if all(r.finish_reason is not None for r in reqs):
            break
    assert all(r.finish_reason is not None for r in reqs)
    return [list(stream_tokens(r, timeout=10)) for r in reqs]


def test_transient_fault_rollback_over_int4_tick(cfg_params):
    """A transient fault mid-tick over int4 weights: rollback + retry
    reproduces the unfaulted int4 run bit-for-bit (the packed planes are
    held, never donated, so a replayed tick reads the same codes)."""
    cfg, params = cfg_params
    prompts = [list(RNG.integers(0, cfg.vocab_size, n)) for n in (40, 70)]

    def wave():
        return [Request(prompt_ids=p, max_new_tokens=8) for p in prompts]

    ec = EngineConfig(weight_qtype="sym_int4", retry_backoff_s=0.001, **EC)
    base_streams = _drive_ticks(ServingEngine(cfg, params, ec), wave())

    inj = FaultInjector().inject("decode-dispatch", TransientFault, nth=2)
    eng = ServingEngine(cfg, params, ec, fault_injector=inj)
    reqs = wave()
    streams = _drive_ticks(eng, reqs)
    assert inj.fired == 1
    assert eng.metrics["retries"] == 1
    assert streams == base_streams
    assert all(r.finish_reason == "length" for r in reqs)
    # the packed planes survived the rollback's epoch re-upload untouched
    assert eng.params["layers"]["qkv"].data.dtype == jnp.uint8


# -- /health weights block ---------------------------------------------------

def test_health_weights_block_reports_packed_bytes(cfg_params):
    """End-to-end /health: the weights block rides next to the kv block
    — qtype, packed bytes, bf16-equivalent bytes, bytes saved — and the
    flat /metrics exposition carries the numeric series."""
    pytest.importorskip("aiohttp")
    from ipex_llm_tpu.serving.api_server import OpenAIServer
    from tests.test_serving_faults import _Tok, _spin_server

    cfg, params = cfg_params
    packed, dense = param_bytes(requantize_params(params, "sym_int4"))
    eng = ServingEngine(cfg, params,
                        EngineConfig(weight_qtype="sym_int4", **EC)).start()
    srv = OpenAIServer(eng, _Tok(), "tiny")
    loop, port = _spin_server(srv)
    try:
        health = json.loads(urllib.request.urlopen(
            f"http://127.0.0.1:{port}/health", timeout=30).read())
        w = health["weights"]
        assert w["qtype"] == "sym_int4"
        assert w["weight_bytes"] == packed
        assert w["dense_bytes"] == dense
        assert w["bytes_saved"] == dense - packed > 0
        assert "kv" in health            # side by side with the pool bytes
        metrics = json.loads(urllib.request.urlopen(
            f"http://127.0.0.1:{port}/metrics?format=json",
            timeout=30).read())["metrics"]
        assert metrics["weights_weight_bytes"] == packed
        assert metrics["weights_bytes_saved"] == dense - packed
    finally:
        loop.call_soon_threadsafe(loop.stop)
        eng.stop()


# -- quality gate (slow tier) ------------------------------------------------

@pytest.mark.slow
def test_int4_quality_gate_long_greedy_and_ppl_ratio(cfg_params):
    """Slow quality gate for int4 weights (the PR 5 fp8 pattern): (1) a
    >=64-step greedy stream through the int4 engine is self-consistent
    across horizons (H=8 reproduces H=1 bit-for-bit); (2) the int4
    sliding-ppl ratio vs the bf16 tree stays < 1.25 on the builtin
    corpus — the reference ships sym_int4 as its headline production
    format, and the engine's planes are the same codec."""
    cfg, params = cfg_params
    prompt = list(RNG.integers(0, cfg.vocab_size, 24))

    def run(h):
        eng = ServingEngine(cfg, params, EngineConfig(
            max_rows=2, max_seq_len=256, page_size=32, prefill_bucket=32,
            weight_qtype="sym_int4", decode_horizon=h))
        (stream,) = _drive(eng, {0: [Request(prompt_ids=prompt,
                                             max_new_tokens=96)]},
                           max_ticks=6000)
        return stream

    s1, s8 = run(1), run(8)
    assert len(s1) == 96 and s1 == s8

    bench_dir = os.path.join(os.path.dirname(__file__), "..", "benchmark")
    sys.path.insert(0, bench_dir)
    try:
        import ppl as ppl_mod
    finally:
        sys.path.remove(bench_dir)

    ids = (np.asarray(ppl_mod.builtin_tokens(None, n_tokens=768), np.int64)
           % cfg.vocab_size).astype(np.int32)
    p4 = requantize_params(params, "sym_int4")
    p_bf16 = ppl_mod.sliding_ppl(cfg, params, ids, seq_len=256, stride=128)
    p_int4 = ppl_mod.sliding_ppl(cfg, p4, ids, seq_len=256, stride=128)
    ratio = p_int4 / p_bf16
    assert ratio < 1.25, (p_bf16, p_int4)
