"""Pallas kernel tests (interpreter mode on CPU).

The jnp reference ops are the oracle (the reference's CPU-fallback testing
pattern, SURVEY.md §4); the kernels must match them elementwise within
bf16-accumulation tolerance.  On real TPU the same wrappers compile through
Mosaic; here they run interpreted so CI exercises identical code paths.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ipex_llm_tpu.ops.attention import sdpa_reference
from ipex_llm_tpu.ops.linear import qmatmul_reference
from ipex_llm_tpu.ops.pallas.decode_attention import decode_sdpa
from ipex_llm_tpu.ops.pallas.flash_attention import flash_sdpa
from ipex_llm_tpu.ops.pallas.qmatmul import qmatmul_pallas
from ipex_llm_tpu.quantize import quantize
from tests.test_paged_pool_inplace import _bytes as _bits

RNG = np.random.default_rng(7)


@pytest.mark.parametrize("qtype", ["sym_int4", "asym_int4", "sym_int8", "nf4",
                                   "fp4", "sym_int5", "asym_int5", "fp6",
                                   "fp8_e4m3", "fp8_e5m2"])
def test_qmatmul_pallas_matches_reference(qtype):
    """All kernel formats incl. the r4 additions (VERDICT weak #5: fp8/fp6/
    int5 previously took the XLA dequant path; BASELINE tracks fp6/fp8
    driver configs)."""
    k, n, m = 160, 200, 3
    if qtype in ("fp8_e4m3", "fp8_e5m2"):
        k = 256  # fp8 block_size=128: cover 2 whole blocks
    w = (RNG.standard_normal((k, n)) * 0.05).astype(np.float32)
    x = (RNG.standard_normal((m, k)) * 0.5).astype(np.float32)
    qt = quantize(w, qtype)
    want = np.asarray(qmatmul_reference(jnp.asarray(x), qt))
    got = np.asarray(qmatmul_pallas(jnp.asarray(x), qt))
    np.testing.assert_allclose(got, want, rtol=2e-2, atol=2e-2)


def test_qmatmul_pallas_batched_input():
    k, n = 64, 128
    w = (RNG.standard_normal((k, n)) * 0.05).astype(np.float32)
    x = RNG.standard_normal((2, 5, k)).astype(np.float32)
    qt = quantize(w, "sym_int4")
    want = np.asarray(qmatmul_reference(jnp.asarray(x), qt))
    got = np.asarray(qmatmul_pallas(jnp.asarray(x), qt))
    assert got.shape == (2, 5, n)
    np.testing.assert_allclose(got, want, rtol=2e-2, atol=2e-2)


def test_qmatmul_pallas_bf16_activations():
    k, n = 128, 256
    w = (RNG.standard_normal((k, n)) * 0.05).astype(np.float32)
    x = (RNG.standard_normal((4, k))).astype(jnp.bfloat16)
    qt = quantize(w, "sym_int8")
    want = np.asarray(qmatmul_reference(jnp.asarray(x), qt)).astype(np.float32)
    got = np.asarray(qmatmul_pallas(jnp.asarray(x), qt)).astype(np.float32)
    np.testing.assert_allclose(got, want, rtol=5e-2, atol=5e-2)


# -- a layer of a stack read where it lies (quantize/core.py QLayer) -----------
#
# The stacked call (the stack and a traced layer index, through the single
# entry ops/linear.qmatmul with the kernel forced) against the per-plane call
# on stack[layer], bit for bit, whichever route the operand takes.

_FORMATS = ("sym_int4", "asym_int4", "nf4", "fp4", "sym_int5", "asym_int5",
            "sym_int8", "fp8_e4m3", "fp8_e5m2", "fp6")


def _stack_of(qtype, layers, k, n, pad=True):
    from ipex_llm_tpu.quantize.core import pad_planes

    qts = [quantize((RNG.standard_normal((k, n)) * 0.05).astype(np.float32),
                    qtype) for _ in range(layers)]
    if pad:
        qts = [pad_planes(q) for q in qts]
    return jax.tree_util.tree_map(lambda *a: jnp.stack(a), *qts), qts


def copies_of_packed_planes(jaxpr) -> list:
    """What in a program takes a layer's plane OUT of a stack of packed
    codes (uint8, rank >= 3): a dynamic_slice or gather of it, or a scan that
    carries it as ``xs``.  A kernel call reads such a stack whole."""
    from ipex_llm_tpu.analysis.trace.tracer import _iter_subjaxprs

    def packed(v):
        a = getattr(v, "aval", None)
        return (a is not None and str(getattr(a, "dtype", "")) == "uint8"
                and len(a.shape) >= 3)

    found = []
    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        if name in ("dynamic_slice", "gather") and packed(eqn.invars[0]):
            found.append((name, eqn.invars[0].aval.shape))
        if name == "scan":
            skip = eqn.params["num_consts"] + eqn.params["num_carry"]
            found += [("scan xs", v.aval.shape) for v in eqn.invars[skip:]
                      if packed(v)]
        for sub in eqn.params.values():
            for j in _iter_subjaxprs(sub):
                found += copies_of_packed_planes(j)
    return found


def _equations(jaxpr, into_kernels=True):
    """Every equation of a program, those of its sub-programs included."""
    from ipex_llm_tpu.analysis.trace.tracer import _iter_subjaxprs

    for eqn in jaxpr.eqns:
        yield eqn
        if eqn.primitive.name == "pallas_call" and not into_kernels:
            continue
        for sub in eqn.params.values():
            for j in _iter_subjaxprs(sub):
                yield from _equations(j, into_kernels)


def scale_plane_passes(jaxpr) -> list:
    """What in a program touches a scale plane (the fp16 scales or their
    uint16 bit patterns, rank >= 2) on its way to a consumer: a slice, a
    gather, a pad or a conversion of it, or a scan that carries it as ``xs``,
    outside a kernel (which converts its tile inside).  A kernel call reads
    the stack of bit patterns whole."""
    def plane(v):
        a = getattr(v, "aval", None)
        return (a is not None and len(getattr(a, "shape", ())) >= 2
                and str(a.dtype) in ("uint16", "float16"))

    found = []
    for eqn in _equations(jaxpr, into_kernels=False):
        name = eqn.primitive.name
        if name in ("dynamic_slice", "gather", "pad", "convert_element_type",
                    "bitcast_convert_type") and plane(eqn.invars[0]):
            found.append((name, eqn.invars[0].aval.shape))
        if name == "scan":
            skip = eqn.params["num_consts"] + eqn.params["num_carry"]
            found += [("scan xs", v.aval.shape) for v in eqn.invars[skip:]
                      if plane(v)]
    return found


def kernel_scale_operands(jaxpr, prefix: str) -> list:
    """(dtype, rank) of what the kernel calls whose name starts with
    ``prefix`` take as scale planes: their operands of rank >= 2 that are
    neither packed codes (uint8) nor bf16 activations."""
    return [(str(v.aval.dtype), len(v.aval.shape))
            for eqn in _equations(jaxpr, into_kernels=False)
            if eqn.primitive.name == "pallas_call"
            and str(eqn.params["name"]).startswith(prefix)
            for v in eqn.invars if len(v.aval.shape) >= 2
            and str(v.aval.dtype) not in ("uint8", "bfloat16")]


def kernel_calls(jaxpr, prefix: str) -> int:
    """The kernel calls in a program whose name starts with ``prefix``, a
    scan's body counted once."""
    from ipex_llm_tpu.analysis.trace.tracer import _iter_subjaxprs

    return sum(
        (eqn.primitive.name == "pallas_call"
         and str(eqn.params["name"]).startswith(prefix))
        + sum(kernel_calls(j, prefix) for sub in eqn.params.values()
              for j in _iter_subjaxprs(sub))
        for eqn in jaxpr.eqns)


def _slices_a_packed_stack(fn, *args) -> bool:
    return bool(copies_of_packed_planes(jax.make_jaxpr(fn)(*args).jaxpr))


def _assert_layers_are_the_planes(stack, planes, x):
    from ipex_llm_tpu.ops.linear import qmatmul
    from ipex_llm_tpu.quantize.core import QLayer

    stacked = jax.jit(lambda x, s, l: qmatmul(x, QLayer(s, l)))
    n = len(planes)
    for layer in sorted({0, n // 2, n - 1}):
        got = stacked(x, stack, jnp.int32(layer))
        want = qmatmul(x, planes[layer])
        assert got.dtype == want.dtype and got.shape == want.shape
        np.testing.assert_array_equal(_bits(got), _bits(want),
                                      err_msg=f"{stack.qtype} layer {layer}")


@pytest.mark.parametrize("m", [1, 16, 128])
@pytest.mark.parametrize("qtype", _FORMATS)
def test_a_layer_of_a_stack_is_that_layers_plane(kernel_forced, qtype, m):
    """Every kernel format, the first, a middle and the last layer by a
    traced index: the bits of the per-plane call.  The sym 4-bit stacks,
    padded at build, are read in place (no slice of the codes in the
    program); the 5-bit ones, two planes cut out of one array, are sliced."""
    from ipex_llm_tpu.ops.linear import qmatmul
    from ipex_llm_tpu.ops.pallas.qmatmul import stacked_supported
    from ipex_llm_tpu.quantize.core import QLayer

    stack, planes = _stack_of(qtype, 3, 256, 256)
    x = jnp.asarray(RNG.standard_normal((m, 256)) * 0.5, jnp.bfloat16)
    _assert_layers_are_the_planes(stack, planes, x)
    in_place = stacked_supported(stack)
    if qtype in ("sym_int4", "nf4", "fp4"):
        assert in_place
    if qtype in ("sym_int5", "asym_int5"):
        assert not in_place
    assert _slices_a_packed_stack(
        lambda x, s, l: qmatmul(x, QLayer(s, l)), x, stack,
        jnp.int32(1)) == (not in_place)


@pytest.mark.parametrize("m", [1, 16, 128])
def test_a_stack_whose_k_the_tiles_pad_is_read_in_place(kernel_forced, m):
    """K = 2688 is padded to 3072 at build (two K tiles of 1536): the
    stacked call walks both, and gives the plane's bits."""
    from ipex_llm_tpu.ops.pallas.qmatmul import stacked_supported

    stack, planes = _stack_of("sym_int4", 2, 2688, 128)
    assert stack.data.shape == (2, 1536, 128) and stacked_supported(stack)
    x = jnp.asarray(RNG.standard_normal((m, 2688)) * 0.5, jnp.bfloat16)
    _assert_layers_are_the_planes(stack, planes, x)


@pytest.mark.parametrize("k,n", [(256, 256), (2688, 128)])
def test_a_rows_bits_do_not_depend_on_the_m_tile(kernel_forced, k, n):
    """Up to 256 rows are ONE M tile (144: a mixed tick's chunk of 128 and
    its 16 decode rows in one forward, so a weight tile is fetched and
    dequantized once for all of them): each row has at M = 144 the bits it
    has at M = 128 and at M = 16, over one K tile and over two.  Past 256
    rows the tile is 128."""
    from ipex_llm_tpu.ops.linear import qmatmul
    from ipex_llm_tpu.quantize.core import QLayer

    stack, _ = _stack_of("sym_int4", 2, k, n)
    x = jnp.asarray(RNG.standard_normal((272, k)) * 0.5, jnp.bfloat16)
    call = jax.jit(lambda x, s, l: qmatmul(x, QLayer(s, l)))

    def m_tiles(m):
        (eqn,) = [e for e in _equations(
            jax.make_jaxpr(call)(x[:m], stack, jnp.int32(1)).jaxpr,
            into_kernels=False) if e.primitive.name == "pallas_call"]
        return eqn.params["grid_mapping"].grid[0]

    assert [m_tiles(m) for m in (16, 128, 144, 256, 272)] == [1, 1, 1, 1, 3]
    whole = call(x[:144], stack, jnp.int32(1))
    np.testing.assert_array_equal(
        _bits(whole[:128]), _bits(call(x[:128], stack, jnp.int32(1))))
    np.testing.assert_array_equal(
        _bits(whole[128:]), _bits(call(x[128:144], stack, jnp.int32(1))))
    # and beyond one tile: the rows of three tiles of 128
    np.testing.assert_array_equal(
        _bits(call(x, stack, jnp.int32(1))[:144]), _bits(whole))


def test_a_stack_not_padded_to_tiles_is_sliced_and_gives_the_same_bits(
        kernel_forced):
    """N = 200 is no whole lane tile: the layer is sliced out and padded in
    the call, as a lone weight is."""
    from ipex_llm_tpu.ops.linear import qmatmul
    from ipex_llm_tpu.ops.pallas.qmatmul import stacked_supported
    from ipex_llm_tpu.quantize.core import QLayer

    stack, planes = _stack_of("sym_int4", 3, 160, 200, pad=False)
    assert not stacked_supported(stack)
    x = jnp.asarray(RNG.standard_normal((5, 160)) * 0.5, jnp.bfloat16)
    _assert_layers_are_the_planes(stack, planes, x)
    assert _slices_a_packed_stack(
        lambda x, s, l: qmatmul(x, QLayer(s, l)), x, stack, jnp.int32(1))


@pytest.mark.parametrize("qtype", ["sym_int4", "sym_int5", "asym_int4"])
def test_a_lone_weight_is_the_kernel_over_a_stack_of_one(kernel_forced,
                                                         qtype):
    """A rank-2 weight (the head) and the same planes as a stack of one
    layer: one kernel, the same bits."""
    from ipex_llm_tpu.ops.linear import qmatmul
    from ipex_llm_tpu.quantize.core import QLayer

    stack, (plane,) = _stack_of(qtype, 1, 256, 384)
    x = jnp.asarray(RNG.standard_normal((7, 256)) * 0.5, jnp.bfloat16)
    np.testing.assert_array_equal(
        _bits(qmatmul(x, QLayer(stack, jnp.int32(0)))),
        _bits(qmatmul(x, plane)))
    want = np.asarray(qmatmul_reference(x, plane), np.float32)
    np.testing.assert_allclose(np.asarray(qmatmul(x, plane), np.float32),
                               want, rtol=5e-2, atol=5e-2)


# -- the scale planes read where they lie (QTensor.scale_bits) -----------------
#
# A QTensor holds its fp16 scales as their uint16 bit patterns.  The expert
# kernel takes a tile of them out of the whole stack by the codes' own index
# map and converts it inside; the dense kernel streams the layer's plane as
# float32, converted by XLA before the call.  Both are the same float32
# numbers times the same codes in the same order, hence the same bits.

_ODD_SCALES = np.array(        # fp16 patterns a conversion could get wrong
    [0x0000, 0x8000, 0x0001, 0x8001, 0x03FF, 0x83FF, 0x0400, 0x8400,
     0x3C00, 0xBC00, 0x7BFF, 0xFBFF, 0x2E66, 0xAE66, 0x0200, 0x8200],
    np.uint16)

# K tiles of 256 (one), 2048, 1792, 1536 and 1280 rows: 16 (a stack padded
# to 512: two blocks of 8), 64, 56, 48 and 40 scale rows a tile
_SCALE_CASES = [(k, odd) for k in (256, 4096, 3584, 3072, 5120)
                for odd in (False, True)]


@pytest.mark.parametrize("k,odd", _SCALE_CASES,
                         ids=[f"{k}{'-odd' if o else ''}"
                              for k, o in _SCALE_CASES])
def test_the_expert_kernel_converts_scale_bits_as_xla_converts_planes(k, odd):
    """An entry of a stack through the expert kernel (bit patterns read in
    place, converted in the kernel) and the same entry through the dense
    kernel (its plane sliced out and converted to float32 by XLA): one
    result, bit for bit, subnormal, zero and negative scales included."""
    from ipex_llm_tpu.ops.pallas import qmatmul as qmm
    from ipex_llm_tpu.quantize.core import dequantize

    n, entries = 128, 3
    stack, _ = _stack_of("sym_int4", entries, k, n)
    assert stack.scale_bits.dtype == jnp.uint16 and qmm.grouped_supported(
        stack)
    if odd:
        stack = dataclasses.replace(stack, scale_bits=jnp.asarray(
            np.resize(_ODD_SCALES, stack.scale_bits.shape)))
    x = jnp.asarray(RNG.standard_normal((16, k)) * 0.5, jnp.bfloat16)
    ids = jnp.array([2, 0], jnp.int32)
    got = qmm.qmatmul_pallas_grouped(x, stack, ids, jnp.int32(2))
    for tile, entry in enumerate([2, 0]):
        plane = jax.tree_util.tree_map(lambda a, e=entry: a[e], stack)
        want = qmatmul_pallas(x, plane, keep_f32=True)
        np.testing.assert_array_equal(_bits(got[tile]), _bits(want))
        plain = np.asarray(x.astype(jnp.float32) @ dequantize(plane))
        scale = max(1.0, float(np.abs(plain).max()))
        np.testing.assert_allclose(np.asarray(got[tile]) / scale,
                                   plain / scale, atol=2e-2)


def test_f16_bits_to_f32_is_astype_over_every_finite_pattern():
    from ipex_llm_tpu.ops.pallas.qmatmul import _f16_bits_to_f32

    bits = np.arange(1 << 16, dtype=np.uint16)
    bits = bits[(bits & 0x7C00) != 0x7C00]           # not inf, not nan
    got = np.asarray(_f16_bits_to_f32(jnp.asarray(bits, jnp.int32)))
    want = bits.view(np.float16).astype(np.float32)
    np.testing.assert_array_equal(got.view(np.uint32), want.view(np.uint32))


@pytest.mark.parametrize("per_tile", [False, True])
def test_the_grouped_kernel_reads_a_group_out_of_the_whole_stack(per_tile):
    """Entries named from ``first`` on in the whole ``[L x E, ...]`` stack
    give the bits of the same entries in the group's planes cut out."""
    from ipex_llm_tpu.ops.pallas import qmatmul as qmm

    stack, _ = _stack_of("sym_int4", 6, 256, 128)
    assert qmm.grouped_supported(stack)
    x = jnp.asarray(RNG.standard_normal(((3, 16, 256) if per_tile
                                         else (16, 256))) * 0.5, jnp.bfloat16)
    ids, first, group = jnp.array([1, 0, 1]), 4, 2
    whole = qmm.qmatmul_pallas_grouped(x, stack, ids, jnp.int32(2),
                                       first=jnp.int32(first))
    cut = jax.tree_util.tree_map(lambda a: a[first: first + group], stack)
    sliced = qmm.qmatmul_pallas_grouped(x, cut, ids, jnp.int32(2))
    np.testing.assert_array_equal(_bits(whole[:2]), _bits(sliced[:2]))
    want = qmatmul_pallas(x[1] if per_tile else x, jax.tree_util.tree_map(
        lambda a: a[first], stack), keep_f32=True)
    np.testing.assert_array_equal(_bits(whole[1]), _bits(want))


def _attn_case(b=2, t=32, s=96, hq=4, hkv=2, d=64):
    q = (RNG.standard_normal((b, t, hq, d)) * 0.3).astype(np.float32)
    k = (RNG.standard_normal((b, s, hkv, d)) * 0.3).astype(np.float32)
    v = (RNG.standard_normal((b, s, hkv, d)) * 0.3).astype(np.float32)
    return jnp.asarray(q), jnp.asarray(k), jnp.asarray(v)


def test_flash_causal_gqa_matches_reference():
    q, k, v = _attn_case()
    b, t = q.shape[:2]
    s = k.shape[1]
    # decode-style: prompt occupies slots [kv_start, kv_len); queries at the end
    kv_start = jnp.asarray([0, 8], jnp.int32)
    kv_len = jnp.full((b,), s - 16, jnp.int32)
    qpos = jnp.broadcast_to(jnp.arange(t)[None] + (s - 16 - t), (b, t))
    kwargs = dict(
        causal=True, q_positions=qpos, kv_len=kv_len, kv_start=kv_start
    )
    want = np.asarray(sdpa_reference(q, k, v, **kwargs))
    got = np.asarray(flash_sdpa(q, k, v, **kwargs))
    np.testing.assert_allclose(got, want, rtol=2e-2, atol=2e-2)


def test_flash_sliding_window_traced_flag():
    q, k, v = _attn_case(b=1, t=48, s=48, hq=2, hkv=2, d=32)
    qpos = jnp.broadcast_to(jnp.arange(48)[None], (1, 48))
    base = dict(causal=True, q_positions=qpos,
                kv_len=jnp.full((1,), 48, jnp.int32),
                kv_start=jnp.zeros((1,), jnp.int32), window=16)
    for flag in (True, False):
        won = jnp.asarray(flag)
        want = np.asarray(sdpa_reference(q, k, v, window_on=won, **base))
        got = np.asarray(flash_sdpa(q, k, v, window_on=won, **base))
        np.testing.assert_allclose(got, want, rtol=2e-2, atol=2e-2, err_msg=f"window_on={flag}")


def test_flash_softcap():
    q, k, v = _attn_case(b=1, t=16, s=16, hq=2, hkv=1, d=32)
    want = np.asarray(sdpa_reference(q, k, v, softcap=30.0))
    got = np.asarray(flash_sdpa(q, k, v, softcap=30.0))
    np.testing.assert_allclose(got, want, rtol=2e-2, atol=2e-2)


def test_decode_sdpa_matches_reference():
    """T=1 decode kernel vs the jnp oracle: GQA, left-pad kv_start, ragged
    per-row lengths.  Kernel reads the head-major [B,Hkv,S,D] cache layout."""
    b, s, hq, hkv, d = 3, 160, 8, 2, 64
    q = jnp.asarray((RNG.standard_normal((b, 1, hq, d)) * 0.3).astype(np.float32))
    k = jnp.asarray((RNG.standard_normal((b, s, hkv, d)) * 0.3).astype(np.float32))
    v = jnp.asarray((RNG.standard_normal((b, s, hkv, d)) * 0.3).astype(np.float32))
    kv_len = jnp.asarray([40, 100, 160], jnp.int32)
    kv_start = jnp.asarray([5, 0, 32], jnp.int32)
    qpos = (kv_len - 1)[:, None]
    want = np.asarray(sdpa_reference(
        q, k, v, causal=True, q_positions=qpos, kv_len=kv_len,
        kv_start=kv_start,
    ))
    got = np.asarray(decode_sdpa(
        q, k.transpose(0, 2, 1, 3), v.transpose(0, 2, 1, 3),
        kv_len=kv_len, kv_start=kv_start,
    ))
    np.testing.assert_allclose(got, want, rtol=2e-2, atol=2e-2)


def test_decode_sdpa_fp8_kv_in_kernel():
    """fp8(e5m2) KV tiles are widened inside the kernel — must match casting
    the cache at the XLA level (the sdp_fp8 contract)."""
    b, s, hq, hkv, d = 2, 128, 4, 4, 64
    q = jnp.asarray((RNG.standard_normal((b, 1, hq, d)) * 0.3).astype(np.float32))
    k8 = jnp.asarray(
        (RNG.standard_normal((b, s, hkv, d)) * 0.3).astype(np.float32)
    ).astype(jnp.float8_e5m2)
    v8 = jnp.asarray(
        (RNG.standard_normal((b, s, hkv, d)) * 0.3).astype(np.float32)
    ).astype(jnp.float8_e5m2)
    kv_len = jnp.asarray([64, 128], jnp.int32)
    kv_start = jnp.zeros((b,), jnp.int32)
    qpos = (kv_len - 1)[:, None]
    want = np.asarray(sdpa_reference(
        q, k8.astype(jnp.bfloat16), v8.astype(jnp.bfloat16),
        causal=True, q_positions=qpos, kv_len=kv_len, kv_start=kv_start,
    ))
    got = np.asarray(decode_sdpa(
        q, k8.transpose(0, 2, 1, 3), v8.transpose(0, 2, 1, 3),
        kv_len=kv_len, kv_start=kv_start,
    ))
    np.testing.assert_allclose(got, want, rtol=3e-2, atol=3e-2)


def test_decode_sdpa_window_and_softcap():
    b, s, hq, hkv, d = 1, 96, 2, 2, 32
    q = jnp.asarray((RNG.standard_normal((b, 1, hq, d)) * 0.3).astype(np.float32))
    k = jnp.asarray((RNG.standard_normal((b, s, hkv, d)) * 0.3).astype(np.float32))
    v = jnp.asarray((RNG.standard_normal((b, s, hkv, d)) * 0.3).astype(np.float32))
    kv_len = jnp.asarray([80], jnp.int32)
    kv_start = jnp.zeros((b,), jnp.int32)
    qpos = (kv_len - 1)[:, None]
    for flag in (True, False):
        won = jnp.asarray(flag)
        want = np.asarray(sdpa_reference(
            q, k, v, causal=True, q_positions=qpos, kv_len=kv_len,
            kv_start=kv_start, window=24, window_on=won, softcap=30.0,
        ))
        got = np.asarray(decode_sdpa(
            q, k.transpose(0, 2, 1, 3), v.transpose(0, 2, 1, 3),
            kv_len=kv_len, kv_start=kv_start, window=24,
            window_on=won, softcap=30.0,
        ))
        np.testing.assert_allclose(got, want, rtol=2e-2, atol=2e-2,
                                   err_msg=f"window_on={flag}")


def test_flash_bf16_long_prefill():
    q, k, v = _attn_case(b=1, t=256, s=256, hq=4, hkv=1, d=64)
    q, k, v = q.astype(jnp.bfloat16), k.astype(jnp.bfloat16), v.astype(jnp.bfloat16)
    want = np.asarray(sdpa_reference(q, k, v)).astype(np.float32)
    got = np.asarray(flash_sdpa(q, k, v)).astype(np.float32)
    np.testing.assert_allclose(got, want, rtol=5e-2, atol=5e-2)


def test_serving_engine_uses_paged_kernel(monkeypatch):
    """End-to-end: the engine's tick through the ragged paged-attention
    superkernel (interpret mode) matches plain generate — both the
    prefill-chunk (C>1) and decode (C=1) row shapes route through the ONE
    kernel family."""
    import numpy as np

    from ipex_llm_tpu.generation import GenerationConfig, generate
    from ipex_llm_tpu.ops import dispatch
    from ipex_llm_tpu.ops.pallas import ragged_paged_attention
    from ipex_llm_tpu.serving.engine import (
        EngineConfig,
        Request,
        ServingEngine,
        stream_tokens,
    )
    from tests.test_decoder import rand_params, tiny_cfg

    cfg = tiny_cfg(vocab_size=101, hidden_size=48, intermediate_size=96,
                   num_heads=4, num_kv_heads=2, head_dim=12,
                   max_position_embeddings=512)
    params = rand_params(cfg, qtype="bf16")
    prompt = list(np.random.default_rng(4).integers(0, 101, 11))
    # oracle BEFORE enabling pallas: the plain jnp reference path
    want = generate(cfg, params, [prompt],
                    GenerationConfig(max_new_tokens=6, do_sample=False))
    want_toks = list(want.sequences[0, len(prompt):len(prompt) + 6])

    calls = {"n": 0, "prefill": 0}
    real = ragged_paged_attention.ragged_paged_sdpa

    def counted(q, *a, **kw):
        calls["prefill" if q.shape[1] > 1 else "n"] += 1
        return real(q, *a, **kw)

    monkeypatch.setattr(ragged_paged_attention, "ragged_paged_sdpa",
                        counted)
    monkeypatch.setenv("IPEX_LLM_TPU_FORCE_PALLAS", "1")
    dispatch.clear_cache()
    try:
        eng = ServingEngine(cfg, params,
                            EngineConfig(max_rows=2, max_seq_len=128,
                                         page_size=32, prefill_bucket=32)
                            ).start()
        try:
            req = eng.submit(Request(prompt_ids=prompt, max_new_tokens=6))
            got = list(stream_tokens(req, timeout=300))
        finally:
            eng.stop()
        assert got == want_toks, (got, want_toks)
        # the kernels must actually have served both phases — a silent
        # fall-through to the gather path would pass the output check
        assert calls["n"] > 0
        assert calls["prefill"] > 0
    finally:
        monkeypatch.delenv("IPEX_LLM_TPU_FORCE_PALLAS", raising=False)
        dispatch.clear_cache()


# -- the ragged page walk (ops/pallas/ragged_paged_attention.py) ---------------
#
# The kernel walks each row's live pages and nothing else: the grid is rows x
# blocks of kv heads, the table's width no axis of it.  Held here: the values
# against the jnp oracle over the shapes the engine's callers bring, that a
# row's bits depend on that row alone (not on the table's width nor on its
# neighbours), and the grid itself.

_PS, _MAXP = 16, 8           # a row holds at most 128 slots
_KV_DTYPES = {"bf16": jnp.bfloat16, "fp8": jnp.float8_e5m2}


def _ragged_rows(c: int):
    """(kv_len, chunk_len) of the rows every case walks: one at the
    table's full capacity, one that ends exactly on a page boundary, one of
    a single token, one idle (at the engine's scratch sentinel)."""
    cap = _PS * _MAXP
    kv_len = np.asarray([cap, 2 * _PS, 1, cap], np.int32)
    chunk_len = np.asarray([min(c, cap), min(c, 2 * _PS), 1, 0], np.int32)
    return kv_len, chunk_len


def _ragged_fixture(hkv, c, kv, d=128, dv=None, layers=2, width=_MAXP,
                    seed=0, g=2):
    """Random stacked pools, a shuffled table of ``width`` pages a row (the
    first ``_MAXP`` of them allocated), and q for the rows of
    ``_ragged_rows``."""
    rng = np.random.default_rng(seed)
    dv = dv or d
    kv_len, chunk_len = _ragged_rows(c)
    r = len(kv_len)
    n_pages = 1 + r * _MAXP
    dtype = _KV_DTYPES[kv]
    k = jnp.asarray(rng.standard_normal((layers, n_pages, hkv, _PS, d)),
                    jnp.float32).astype(dtype)
    v = jnp.asarray(rng.standard_normal((layers, n_pages, hkv, _PS, dv)),
                    jnp.float32).astype(dtype)
    tables = np.full((r, width), -1, np.int32)
    tables[:, :_MAXP] = 1 + rng.permutation(r * _MAXP).reshape(r, _MAXP)
    q = jnp.asarray(rng.standard_normal((r, c, g * hkv, d)), jnp.bfloat16)
    return q, k, v, tables, kv_len, chunk_len


def _ragged_oracle(q, kl, vl, tables, kv_len, chunk_len):
    """sdpa_reference over the rows' gathered pages of ONE layer."""
    r, c = q.shape[:2]
    t = np.clip(tables, 0, kl.shape[0] - 1)

    def rows(pool):       # [P, H, ps, D] -> [R, S, H, D]
        g = pool[t].astype(jnp.bfloat16)          # [R, maxP, H, ps, D]
        return g.transpose(0, 1, 3, 2, 4).reshape(
            r, -1, pool.shape[1], pool.shape[3])
    qpos = (kv_len - chunk_len)[:, None] + np.arange(c)[None]
    return np.asarray(sdpa_reference(
        q, rows(kl), rows(vl), causal=True, q_positions=jnp.asarray(qpos),
        kv_len=jnp.asarray(kv_len)), np.float32)


def _assert_rows_match(got, want, chunk_len):
    """Valid queries against the oracle; pad queries (past a row's
    chunk_len) are garbage by contract, an idle row exact zeros."""
    got = np.asarray(got, np.float32)
    c = got.shape[1]
    valid = (np.arange(c)[None] < chunk_len[:, None])[:, :, None, None]
    np.testing.assert_allclose(np.where(valid, got, 0),
                               np.where(valid, want, 0),
                               rtol=3e-2, atol=3e-2)
    assert not got[chunk_len == 0].any()


@pytest.mark.parametrize("kv", sorted(_KV_DTYPES))
@pytest.mark.parametrize("c", [1, 8, 128])
@pytest.mark.parametrize("hkv", [1, 2, 8])
def test_ragged_walk_matches_reference(hkv, c, kv):
    """Both forms a caller holds: the stack read in place at ``layer``, and
    one layer alone (a stack of one)."""
    from ipex_llm_tpu.ops.pallas.ragged_paged_attention import (
        ragged_paged_sdpa)

    q, k, v, tables, kv_len, chunk_len = _ragged_fixture(hkv, c, kv)
    args = (jnp.asarray(tables), jnp.asarray(kv_len), jnp.asarray(chunk_len))
    want = _ragged_oracle(q, k[1], v[1], tables, kv_len, chunk_len)
    stacked = ragged_paged_sdpa(q, k, v, *args, layer=jnp.asarray(1))
    alone = ragged_paged_sdpa(q, k[1], v[1], *args)
    _assert_rows_match(stacked, want, chunk_len)
    np.testing.assert_array_equal(_bits(stacked), _bits(alone))


@pytest.mark.parametrize("kv", sorted(_KV_DTYPES))
@pytest.mark.parametrize("c", [1, 8])
def test_ragged_walk_lane_padded_heads(c, kv):
    """MLA's unbalanced pool (K at 192, V at 128): the one layer is sliced
    out and lane-padded, a stack of one."""
    from ipex_llm_tpu.ops.pallas.ragged_paged_attention import (
        ragged_paged_sdpa)

    q, k, v, tables, kv_len, chunk_len = _ragged_fixture(
        2, c, kv, d=192, dv=128)
    got = ragged_paged_sdpa(q, k, v, jnp.asarray(tables),
                            jnp.asarray(kv_len), jnp.asarray(chunk_len),
                            layer=jnp.asarray(1))
    assert got.shape == (4, c, 4, 128)
    _assert_rows_match(got, _ragged_oracle(q, k[1], v[1], tables, kv_len,
                                           chunk_len), chunk_len)


@pytest.mark.parametrize("kv", sorted(_KV_DTYPES))
@pytest.mark.parametrize("c", [1, 8])
def test_ragged_walk_row_bits_are_the_rows_own(c, kv):
    """The SAME rows through tables 8 and 32 pages wide, alone and among
    other rows: identical bits.  A row's output is a function of its own q,
    pages, kv_len and chunk_len."""
    from ipex_llm_tpu.ops.pallas.ragged_paged_attention import (
        ragged_paged_sdpa)

    q, k, v, narrow, kv_len, chunk_len = _ragged_fixture(2, c, kv)
    wide = np.full((len(narrow), 32), -1, np.int32)
    wide[:, :_MAXP] = narrow
    # the idle row sits at the sentinel of ITS table's width, as the engine
    # places it
    lens = {8: kv_len, 32: np.where(chunk_len > 0, kv_len, 32 * _PS)}

    def run(rows, tables):
        return _bits(ragged_paged_sdpa(
            q[rows], k, v, jnp.asarray(tables[rows]),
            jnp.asarray(lens[tables.shape[1]][rows]),
            jnp.asarray(chunk_len[rows]), layer=jnp.asarray(1)))

    every = np.arange(len(narrow))
    base = run(every, narrow)
    np.testing.assert_array_equal(run(every, wide), base)
    for row in every:
        for tables in (narrow, wide):
            np.testing.assert_array_equal(run(every[row:row + 1], tables),
                                          base[row:row + 1])
    # and in other company: reversed order, beside a copy of itself
    np.testing.assert_array_equal(run(every[::-1], wide), base[::-1])


def _pallas_grids(jaxpr) -> list[tuple]:
    """The grid of every pallas_call under a jaxpr, nested calls included."""
    grids = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            grids.append(tuple(eqn.params["grid_mapping"].grid))
        for p in eqn.params.values():
            for sub in (p if isinstance(p, (list, tuple)) else [p]):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    grids += _pallas_grids(inner)
    return grids


@pytest.mark.parametrize("c,hkv", [(1, 8), (128, 8), (1, 2)])
def test_ragged_walk_grid_has_no_table_width_axis(c, hkv):
    """No step for dead table width: the lowered pallas_call's grid is rows
    x head blocks, the same for tables 8 and 32 pages wide."""
    from ipex_llm_tpu.ops.pallas import ragged_paged_attention as rpa

    q, k, v, tables, kv_len, chunk_len = _ragged_fixture(hkv, c, "bf16")
    grids = []
    for width in (8, 32):
        wide = np.full((len(tables), width), -1, np.int32)
        wide[:, :_MAXP] = tables
        jaxpr = jax.make_jaxpr(
            lambda q, k, v, t: rpa.ragged_paged_sdpa(
                q, k, v, t, jnp.asarray(kv_len), jnp.asarray(chunk_len),
                layer=jnp.asarray(0)))(q, k, v, jnp.asarray(wide))
        grids.append(_pallas_grids(jaxpr.jaxpr))
    assert grids[0] == grids[1] and len(grids[0]) == 1
    rows, head_blocks = grids[0][0]
    assert rows == len(tables) and hkv % head_blocks == 0
    gc_pad = -(-2 * c // 8) * 8         # g = 2 query heads a kv head
    assert head_blocks == hkv // rpa._heads_per_step(
        hkv, gc_pad, 128, 128, _PS, 2, 2)


def test_ragged_walk_heads_a_step_follow_the_shapes():
    """Heads a step come from the call's shapes against VMEM: a decode
    tile takes a whole page of kv heads, a 128-wide prefill chunk fewer, a
    model of 2 kv heads both; always a divisor of the head count."""
    from ipex_llm_tpu.ops.pallas.ragged_paged_attention import (
        _heads_per_step)

    assert _heads_per_step(8, 8, 128, 128, 128, 2, 2) == 8      # decode
    assert _heads_per_step(8, 8, 128, 128, 128, 1, 2) == 8      # fp8 pool
    assert 1 <= _heads_per_step(8, 512, 128, 128, 128, 2, 2) < 8
    assert _heads_per_step(2, 16, 128, 128, 128, 2, 2) == 2     # 2 kv heads
    assert _heads_per_step(6, 2048, 128, 128, 128, 2, 2) == 1
    for hkv in (1, 2, 3, 6, 8, 32):
        assert hkv % _heads_per_step(hkv, 64, 256, 128, 128, 2, 2) == 0
