"""Fused block-dequant matmul kernel (the ``xe_linear.forward_new`` /
``xe_batch.batch_forward`` equivalent, reference low_bit_linear.py:545,699).

Design (TPU-first, see SURVEY.md §2.3 "TPU mapping"):

- Weights stay packed in HBM (4-bit: two codes per byte, block-local halves
  layout from quantize/core.py::_pack_nibbles; 8-bit: one code per byte).
  Each grid step DMAs one ``[BK(/2), BN]`` tile into VMEM, unpacks it with a
  reshape + concat (no sublane shuffle, thanks to the halves layout), applies
  the per-block scales, and feeds the MXU.  HBM traffic per weight is ~4.5
  bits instead of 16 — the decode-path win the reference gets from its SYCL
  kernels.
- Accumulation runs in fp32 in the revisited output block across the K grid
  dimension (innermost), the standard Pallas matmul pattern.
- The contraction (K) axis is the quantization-block axis, so a K tile always
  covers whole quantization blocks and scales slice as ``[BK/bs, BN]``.

- A weight that lives in a stack ``[L, ...]`` (a layer scan's) reaches the
  kernel as the stack and the layer's index (``quantize/core.py`` ``QLayer``):
  the index maps take the layer from scalar prefetch, so the tiles are read
  out of the stack and no per-layer copy of the codes is made on the way.  A
  lone weight is the same kernel over a stack of one.
- Mosaic loads no fp16 vector on this chip, so a ``QTensor`` holds its fp16
  scales as their uint16 bit patterns (``quantize/core.py``).  The EXPERT
  kernel (``qmatmul_expert``) takes a tile of the patterns out of the whole
  ``[L x E, ...]`` stack through the codes' own index map and converts it
  inside (``_f16_bits_to_f32``): nothing stands between the tick program's
  parameter and that call.  The DENSE kernel (``qmatmul``) streams the one
  layer's plane as float32, made by one XLA fusion a call, because
  converting inside it is VPU-bound and a whole scale stack under 128 MiB
  gets copied to fast memory each layer of a mixed tick (PERF.md section 6,
  PR 47).

Supported formats: sym_int4 / asym_int4 / sym_int8, the 4-bit codebook
formats nf4 / fp4 (16-entry lookup unrolled as a select chain on the VPU),
the minifloats fp8_e4m3 / fp8_e5m2 / fp6 (exponent/mantissa decoded
arithmetically in-kernel — ``exp2`` on the VPU, no 256-entry table), and
sym/asym_int5 (dual-plane unpack of the _pack_5bit layout: nibble plane +
bit plane).  ops/linear.py routes every other format to the XLA reference
path before the call.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ipex_llm_tpu.ops.pallas._compat import (
    interpret as _interpret,
    round_up as _round_up,
)

from ipex_llm_tpu.quantize import numerics
from ipex_llm_tpu.quantize.core import QLayer, QTensor

_NIB4 = ("sym_int4", "asym_int4", "nf4", "fp4")
_BIT5 = ("sym_int5", "asym_int5")
_MINIFLOAT = {  # qtype -> (exp_bits, man_bits, bias)
    "fp8_e4m3": (4, 3, 7),
    "fp8_e5m2": (5, 2, 15),
    "fp6": (3, 2, 3),
}
_SUPPORTED = _NIB4 + _BIT5 + ("sym_int8",) + tuple(_MINIFLOAT)


def _data_row_factor(qtype: str) -> tuple[int, int]:
    """(num, den): logical K rows = data rows * num / den."""
    if qtype in _NIB4:
        return 2, 1
    if qtype in _BIT5:
        return 8, 5
    return 1, 1


def _codebook_select(codes: jnp.ndarray, table: np.ndarray) -> jnp.ndarray:
    """16-entry codebook lookup as an unrolled select chain (VPU-friendly)."""
    out = jnp.full(codes.shape, float(table[0]), jnp.float32)
    for i in range(1, len(table)):
        out = jnp.where(codes == i, float(table[i]), out)
    return out


def _minifloat_decode(c: jnp.ndarray, exp_bits: int, man_bits: int,
                      bias: int) -> jnp.ndarray:
    """Arithmetic 1+e+m minifloat decode (matches numerics._minifloat_table):
    sign × (1 + m/2^mb) × 2^(e-bias), subnormals m/2^mb × 2^(1-bias)."""
    man_div = float(1 << man_bits)
    sign = 1.0 - 2.0 * ((c >> (exp_bits + man_bits)) & 1).astype(jnp.float32)
    e = ((c >> man_bits) & ((1 << exp_bits) - 1)).astype(jnp.float32)
    man = (c & ((1 << man_bits) - 1)).astype(jnp.float32)
    mag = jnp.where(
        e > 0,
        (1.0 + man / man_div) * jnp.exp2(e - bias),
        man / man_div * (2.0 ** (1 - bias)),
    )
    return sign * mag


def _dequant_tile(codes, scales, zeros, qtype: str, bs: int, bk: int, bn: int,
                  high=None):
    """codes [BK(/2), BN] (+ ``high`` [BK/8, BN] for 5-bit) -> w [BK, BN]
    f32 inside the kernel."""
    nb = bk // bs
    # Mosaic can't lower uint8 bit-ops/casts directly; widen to int32 first
    if qtype in _NIB4 or qtype in _BIT5:
        p = codes.reshape(nb, bs // 2, bn).astype(jnp.int32)
        c = jnp.concatenate([p & 0x0F, p >> 4], axis=1)  # [nb, bs, bn]
        if qtype in _BIT5:  # OR in the fifth-bit plane (core.py::_pack_5bit)
            hb = high.astype(jnp.int32)  # [bk//8, bn]
            hi = jnp.stack([(hb >> j) & 1 for j in range(8)], axis=1)
            c = c | (hi.reshape(nb, bs, bn) << 4)
    else:  # byte-per-code: sym_int8 / fp8 / fp6
        c = codes.reshape(nb, bs, bn).astype(jnp.int32)
    s = scales.reshape(nb, 1, bn)
    if qtype == "sym_int4":
        w = (c.astype(jnp.float32) - 8.0) * s
    elif qtype == "sym_int5":
        w = (c.astype(jnp.float32) - 16.0) * s
    elif qtype == "sym_int8":
        w = (c.astype(jnp.float32) - 128.0) * s
    elif qtype in ("asym_int4", "asym_int5"):
        w = c.astype(jnp.float32) * s + zeros.reshape(nb, 1, bn)
    elif qtype == "nf4":
        w = _codebook_select(c, numerics.NF4_TABLE) * s
    elif qtype in _MINIFLOAT:
        w = _minifloat_decode(c, *_MINIFLOAT[qtype]) * s
    else:  # fp4
        w = _codebook_select(c, numerics.FP4_TABLE) * s
    return w.reshape(bk, bn)


def tile_plan(k: int, n: int, bs: int) -> tuple[int, int, int, int]:
    """``(bk, kp, bn, np_)``: the K and N tiles of a ``[k, n]`` weight and
    the sizes its planes are padded to so that the tiles cover them exactly.

    N is padded to the lane width only and tiled by the widest of 512, 384,
    256, 128 that divides it.  K up to 2048 is one tile; past that the tile
    is the largest multiple of 256 (a whole number of scale sublanes) between
    1024 and 2048 that divides K, K being padded by as little as gives one.
    Widths that are multiples of 2048 and 512 keep the tiles they had.  The
    planes are padded ONCE, when the tree is built (``quantize/core.py``
    ``pad_planes``).  A lone weight that arrives unpadded is padded in the
    call; a layer of a STACK that is not at these sizes is first sliced out
    of it (``stacked_supported``), and is then such a lone weight."""
    n128 = _round_up(n, 128)
    bn = n128 if n128 <= 512 else next(
        c for c in (512, 384, 256, 128) if n128 % c == 0)
    if k <= 2048:
        return k, k, bn, n128
    kp = _round_up(k, 256)
    while True:
        for bk in range(2048, 1023, -256):
            if kp % bk == 0:
                return bk, kp, bn, n128
        kp += 256


def _f16_bits_to_f32(h):
    """fp16 bit patterns (int32 in [0, 65536)) -> float32, by integer
    arithmetic, exact for every pattern (subnormals included; the VPU
    flushes float32 subnormals, so a renormalising multiply would not be):
    Mosaic loads no f16 vector on this chip, so the expert kernel reads the
    fp16 scale planes where they lie as uint16."""
    sign = jnp.where((h & 0x8000) != 0, -1.0, 1.0)
    mag = h & 0x7FFF
    normal = jax.lax.bitcast_convert_type((mag << 13) + (112 << 23),
                                          jnp.float32)
    subnormal = (mag & 0x3FF).astype(jnp.float32) * (2.0 ** -24)
    return sign * jnp.where(mag >= 0x400, normal, subnormal)


def _make_kernel(qtype, bs, bk, bn, compute_dtype, has_high, has_zeros):
    def kern(*refs):
        # refs[0] is the prefetched layer index: the index maps' alone
        x_ref, d_ref = refs[1], refs[2]
        i = 3
        h_ref = None
        if has_high:
            h_ref, i = refs[i], i + 1
        s_ref, i = refs[i], i + 1
        z_ref = refs[i] if has_zeros else None
        o_ref = refs[-1]

        ki = pl.program_id(2)

        @pl.when(ki == 0)
        def _():
            o_ref[:] = jnp.zeros_like(o_ref)

        w = _dequant_tile(
            d_ref[:], s_ref[:], None if z_ref is None else z_ref[:],
            qtype, bs, bk, bn,
            high=None if h_ref is None else h_ref[:],
        ).astype(compute_dtype)
        o_ref[:] += jnp.dot(
            x_ref[:].astype(compute_dtype), w,
            preferred_element_type=jnp.float32,
        )

    return kern


@functools.partial(
    jax.jit, static_argnames=("qtype", "bs", "logical_out", "compute_dtype")
)
def _qmatmul_2d(x, data, scales, zeros, layer=None, *, qtype: str, bs: int,
                logical_out: int, compute_dtype):
    """x [M, K_pad] @ dequant(data) [K_pad, N_pad] -> [M, logical_out].

    ``data`` is the weight's code plane ``[K_pad(/2), N]``, or with
    ``layer`` (a traced int32 scalar) the STACK ``[L, K_pad(/2), N]`` the
    plane lies in: the kernel's index maps take the layer from scalar
    prefetch and its DMA reads that layer's tiles out of the stack, so no
    per-layer copy of the codes exists between a layer scan and this call.
    A stack tiles exactly (``stacked_supported``); a lone plane that does
    not is padded here, and is then the same kernel over a stack of one.
    ``scales``/``zeros`` are the one layer's planes either way, as numbers
    (an eighth of the codes' bytes, streamed as float32)."""
    m, k = x.shape
    n = data.shape[-1]
    bit5 = qtype in _BIT5
    num, den = _data_row_factor(qtype)

    # M tile: up to 256 rows are ONE tile (the grid is M-outermost, so every
    # further M tile fetches and dequantizes the whole weight again: a mixed
    # tick's chunk and its decode rows, 128 + 16, ride one); past that, 128
    bm = _round_up(m, 16) if m <= 256 else 128
    # K tile: whole quantization blocks, target ~2048 contraction rows
    bk, kp, bn, np_ = tile_plan(k, n, bs)

    # pad every dim so grid blocks tile exactly (zero scale rows/cols are
    # numerically inert: dequant yields w=0 there for all supported formats
    # except asym_int4/5, whose zero-point plane is also zero-padded).
    # Planes padded at build (core.pad_planes) arrive at (kp, np_) already.
    mp = _round_up(m, bm)
    if mp != m or kp != k:
        x = jnp.pad(x, ((0, mp - m), (0, kp - k)))
    high = None
    if layer is not None:
        if bit5 or (kp, np_) != (k, n) or data.shape[1] * num // den != k:
            raise ValueError(
                f"stacked {qtype} planes {data.shape[1:]} are not at the "
                f"kernel's tiles ({kp}, {np_}): take the layer's plane")
    elif bit5:
        # split the _pack_5bit planes: [K/2, N] nibbles ++ [K/8, N] top bits
        high = data[k // 2:]
        data = data[: k // 2]
        if kp != k or np_ != n:
            data = jnp.pad(data, ((0, (kp - k) // 2), (0, np_ - n)))
            high = jnp.pad(high, ((0, (kp - k) // 8), (0, np_ - n)))
        high = high[None]
    else:
        drows = kp * den // num
        if data.shape[0] != drows or np_ != n:
            data = jnp.pad(data, ((0, drows - data.shape[0]), (0, np_ - n)))
    if layer is None:
        data, layer = data[None], 0
    nb_p = kp // bs
    scales = jnp.pad(
        scales, ((0, nb_p - scales.shape[0]), (0, np_ - n))
    ).astype(jnp.float32)
    if zeros is not None:
        zeros = jnp.pad(
            zeros, ((0, nb_p - zeros.shape[0]), (0, np_ - n))
        ).astype(jnp.float32)

    d_rows = bk // 2 if (qtype in _NIB4 or bit5) else bk
    stack_blk = lambda mi, ni, ki, l: (l[0], ki, ni)  # noqa: E731
    blk = lambda mi, ni, ki, l: (ki, ni)  # noqa: E731
    in_specs = [
        pl.BlockSpec((bm, bk), lambda mi, ni, ki, l: (mi, ki)),
        pl.BlockSpec((None, d_rows, bn), stack_blk),
    ]
    args = [x, data]
    if bit5:
        in_specs.append(pl.BlockSpec((None, bk // 8, bn), stack_blk))
        args.append(high)
    in_specs.append(pl.BlockSpec((bk // bs, bn), blk))
    args.append(scales)
    if zeros is not None:
        in_specs.append(pl.BlockSpec((bk // bs, bn), blk))
        args.append(zeros)

    kern = _make_kernel(qtype, bs, bk, bn, compute_dtype,
                        has_high=bit5, has_zeros=zeros is not None)
    out = pl.pallas_call(
        kern,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(mp // bm, np_ // bn, kp // bk),
            in_specs=in_specs,
            out_specs=pl.BlockSpec((bm, bn), lambda mi, ni, ki, l: (mi, ni)),
        ),
        out_shape=jax.ShapeDtypeStruct((mp, np_), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        cost_estimate=pl.CostEstimate(
            flops=2 * mp * np_ * kp,
            bytes_accessed=(
                mp * kp * 2 + (kp * np_ * den // num) + mp * np_ * 4
            ),
            transcendentals=0,
        ),
        interpret=_interpret(),
        name="qmatmul",
    )(jnp.reshape(layer, (1,)).astype(jnp.int32), *args)
    return out[:m, :logical_out]


def _grouped_kernel(ids_ref, first_ref, x_ref, d_ref, s_ref, o_ref, *, qtype,
                    bs, bk, bn, compute_dtype):
    del ids_ref, first_ref                    # the index maps' alone
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _():
        o_ref[:] = jnp.zeros_like(o_ref)

    scales = _f16_bits_to_f32(s_ref[:].astype(jnp.int32))
    w = _dequant_tile(d_ref[:], scales, None, qtype, bs, bk,
                      bn).astype(compute_dtype)
    o_ref[:] += jnp.dot(x_ref[:].astype(compute_dtype), w,
                        preferred_element_type=jnp.float32)


@functools.partial(
    jax.jit, static_argnames=("qtype", "bs", "logical_out", "compute_dtype")
)
def _qmatmul_grouped(x, data, scale_bits, ids, n_live, first, *, qtype: str,
                     bs: int, logical_out: int, compute_dtype):
    """One call over ROW TILES that each name an entry of a stack of planes
    (an expert layer's hit experts): ``data`` [E, K/2, N] and ``scale_bits``
    [E, K/bs, N] (the fp16 scales as the uint16 bit patterns a ``QTensor``
    holds; Mosaic loads no fp16 vector on this chip) stay whole in HBM and
    tile ``t`` of the grid's first axis multiplies its rows by entry
    ``first + ids[t]`` of them, both read in place through one index map
    and the scales converted in the kernel.
    ``x`` is [bm, K] (every tile reads it) or [T, bm, K] (tile t its own).

    The grid is (``n_live``, N tiles, K tiles) and its first bound is the
    TRACED count of live tiles: the tiles after them are not in the grid, so
    nothing is fetched or computed for them and their rows of the result
    are NOT WRITTEN (a caller reads live tiles' rows only).  Every tile
    streams and dequantizes its entry's planes once, so a caller sizes its
    tiles so that an entry seldom needs a second (``ops/moe.py``
    ``row_tile``).  A row's bits depend on its own K sweep alone: not on
    ``bm``, nor on the rows that share its tile (measured on the chip at 16
    to 512 rows a tile: PERF.md, PR 41).  Returns [T, bm, logical_out]
    float32.  The planes tile exactly (padded at build)."""
    per_tile = x.ndim == 3
    bm, k = x.shape[-2:]
    tiles, n = ids.shape[0], data.shape[2]
    bk, kp, bn, np_ = tile_plan(k, n, bs)
    if ((kp, np_) != (k, n) or data.shape[1] * 2 != k
            or scale_bits.shape != (data.shape[0], k // bs, n)):
        raise ValueError(
            f"stacked planes [{data.shape[1] * 2}, {n}] are not padded to "
            f"the kernel's tiles ({kp}, {np_}): pad them at build "
            "(quantize.core.pad_planes)")
    if bm % 16:
        raise ValueError(f"a row tile of {bm} rows is not whole bf16 "
                         "sublane tiles (16)")

    if per_tile:
        x_spec = pl.BlockSpec((None, bm, bk),
                              lambda t, ni, ki, ids, first: (t, 0, ki))
    else:
        x_spec = pl.BlockSpec((bm, bk),
                              lambda t, ni, ki, ids, first: (0, ki))
    entry = lambda t, ni, ki, ids, first: (  # noqa: E731
        first[0] + ids[t], ki, ni)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(jnp.reshape(n_live, ()).astype(jnp.int32), np_ // bn, kp // bk),
        in_specs=[x_spec,
                  pl.BlockSpec((None, bk // 2, bn), entry),
                  pl.BlockSpec((None, bk // bs, bn), entry)],
        out_specs=pl.BlockSpec(
            (None, bm, bn), lambda t, ni, ki, ids, first: (t, 0, ni)),
    )
    out = pl.pallas_call(
        functools.partial(_grouped_kernel, qtype=qtype, bs=bs, bk=bk, bn=bn,
                          compute_dtype=compute_dtype),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((tiles, bm, np_), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "parallel", "arbitrary"),
        ),
        interpret=_interpret(),
        name="qmatmul_expert",
    )(ids.astype(jnp.int32), jnp.reshape(first, (1,)).astype(jnp.int32), x,
      data, scale_bits)
    return out[..., :logical_out]


def grouped_supported(qt: QTensor) -> bool:
    """Whether :func:`qmatmul_pallas_grouped` covers this stack: sym_int4
    planes [E, K/2, N] with fp16 scales (held as their bits), padded to the
    kernel's tiles."""
    if (qt.qtype != "sym_int4" or qt.data.ndim != 3
            or qt.scale_bits.dtype != jnp.uint16):
        return False
    k, n = qt.data.shape[1] * 2, qt.data.shape[2]
    _, kp, _, np_ = tile_plan(k, n, qt.block_size)
    return (kp, np_) == (k, n)


def qmatmul_pallas_grouped(x: jnp.ndarray, qt: QTensor, ids, n_live,
                           first=0, compute_dtype=jnp.bfloat16):
    """x @ dequant(qt[first + ids[t]]) for the first ``n_live`` row tiles t
    of ``ids`` (traced int32 [T], entries counted from ``first`` of the
    stacked weight ``qt``): x [M, in] for every tile, or [T, M, in] a
    tile's own.  ``qt`` is read in place, codes and scales (an expert
    layer's ``[E, ...]`` planes, or with ``first`` a model's ``[L x E,
    ...]``).  Returns [T, M, out] float32 whose tiles past ``n_live`` are
    not written."""
    m, k_pad = x.shape[-2], qt.data.shape[1] * 2
    if x.shape[-1] != k_pad or m % 16:
        x = jnp.pad(x, [(0, 0)] * (x.ndim - 2) + [
            (0, _round_up(m, 16) - m), (0, k_pad - x.shape[-1])])
    return _qmatmul_grouped(
        x, qt.data, qt.scale_bits, ids, n_live, first, qtype=qt.qtype,
        bs=qt.block_size, logical_out=qt.out_features,
        compute_dtype=compute_dtype)[:, :m]


def stacked_supported(stack: QTensor) -> bool:
    """Whether :func:`qmatmul_pallas` reads a layer of this stack ``[L,
    ...]`` in place: one code plane a layer (the 5-bit formats cut two out
    of one array) that the kernel's tiles cover exactly, as the planes of
    the sym 4-bit formats do once ``pad_planes`` has padded them at build."""
    if (stack.qtype not in _SUPPORTED or stack.qtype in _BIT5
            or stack.data.ndim != 3):
        return False
    num, den = _data_row_factor(stack.qtype)
    k, n = stack.data.shape[1] * num // den, stack.data.shape[2]
    _, kp, _, np_ = tile_plan(k, n, stack.block_size)
    return (kp, np_) == (k, n) and stack.scale_bits.shape[1:] == (
        k // stack.block_size, n)


def qmatmul_pallas(x: jnp.ndarray, qt: QTensor | QLayer,
                   compute_dtype=jnp.bfloat16, keep_f32: bool = False):
    """x [..., in] @ dequant(qt) -> [..., out] via the fused Pallas kernel.
    A ``QLayer`` of a stack that tiles exactly is read where it lies
    (``stacked_supported``); of any other stack the layer is sliced out
    first, as a lone ``QTensor`` arrives.

    ``keep_f32`` returns the fp32 accumulator untouched (the row-parallel
    shard_map wrapper psums partial products in fp32 before the final cast).
    """
    if not supported(qt):
        raise NotImplementedError(qt.qtype)
    layer = None
    if isinstance(qt, QLayer):
        if stacked_supported(qt.stack):
            # the codes stay in their stack; the small planes are sliced out
            layer, data = qt.layer, qt.stack.data
            qt = QLayer(dataclasses.replace(qt.stack, data=None), layer)
        qt = qt.take()
    if layer is None:
        data = qt.data
    lead = x.shape[:-1]
    k = x.shape[-1]
    num, den = _data_row_factor(qt.qtype)
    k_pad = data.shape[-2] * num // den
    x2 = x.reshape(-1, k)
    if k_pad != k:  # quantization block padding (core.py::_to_blocks)
        x2 = jnp.pad(x2, ((0, 0), (0, k_pad - k)))
    out = _qmatmul_2d(
        x2, data, qt.scales, qt.zeros, layer,
        qtype=qt.qtype, bs=qt.block_size, logical_out=qt.out_features,
        compute_dtype=compute_dtype,
    )
    out = out.reshape(*lead, qt.out_features)
    return out if keep_f32 else out.astype(x.dtype)


def supported(qt: QTensor) -> bool:
    """Whether :func:`qmatmul_pallas` covers this weight's format."""
    return qt.qtype in _SUPPORTED


def sharded_supported(qt: QTensor, tp: int) -> bool:
    """Whether :func:`qmatmul_pallas_sharded` covers this weight at this
    tp degree; dispatch asks BEFORE routing to it."""
    if not supported(qt):
        return False
    if qt.tp_mode == "col":
        return qt.out_features % tp == 0
    if qt.tp_mode == "row":
        return (qt.qtype not in _BIT5
                and qt.in_features % ((qt.block_size or 1) * tp) == 0)
    return False


def qmatmul_pallas_sharded(x: jnp.ndarray, qt: QTensor, mesh,
                           compute_dtype=jnp.bfloat16):
    """Tensor-parallel fused dequant-matmul: the kernel runs per-shard under
    ``jax.shard_map`` with only the ``tp`` axis manual, so dp/pp/cp stay
    under GSPMD management (partial-auto mode).

    - ``tp_mode='col'`` (qkv/gate_up): weight planes sharded on the out
      axis, x replicated over tp, output tp-sharded on its last axis — no
      collective.
    - ``tp_mode='row'`` (o/down): weight planes sharded on the in axis, x
      tp-sharded on its last axis, fp32 partials combined with ``psum``
      over ICI (the AutoTP ``inference_all_reduce`` equivalent, reference
      low_bit_linear.py:715-722) — but here fused right after the kernel.
    """
    from jax.sharding import PartitionSpec as P

    tp = mesh.shape["tp"]
    if not sharded_supported(qt, tp):
        raise NotImplementedError(
            f"{qt!r} tp_mode={qt.tp_mode} does not shard over tp={tp}")
    lead = (None,) * (x.ndim - 1)
    has_zeros = qt.zeros is not None

    if qt.tp_mode == "col":
        local_shape = (qt.in_features, qt.out_features // tp)
        w_spec = P(None, "tp")
        x_spec = P(*lead, None)
        out_spec = P(*lead, "tp")
    else:
        local_shape = (qt.in_features // tp, qt.out_features)
        w_spec = P("tp", None)
        x_spec = P(*lead, "tp")
        out_spec = P(*lead, None)

    def run(xl, data, scale_bits, zeros=None):
        lqt = QTensor(data, scale_bits, zeros, qt.qtype, local_shape,
                      qt.block_size)
        if qt.tp_mode == "col":
            return qmatmul_pallas(xl, lqt, compute_dtype)
        part = qmatmul_pallas(xl, lqt, compute_dtype, keep_f32=True)
        return jax.lax.psum(part, "tp").astype(xl.dtype)

    in_specs = [x_spec, w_spec, w_spec] + ([w_spec] if has_zeros else [])
    args = [x, qt.data, qt.scale_bits] + ([qt.zeros] if has_zeros else [])
    return jax.shard_map(
        run, mesh=mesh, axis_names={"tp"},
        in_specs=tuple(in_specs), out_specs=out_spec, check_vma=False,
    )(*args)
