"""Ragged paged attention: ONE kernel for every row shape in an engine tick.

The "Ragged Paged Attention" design (PAPERS.md, arxiv 2604.15464) applied
to this repo's paged pool: a single Pallas program walks a ragged batch of
rows where each row is either a **decode row** (1 query token at slot
``kv_len - 1``), a **prefill-chunk row** (up to C query tokens right-padded
to the chunk width, causally masked against its own history), or an **idle
row** (``chunk_len == 0`` — fully masked, zero output).  It subsumes both
``paged_attention.paged_decode_sdpa`` and ``paged_prefill_sdpa``, so the
serving engine's fused tick program (``serving/engine.py::_ragged_tick``)
carries exactly one attention kernel family regardless of the admission mix.

What it fixes over the split kernels (the BENCH_r05 per-op losses):

- **per-row raggedness is traced, not static**: ``chunk_len`` [R] rides the
  scalar prefetch, so one compiled program serves every (decode, prefill,
  idle) row mix — the causal mask is keyed off ``(kv_len, chunk_len)`` per
  row instead of a uniform static chunk;
- **fewer pool round-trips per page**: the K/V BlockSpec index maps clamp
  the page-grid index to the row's LAST VALID page, so the tail of the
  static ``maxP`` grid re-maps to an already-resident block and Pallas
  elides the DMA entirely (the old kernel streamed every dead tail page
  from HBM just to skip its compute);
- **query-row tiling**: the whole GQA group x chunk tile ``[G*C, D]`` feeds
  ONE MXU dot per page tile, amortizing each K/V page fetch across every
  query row that needs it (the split decode kernel issued [G, D] slivers);
- **no full-width accumulator re-materialization**: the online-softmax
  state (m/l/acc) lives in VMEM scratch across the page walk and the
  output tile is written exactly once, at the last page step — versus the
  XLA fallback materializing fp32 ``[R, H, C, maxP*ps]`` score/prob
  tensors over the row's full table width per layer.

The pool is addressed by ``(layer, page)``: the kernel takes the WHOLE
stacked pools ``[L, P, Hkv, ps, D]`` and the layer index as a fourth
scalar-prefetch operand, and the K/V index map returns ``(layer,
table[row, page step], head, 0, 0)`` — so the decoder's layer scan never
slices a layer out of the stack to feed it (kv.py ``PagedKVCache``).  A
caller holding one layer ``[P, Hkv, ps, D]`` passes it as a stack of one.
Head sizes that need lane padding (``D % 128``, MLA's 192) pad a copy of
the pool: for those the one layer is sliced out first, a choice by shape,
so the pad never touches the stack.

K/V tiles stream in the pool's STORAGE dtype and widen to the compute
dtype in-kernel: an fp8(e5m2) pool (``EngineConfig.kv_storage="fp8"``)
costs half the HBM bytes end to end — the paged, ragged form of the
reference's ``xe_addons.sdp_fp8`` contract (PR 5).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ipex_llm_tpu.ops.pallas._compat import (
    NEG_INF,
    heads_shardable,
    interpret as _interpret,
    round_up as _round_up,
)


def _kernel(tables_ref, len_ref, chunk_ref, layer_ref, q_ref, k_ref, v_ref,
            o_ref, m_ref, l_ref, acc_ref, *, scale, ps, c, compute_dtype):
    """One (row, kv-head, page) grid step of the ragged walk.

    q rows are the ``[G, C]`` group x chunk tile flattened chunk-minor:
    flat row j is the query at absolute slot
    ``kv_len - chunk_len + (j % c)``, so a decode row (``chunk_len == 1``,
    ``c`` may still be > 1 when batched with prefill rows) reduces to the
    classic single query at ``kv_len - 1``, and a prefill row's valid
    queries are causal against their own history.  Pad query rows
    (``j % c >= chunk_len``) land past ``kv_len`` and read only valid
    slots — bounded garbage the caller discards.  ``chunk_len == 0`` rows
    never enter the live branch and emit exact zeros.
    """
    r = pl.program_id(0)
    pi = pl.program_id(2)

    @pl.when(pi == 0)
    def _():
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    kv_len = len_ref[r]
    chunk = chunk_ref[r]
    lo = pi * ps
    tile_live = (lo < kv_len) & (chunk > 0)

    @pl.when(tile_live)
    def _():
        q = q_ref[0, 0].astype(jnp.float32)              # [G*C, D]
        # storage-dtype tile (possibly e5m2) widens HERE, inside the
        # kernel, so fp8 pools stream half the HBM bytes
        k = k_ref[0, 0].astype(compute_dtype).astype(jnp.float32)  # [ps, D]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale                                        # [G*C, ps]
        g = s.shape[0]
        kpos = lo + jax.lax.broadcasted_iota(jnp.int32, (g, ps), 1)
        qpos = (kv_len - chunk
                + jax.lax.broadcasted_iota(jnp.int32, (g, ps), 0) % c)
        # per-row causal mask keyed off (kv_len, chunk_len): a pad query
        # (qpos >= kv_len) still needs the kv_len bound — unlike the
        # uniform-chunk kernel, its own position no longer subsumes it
        s = jnp.where((kpos <= qpos) & (kpos < kv_len), s, NEG_INF)

        m_prev = m_ref[:]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        m_safe = jnp.maximum(m_new, -1e29)
        p = jnp.exp(s - m_safe)
        alpha = jnp.exp(jnp.maximum(m_prev, -1e29) - m_safe)
        v = v_ref[0, 0].astype(compute_dtype)            # [ps, Dv]
        l_ref[:] = l_ref[:] * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[:] = acc_ref[:] * alpha + jax.lax.dot_general(
            p.astype(compute_dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        m_ref[:] = m_new

    @pl.when(pi == pl.num_programs(2) - 1)
    def _():
        denom = jnp.where(l_ref[:] == 0.0, 1.0, l_ref[:])
        o_ref[0, 0] = (acc_ref[:] / denom).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("scale", "out_dtype", "c"))
def _ragged(q, k_pool, v_pool, tables, kv_len, chunk_len, layer, *, scale,
            out_dtype, c=1):
    """q [R, Hkv, G*C, D]; k/v_pool [L, P, Hkv, ps, D(v)] the stacked
    pools, read at ``layer`` (int32 scalar); tables [R, maxP]; kv_len [R]
    valid slots incl. this chunk; chunk_len [R] valid queries (0 = idle
    row); ``c`` the static padded chunk width the G axis was flattened
    with."""
    r, hkv, gc, d = q.shape
    _, n_pages, _, ps, dv = v_pool.shape

    gc_pad = _round_up(gc, 8)
    d_pad = _round_up(d, 128)
    dv_pad = _round_up(dv, 128)
    if (gc_pad, d_pad) != (gc, d):
        q = jnp.pad(q, ((0, 0), (0, 0), (0, gc_pad - gc), (0, d_pad - d)))
    if (d_pad, dv_pad) != (d, dv) and k_pool.shape[0] > 1:
        # lane padding makes a copy of what it pads: slice the one layer
        # out first so that copy is a layer's, never the stack's
        k_pool = jax.lax.dynamic_index_in_dim(k_pool, layer, 0)
        v_pool = jax.lax.dynamic_index_in_dim(v_pool, layer, 0)
        layer = jnp.zeros((), jnp.int32)
    if d_pad != d:
        k_pool = jnp.pad(k_pool, ((0, 0),) * 4 + ((0, d_pad - d),))
    if dv_pad != dv:
        v_pool = jnp.pad(v_pool, ((0, 0),) * 4 + ((0, dv_pad - dv),))
    # unallocated (-1) table slots clip to the engine scratch page 0; the
    # index-map clamp below keeps them from ever being streamed for rows
    # whose kv_len ends earlier
    tables = jnp.clip(tables, 0, n_pages - 1).astype(jnp.int32)
    maxp = tables.shape[1]

    def kv_map(ri, hi, pi, tables_ref, len_ref, chunk_ref, layer_ref):
        # clamp the page walk to the row's last valid page: every tail
        # grid step re-maps to the block already resident from the
        # previous step, so Pallas skips its DMA — dead table width costs
        # no pool round-trips (the page axis is the innermost grid dim).
        # Idle slots (chunk_len 0 — batch pads, ensure-failed rows) clamp
        # to page 0 outright: their kv_len is the scratch-routing
        # sentinel (past the table width), which would otherwise walk
        # the whole grid of someone else's table for a row that computes
        # nothing.
        last = jnp.where(chunk_ref[ri] > 0,
                         jnp.maximum((len_ref[ri] - 1) // ps, 0), 0)
        return (layer_ref[0], tables_ref[ri, jnp.minimum(pi, last)], hi,
                0, 0)

    def q_map(ri, hi, pi, tables_ref, len_ref, chunk_ref, layer_ref):
        return (ri, hi, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(r, hkv, maxp),
        in_specs=[
            pl.BlockSpec((1, 1, gc_pad, d_pad), q_map),
            # the layer axis is squeezed: the kernel sees [1, 1, ps, D]
            # tiles exactly as it did over a per-layer pool
            pl.BlockSpec((None, 1, 1, ps, d_pad), kv_map),
            pl.BlockSpec((None, 1, 1, ps, dv_pad), kv_map),
        ],
        out_specs=pl.BlockSpec((1, 1, gc_pad, dv_pad), q_map),
        scratch_shapes=[
            pltpu.VMEM((gc_pad, 1), jnp.float32),
            pltpu.VMEM((gc_pad, 1), jnp.float32),
            pltpu.VMEM((gc_pad, dv_pad), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(_kernel, scale=scale, ps=ps, c=c,
                          compute_dtype=jnp.bfloat16),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((r, hkv, gc_pad, dv_pad), out_dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=_interpret(),
        name="ragged_paged_attention",
    )(tables, kv_len.astype(jnp.int32), chunk_len.astype(jnp.int32),
      jnp.reshape(layer, (1,)).astype(jnp.int32), q, k_pool, v_pool)
    return out[:, :, :gc, :dv]


def ragged_paged_sdpa(
    q: jnp.ndarray,            # [R, C, Hq, D] right-padded per-row chunks
    k_pool: jnp.ndarray,       # [L, P, Hkv, ps, D] stacked pools (storage
    v_pool: jnp.ndarray,       # dtype) with ``layer``, or one [P, Hkv, ps, D]
    tables: jnp.ndarray,       # [R, maxP] int32 (-1 = unallocated)
    kv_len: jnp.ndarray,       # [R] valid slots INCLUDING this chunk
    chunk_len: jnp.ndarray | None = None,  # [R] valid queries; None = all C
    *,
    scale: float | None = None,
    layer=None,                # int32 scalar: which layer of the stack
) -> jnp.ndarray:
    """Ragged-batch attention straight off the paged pool.

    Row i's ``chunk_len[i]`` valid queries sit right-aligned at absolute
    slots ``[kv_len[i] - chunk_len[i], kv_len[i])`` — ``C == 1`` with
    ``chunk_len == 1`` is exactly the decode step, ``chunk_len[i] == 0``
    marks an idle row (zero output), and anything between is a ragged
    prefill chunk whose pad-position outputs are garbage the caller
    discards (the engine's ``gather_positions`` contract).  The chunk's
    own K/V must already be scattered into the pool (the decoder's
    update-then-attend order).  With ``layer`` the pools are the whole
    stacks and the kernel reads that layer of them in place; without, they
    are one layer (a stack of one).  Returns [R, C, Hq, Dv] in q.dtype.
    """
    r, c, hq, d = q.shape
    if layer is None:
        k_pool, v_pool, layer = k_pool[None], v_pool[None], 0
    hkv = k_pool.shape[2]
    if hq % hkv:
        raise NotImplementedError("Hq must be a multiple of Hkv")
    g = hq // hkv
    if scale is None:
        scale = d ** -0.5
    if chunk_len is None:
        chunk_len = jnp.full((r,), c, jnp.int32)
    # [R, C, Hq, D] -> [R, Hkv, G*C, D], chunk axis minor (kernel contract)
    qg = q.transpose(0, 2, 1, 3).reshape(r, hkv, g, c, d).reshape(
        r, hkv, g * c, d)
    out = _ragged(qg, k_pool, v_pool, tables, kv_len, chunk_len,
                  jnp.asarray(layer, jnp.int32), scale=float(scale),
                  out_dtype=q.dtype, c=c)
    dv = v_pool.shape[-1]
    return out.reshape(r, hkv, g, c, dv).transpose(0, 3, 1, 2, 4).reshape(
        r, c, hq, dv)


def ragged_paged_sdpa_sharded(q, k_pool, v_pool, tables, kv_len, mesh,
                              chunk_len=None, *,
                              scale: float | None = None, layer=None):
    """TP form: q heads sharded over ``tp``, pool kv heads sharded (or
    GQA-repeated up to ``tp`` — repeat-of-replicated feeding a
    head-sharded consumer lowers to a local per-shard slice); tables,
    lengths, and chunk lens replicated.  Attention is head-local, so the
    per-shard kernel needs no collective — the following row-parallel
    o-proj psum combines shards (the paged_decode_sdpa_sharded contract,
    extended to the ragged batch).  ``layer`` as in
    :func:`ragged_paged_sdpa`: the stacks pass through whole, head-sharded,
    and every shard reads its heads of that layer in place."""
    from jax.sharding import PartitionSpec as P

    tp = mesh.shape["tp"]
    if layer is None:
        k_pool, v_pool, layer = k_pool[None], v_pool[None], 0
    hq, hkv = q.shape[2], k_pool.shape[2]
    if not heads_shardable(hq, hkv, tp):
        raise NotImplementedError(
            f"unsupported head/tp factorization: {hq} q / {hkv} kv heads "
            f"over tp={tp}")
    if hkv % tp:
        # fewer kv heads than shards: the repeat makes a copy, so it is
        # made of the one layer and not of the stack
        rep = tp // hkv
        k_pool = jnp.repeat(
            jax.lax.dynamic_index_in_dim(k_pool, layer, 0), rep, axis=2)
        v_pool = jnp.repeat(
            jax.lax.dynamic_index_in_dim(v_pool, layer, 0), rep, axis=2)
        layer = 0
    if chunk_len is None:
        chunk_len = jnp.full((q.shape[0],), q.shape[1], jnp.int32)

    def run(ql, kl, vl, tb, ln, cl, ly):
        return ragged_paged_sdpa(ql, kl, vl, tb, ln, cl, scale=scale,
                                 layer=ly)

    q_spec = P(None, None, "tp", None)
    pool_spec = P(None, None, "tp", None, None)
    return jax.shard_map(
        run, mesh=mesh, axis_names={"tp"},
        in_specs=(q_spec, pool_spec, pool_spec, P(None, None), P(None),
                  P(None), P()),
        out_specs=q_spec,
        check_vma=False,
    )(q, k_pool, v_pool, tables, kv_len, chunk_len,
      jnp.asarray(layer, jnp.int32))
