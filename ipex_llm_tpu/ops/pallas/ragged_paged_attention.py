"""Ragged paged attention: ONE kernel for every row shape in an engine tick.

The "Ragged Paged Attention" design (PAPERS.md, arxiv 2604.15464) applied
to this repo's paged pool: a single Pallas program walks a ragged batch of
rows where each row is either a **decode row** (1 query token at slot
``kv_len - 1``), a **prefill-chunk row** (up to C query tokens right-padded
to the chunk width, causally masked against its own history), or an **idle
row** (``chunk_len == 0`` — fully masked, zero output).  So the
serving engine's fused tick program (``serving/engine.py::_ragged_tick``)
carries exactly one attention kernel family regardless of the admission mix.

What one kernel for every row shape rests on:

- **per-row raggedness is traced, not static**: ``chunk_len`` [R] rides the
  scalar prefetch, so one compiled program serves every (decode, prefill,
  idle) row mix — the causal mask is keyed off ``(kv_len, chunk_len)`` per
  row instead of a uniform static chunk;
- **query-row tiling**: the whole GQA group x chunk tile ``[G*C, D]`` feeds
  ONE MXU dot per head and page, amortizing each K/V page fetch across
  every query row that needs it;
- **no full-width accumulator re-materialization**: the online-softmax
  state (m/l/acc) lives in VMEM scratch across the page walk and the
  output tile is written exactly once, after the last page — versus the
  XLA fallback materializing fp32 ``[R, H, C, maxP*ps]`` score/prob
  tensors over the row's full table width per layer.

**The walk is bounded by what is live** (PR 29).  The grid is rows x
blocks of kv heads; the table's width is no axis of it.  A grid step reads
its row's ``kv_len`` and ``chunk_len`` from scalar prefetch and makes
``cdiv(kv_len, page_size)`` trips of an in-kernel loop, none for an idle
row: a page past a row's last live one costs no step, and a dead row
costs one empty step and writes zeros.  The pools stay in HBM
(``memory_space=pl.ANY``); each trip copies ONE page of the step's kv
heads — contiguous in ``[L, P, Hkv, ps, D]``, 256 KB of K and as much of V
for 8 heads of 128 in bf16 — into one of two VMEM slots with
``make_async_copy`` while the page before it is computed.  How many heads
a step takes (``_heads_per_step``) follows from the shapes the call sees
(``Hkv``, ``G*C``, ``D``, the storage dtype) against a VMEM plan: a decode
tile takes a whole page of heads, a 128-wide prefill chunk fewer; heads
are independent, so a row's bits do not depend on it.  Pages are visited
in order 0..last and a row's arithmetic touches nothing of another row:
its output bits are a function of its own q, pages, ``kv_len`` and
``chunk_len`` only — not of the number of rows, its neighbours, or the
table's width (the engine's bit-identity invariants rest on this).  The
QK dot takes q and K in the compute dtype (bf16) and accumulates in
float32: the same exact products as the float32 x float32 dot it
replaces; scores, softmax state and accumulator stay float32.

The pool is addressed by ``(layer, page)``: the kernel takes the WHOLE
stacked pools ``[L, P, Hkv, ps, D]`` and the layer index as a fourth
scalar-prefetch operand, and a page's copy reads ``pool[layer, table[row,
trip]]`` — so the decoder's layer scan never slices a layer out of the
stack to feed it (kv.py ``PagedKVCache``).  A caller holding one layer
``[P, Hkv, ps, D]`` passes it as a stack of one.  Head sizes that need
lane padding (``D % 128``) pad a copy of the pool: for those the one layer
is sliced out first, a choice by shape, so the pad never touches the stack.
(A latent-attention model's pool holds the latent, not per-head K at 192,
and takes the walk at the end of this file.)

K/V pages stream in the pool's STORAGE dtype and widen to the compute
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


# VMEM a grid step may plan for (K/V page buffers, the q and o blocks, the
# softmax state): half of the 16 MiB a kernel is given by default, the rest
# left to the compiler's temporaries (scores and probabilities of one head)
_VMEM_PLAN_BYTES = 8 * 2**20
# heads a step walks are unrolled in the kernel's body
_MAX_HEADS_PER_STEP = 8


def _heads_per_step(hkv, gc, d, dv, ps, kv_bytes, q_bytes):
    """How many kv heads of a page one grid step takes: the largest divisor
    of ``hkv`` whose buffers fit the plan.  From the call's shapes alone:
    a decode tile takes every head of a page in one copy, a wide prefill
    chunk fewer."""
    per_head = (2 * ps * (d + dv) * kv_bytes        # K and V pages, 2 slots
                + 2 * gc * (d + dv) * q_bytes       # q and o blocks, pipelined
                + gc * dv * 4 + 2 * gc * 128 * 4)   # acc; m and l, lane-padded
    fit = max(1, min(_VMEM_PLAN_BYTES // per_head, _MAX_HEADS_PER_STEP))
    return max(h for h in range(1, hkv + 1) if hkv % h == 0 and h <= fit)


def _attend_page(q, k, v, mask, h, m_ref, l_ref, acc_ref, *, scale,
                 compute_dtype):
    """One head's online-softmax step over one page: q [G*C, D] against
    the page's K and V tiles [ps, D(v)] in the pool's storage dtype, the
    running max / sum / accumulator of head ``h`` updated in place."""
    # the storage-dtype tile (possibly e5m2) widens HERE, inside the
    # kernel, so an fp8 pool streams half the bytes
    k = k.astype(compute_dtype)
    if q.dtype != compute_dtype:
        q, k = q.astype(jnp.float32), k.astype(jnp.float32)
    # compute-dtype operands, float32 accumulation: the same exact
    # products as a float32 x float32 dot
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    ) * scale                                            # [G*C, ps]
    s = jnp.where(mask, s, NEG_INF)
    m_prev = m_ref[h]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    m_safe = jnp.maximum(m_new, -1e29)
    p = jnp.exp(s - m_safe)
    alpha = jnp.exp(jnp.maximum(m_prev, -1e29) - m_safe)
    l_ref[h] = l_ref[h] * alpha + jnp.sum(p, axis=-1, keepdims=True)
    acc_ref[h] = acc_ref[h] * alpha + jax.lax.dot_general(
        p.astype(compute_dtype), v.astype(compute_dtype),
        (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    m_ref[h] = m_new


def _kernel(tables_ref, len_ref, chunk_ref, layer_ref, q_ref, k_hbm, v_hbm,
            o_ref, kbuf, vbuf, sem, m_ref, l_ref, acc_ref, *, scale, ps, c,
            hb, compute_dtype):
    """One (row, block of ``hb`` kv heads) grid step: the row's whole page
    walk, ``cdiv(kv_len, ps)`` trips (none for an idle row), each trip one
    page of ``hb`` heads copied HBM -> VMEM while the page before it is
    computed.

    q rows are the ``[G, C]`` group x chunk tile flattened chunk-minor:
    flat row j is the query at absolute slot
    ``kv_len - chunk_len + (j % c)``, so a decode row (``chunk_len == 1``,
    ``c`` may still be > 1 when batched with prefill rows) reduces to the
    classic single query at ``kv_len - 1``, and a prefill row's valid
    queries are causal against their own history.  Pad query rows
    (``j % c >= chunk_len``) land past ``kv_len`` and read only valid
    slots: bounded garbage the caller discards.  ``chunk_len == 0`` rows
    make no trip and emit exact zeros.
    """
    r = pl.program_id(0)
    hkv = k_hbm.shape[2]
    heads = (slice(None) if hb == hkv
             else pl.ds(pl.program_id(1) * hb, hb))
    kv_len = len_ref[r]
    chunk = chunk_ref[r]
    layer = layer_ref[0]
    # a length past the table (a stale or sentinel one) walks the table
    # and no further
    n_pages = jnp.where(
        chunk > 0, jnp.minimum(pl.cdiv(kv_len, ps), tables_ref.shape[1]), 0)

    def page_copies(i, slot):
        page = tables_ref[r, i]
        return (
            pltpu.make_async_copy(k_hbm.at[layer, page, heads],
                                  kbuf.at[slot], sem.at[0, slot]),
            pltpu.make_async_copy(v_hbm.at[layer, page, heads],
                                  vbuf.at[slot], sem.at[1, slot]),
        )

    @pl.when(n_pages == 0)
    def _():
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(n_pages > 0)
    def _():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)
        for cp in page_copies(0, 0):
            cp.start()

        def page_step(i, carry):
            slot = jax.lax.rem(i, 2)

            @pl.when(i + 1 < n_pages)
            def _():
                for cp in page_copies(i + 1, 1 - slot):
                    cp.start()

            for cp in page_copies(i, slot):
                cp.wait()
            lo = i * ps
            gc = q_ref.shape[2]
            kpos = lo + jax.lax.broadcasted_iota(jnp.int32, (gc, ps), 1)
            qpos = (kv_len - chunk
                    + jax.lax.broadcasted_iota(jnp.int32, (gc, ps), 0) % c)
            # per-row causal mask keyed off (kv_len, chunk_len): a pad
            # query (qpos >= kv_len) still needs the kv_len bound
            mask = (kpos <= qpos) & (kpos < kv_len)
            for h in range(hb):
                _attend_page(q_ref[0, h], kbuf[slot, h], vbuf[slot, h], mask,
                             h, m_ref, l_ref, acc_ref, scale=scale,
                             compute_dtype=compute_dtype)
            return carry

        jax.lax.fori_loop(0, n_pages, page_step, None)
        denom = jnp.where(l_ref[...] == 0.0, 1.0, l_ref[...])
        o_ref[0] = (acc_ref[...] / denom).astype(o_ref.dtype)


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
    # walk ends at the row's last live page, so they are never copied for
    # a row whose kv_len ends earlier
    tables = jnp.clip(tables, 0, n_pages - 1).astype(jnp.int32)
    hb = _heads_per_step(hkv, gc_pad, d_pad, dv_pad, ps,
                         k_pool.dtype.itemsize, q.dtype.itemsize)

    def q_map(ri, hi, tables_ref, len_ref, chunk_ref, layer_ref):
        return (ri, hi, 0, 0)

    # the grid is rows x head blocks: the table's width is no axis of it,
    # and the pools stay in HBM, read a page at a time by the walk's copies
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(r, hkv // hb),
        in_specs=[
            pl.BlockSpec((1, hb, gc_pad, d_pad), q_map),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((1, hb, gc_pad, dv_pad), q_map),
        scratch_shapes=[
            pltpu.VMEM((2, hb, ps, d_pad), k_pool.dtype),
            pltpu.VMEM((2, hb, ps, dv_pad), v_pool.dtype),
            pltpu.SemaphoreType.DMA((2, 2)),
            pltpu.VMEM((hb, gc_pad, 1), jnp.float32),
            pltpu.VMEM((hb, gc_pad, 1), jnp.float32),
            pltpu.VMEM((hb, gc_pad, dv_pad), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(_kernel, scale=scale, ps=ps, c=c, hb=hb,
                          compute_dtype=jnp.bfloat16),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((r, hkv, gc_pad, dv_pad), out_dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
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


# -- latent attention (MLA) over a latent pool -----------------------------------
#
# A latent pool (kv.py ``pool_geometry``) holds one row a token a layer,
# shared by every head: the normalised compressed KV ``c`` [L, P, 1, ps,
# rank] and the rotated shared key ``k_r`` token-minor [L, P, 1, rope, ps]
# (a page of it is whole tiles, and multiplies as it lies).  ONE kernel reads
# it, the ABSORBED walk (:func:`latent_paged_sdpa`): every head of a row
# against each latent page, an MQA walk whose key is ``[c ; k_r]`` and whose
# value is ``c``, the key's first ``rank`` lanes.  The query has ``W_UK``
# folded in (``q~ = W_UK^T q_nope``, models/decoder.py) and the output is
# still latent (``W_UV`` follows).  The same scalar prefetch, HBM pools and
# two VMEM slots as the K/V walk above, a few pages a trip over the row's
# ``cdiv(kv_len, ps)`` live pages; the grid's second axis blocks a row's
# queries (chunk x heads, token-major) where it blocked kv heads.  Decode
# rows, prefill chunks and verify windows all take it, causal against the
# row's own history like the K/V walk.
#
# A chunk's own keys are walked ABSORBED too, out of the pool, where the
# write has just put them.  (Taking them in the expanded form, per-head K and
# V up-projected from the chunk's latent, and merging the two parts by their
# log-sum-exp is fewer operations, and was measured: it makes a token's bits
# depend on how its prompt was CHUNKED, and the chunk's width follows from how
# many rows join a tick; on the chip a 120-token probe beside prefilling rows
# read another first log-probability and another token within 24 in 2 of 3
# cases, PERF.md section 6, PR 37.)  Here a query's arithmetic is a function
# of its own ``q``, the pages before it in order, and the trips' fixed
# bounds: a trip it cannot see leaves its state untouched, bit for bit.

_LATENT_Q_BLOCK = 512
_LATENT_PAGES_PER_TRIP = 4


def _attend_trip(s, c, m_ref, l_ref, acc_ref, compute_dtype):
    """One online-softmax step of the absorbed walk over a trip's masked
    float32 scores ``s`` [Q, K]: the value is the latent ``c`` [K, rank]
    itself; the running max / sum / accumulator updated in place."""
    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    m_safe = jnp.maximum(m_new, -1e29)
    p = jnp.exp(s - m_safe)
    alpha = jnp.exp(jnp.maximum(m_prev, -1e29) - m_safe)
    l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=-1, keepdims=True)
    acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
        p.astype(compute_dtype), c.astype(compute_dtype),
        (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    m_ref[...] = m_new


def _latent_kernel(tables_ref, len_ref, chunk_ref, layer_ref, ql_ref, qr_ref,
                   c_hbm, r_hbm, o_ref, cbuf, rbuf, sem, m_ref, l_ref,
                   acc_ref, *, scale, ps, pp, heads, compute_dtype):
    """One (row, block of queries) grid step of the absorbed walk: the
    row's pages ``[0, cdiv(kv_len, ps))``, ``pp`` latent pages a trip (a
    trip costs ~1.5 us whatever it moves, and one latent page is 0.2 us of
    the device's bandwidth).  Query row j of the block is token ``j //
    heads`` of the chunk, at slot ``kv_len - chunk_len + j // heads``.  A
    trip past the row's last live page copies that page again: its slots lie
    past ``kv_len`` and are masked."""
    r = pl.program_id(0)
    kv_len = len_ref[r]
    chunk = chunk_ref[r]
    layer = layer_ref[0]
    n_pages = jnp.where(
        chunk > 0, jnp.minimum(pl.cdiv(kv_len, ps), tables_ref.shape[1]), 0)
    n_trips = pl.cdiv(n_pages, pp)
    q0 = pl.program_id(1) * ql_ref.shape[1]    # this block's first query row

    def trip_copies(i, slot):
        copies = []
        for j in range(pp):
            page = tables_ref[r, jnp.minimum(i * pp + j, n_pages - 1)]
            copies.append(pltpu.make_async_copy(
                c_hbm.at[layer, page, 0], cbuf.at[slot, pl.ds(j * ps, ps)],
                sem.at[0, slot, j]))
            copies.append(pltpu.make_async_copy(
                r_hbm.at[layer, page, 0],
                rbuf.at[slot, :, pl.ds(j * ps, ps)], sem.at[1, slot, j]))
        return copies

    @pl.when(n_pages == 0)
    def _():
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(n_pages > 0)
    def _():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)
        for cp in trip_copies(0, 0):
            cp.start()
        ql, qr = ql_ref[0], qr_ref[0]
        nq = ql.shape[0]
        qpos = (kv_len - chunk + (
            q0 + jax.lax.broadcasted_iota(jnp.int32, (nq, pp * ps), 0))
            // heads)

        def trip(i, carry):
            slot = jax.lax.rem(i, 2)

            @pl.when(i + 1 < n_trips)
            def _():
                for cp in trip_copies(i + 1, 1 - slot):
                    cp.start()

            for cp in trip_copies(i, slot):
                cp.wait()
            c, kr = cbuf[slot], rbuf[slot]
            # the rotated key's pages lie [rope, ps]: multiplied as they lie
            s = (jax.lax.dot_general(
                ql, c, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)
                + jax.lax.dot_general(
                    qr, kr, (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32)) * scale
            kpos = (i * (pp * ps)
                    + jax.lax.broadcasted_iota(jnp.int32, (nq, pp * ps), 1))
            # a pad query (qpos >= kv_len) still needs the kv_len bound
            s = jnp.where((kpos <= qpos) & (kpos < kv_len), s, NEG_INF)
            _attend_trip(s, c, m_ref, l_ref, acc_ref, compute_dtype)
            return carry

        jax.lax.fori_loop(0, n_trips, trip, None)
        denom = jnp.where(l_ref[...] == 0.0, 1.0, l_ref[...])
        o_ref[0] = (acc_ref[...] / denom).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("scale", "heads"))
def _latent(ql, qr, c_pool, r_pool, tables, kv_len, chunk_len, layer, *,
            scale, heads):
    """ql [R, Q, rank], qr [R, Q, rope] (a row's queries, chunk x heads
    flattened token-major); c_pool [L, P, 1, ps, rank], r_pool [L, P, 1,
    rope, ps] (token-minor).  Returns o [R, Q, rank] in ql.dtype."""
    r, nq, rank = ql.shape
    rope = qr.shape[-1]
    n_pages, ps = c_pool.shape[1], c_pool.shape[3]
    pp = min(_LATENT_PAGES_PER_TRIP, tables.shape[1])
    qb = min(_LATENT_Q_BLOCK, _round_up(nq, 8))
    nq_pad = _round_up(nq, qb)
    if nq_pad != nq:
        ql = jnp.pad(ql, ((0, 0), (0, nq_pad - nq), (0, 0)))
        qr = jnp.pad(qr, ((0, 0), (0, nq_pad - nq), (0, 0)))
    tables = jnp.clip(tables, 0, n_pages - 1).astype(jnp.int32)

    def q_map(ri, qi, *_):
        return (ri, qi, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(r, nq_pad // qb),
        in_specs=[
            pl.BlockSpec((1, qb, rank), q_map),
            pl.BlockSpec((1, qb, rope), q_map),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((1, qb, rank), q_map),
        scratch_shapes=[
            pltpu.VMEM((2, pp * ps, rank), c_pool.dtype),
            pltpu.VMEM((2, rope, pp * ps), r_pool.dtype),
            pltpu.SemaphoreType.DMA((2, 2, pp)),
            pltpu.VMEM((qb, 1), jnp.float32),
            pltpu.VMEM((qb, 1), jnp.float32),
            pltpu.VMEM((qb, rank), jnp.float32),
        ],
    )
    o = pl.pallas_call(
        functools.partial(_latent_kernel, scale=scale, ps=ps, pp=pp,
                          heads=heads, compute_dtype=jnp.bfloat16),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((r, nq_pad, rank), ql.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
            vmem_limit_bytes=32 * 2**20,
        ),
        interpret=_interpret(),
        name="ragged_paged_attention_latent",
    )(tables, kv_len.astype(jnp.int32), chunk_len.astype(jnp.int32),
      jnp.reshape(layer, (1,)).astype(jnp.int32), ql, qr, c_pool, r_pool)
    return o[:, :nq]


def latent_paged_sdpa(q_lat, q_rope, c_pool, r_pool, tables, kv_len,
                      chunk_len=None, *, scale: float, layer=None):
    """The absorbed walk.  q_lat [R, C, H, rank] (``W_UK`` folded in),
    q_rope [R, C, H, rope], right-padded per-row chunks as in
    :func:`ragged_paged_sdpa`; the latent pools whole with ``layer`` (or one
    layer each without); kv_len [R] valid slots including this chunk, whose
    tokens the pool already holds; chunk_len [R] valid queries (0 = idle
    row: no trip, zeros; None = all C).  Returns o_lat [R, C, H, rank] in
    q's dtype, still in the latent space."""
    r, c, h, rank = q_lat.shape
    if layer is None:
        c_pool, r_pool, layer = c_pool[None], r_pool[None], 0
    if chunk_len is None:
        chunk_len = jnp.full((r,), c, jnp.int32)
    o = _latent(
        q_lat.reshape(r, c * h, rank), q_rope.reshape(r, c * h, -1), c_pool,
        r_pool, tables, kv_len, chunk_len, jnp.asarray(layer, jnp.int32),
        scale=float(scale), heads=h)
    return o.reshape(r, c, h, rank)


# --- the walk under a selection (a SPARSE latent pool) -----------------------
#
# A latent-attention model with an indexer (kv.py ``pool_geometry``) keeps a
# token's whole key in ONE row of its pool, ``[c ; k_r ; 0]`` padded to whole
# lanes, and a chunk's query attends only the slots its selection names
# (ops/attention.py ``select_topk``: a mask over the row's slots).  The walk
# is the absorbed walk above with two changes: a trip's scores are ONE dot of
# the query row against the page rows as they lie (the pad lanes are zeros on
# both sides), and the causal bound is replaced by the selection's mask, which
# already lies inside it.  A query's arithmetic is a function of its own
# ``q``, its own mask row and the pages in order: the same invariance.


def _sparse_kernel(tables_ref, len_ref, chunk_ref, layer_ref, q_ref, sel_ref,
                   k_hbm, o_ref, kbuf, sem, m_ref, l_ref, acc_ref, *, scale,
                   ps, pp, heads, rank, compute_dtype):
    """One (row, block of queries) grid step: query row j of the block is
    token ``j // heads`` of the block's tokens, whose mask rows are
    ``sel_ref`` [tokens, S]."""
    r = pl.program_id(0)
    kv_len = len_ref[r]
    chunk = chunk_ref[r]
    layer = layer_ref[0]
    n_pages = jnp.where(
        chunk > 0, jnp.minimum(pl.cdiv(kv_len, ps), tables_ref.shape[1]), 0)
    n_trips = pl.cdiv(n_pages, pp)
    span = pp * ps

    def trip_copies(i, slot):
        copies = []
        for j in range(pp):
            page = tables_ref[r, jnp.minimum(i * pp + j, n_pages - 1)]
            copies.append(pltpu.make_async_copy(
                k_hbm.at[layer, page, 0], kbuf.at[slot, pl.ds(j * ps, ps)],
                sem.at[slot, j]))
        return copies

    @pl.when(n_pages == 0)
    def _():
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(n_pages > 0)
    def _():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)
        for cp in trip_copies(0, 0):
            cp.start()
        q = q_ref[0]
        nq = q.shape[0]
        ntok = nq // heads

        def trip(i, carry):
            slot = jax.lax.rem(i, 2)

            @pl.when(i + 1 < n_trips)
            def _():
                for cp in trip_copies(i + 1, 1 - slot):
                    cp.start()

            for cp in trip_copies(i, slot):
                cp.wait()
            rows = kbuf[slot]                                  # [span, W]
            s = jax.lax.dot_general(
                q, rows, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale     # [nq, span]
            chosen = sel_ref[0, :, pl.ds(pl.multiple_of(i * span, span),
                                         span)]                 # [ntok, span]
            kpos = i * span + jax.lax.broadcasted_iota(
                jnp.int32, (ntok, span), 1)
            # a trip's tail past the row's last live page re-reads that page
            live = (chosen > 0) & (kpos < kv_len)
            live = jnp.broadcast_to(
                live[:, None, :], (ntok, heads, span)).reshape(nq, span)
            # an unchosen slot adds nothing, whatever the running max is
            _attend_trip(jnp.where(live, s, NEG_INF), rows[:, :rank], m_ref,
                         l_ref, acc_ref, compute_dtype)
            return carry

        jax.lax.fori_loop(0, n_trips, trip, None)
        denom = jnp.where(l_ref[...] == 0.0, 1.0, l_ref[...])
        o_ref[0] = (acc_ref[...] / denom).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("scale", "heads", "rank"))
def _sparse(q, k_pool, tables, sel, kv_len, chunk_len, layer, *, scale, heads,
            rank):
    """q [R, C * heads, W] (a row's queries, token-major); k_pool [L, P, 1,
    ps, W]; sel [R, C, S] int32 over the table's width S.  Returns o [R, C *
    heads, rank] in q.dtype."""
    r, nq, width = q.shape
    n_pages, ps = k_pool.shape[1], k_pool.shape[3]
    pp = min(_LATENT_PAGES_PER_TRIP, tables.shape[1])
    span = pp * ps
    # whole tokens a block, eight of them at least (the mask block's sublanes)
    qb = min(_LATENT_Q_BLOCK, _round_up(nq, 8 * heads))
    qb = max(qb // (8 * heads), 1) * 8 * heads
    nq_pad = _round_up(nq, qb)
    if nq_pad != nq:
        q = jnp.pad(q, ((0, 0), (0, nq_pad - nq), (0, 0)))
    s_pad = _round_up(sel.shape[2], span)
    sel = jnp.pad(sel, ((0, 0), (0, nq_pad // heads - sel.shape[1]),
                        (0, s_pad - sel.shape[2])))
    tables = jnp.clip(tables, 0, n_pages - 1).astype(jnp.int32)

    def q_map(ri, qi, *_):
        return (ri, qi, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(r, nq_pad // qb),
        in_specs=[
            pl.BlockSpec((1, qb, width), q_map),
            pl.BlockSpec((1, qb // heads, s_pad), q_map),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((1, qb, rank), q_map),
        scratch_shapes=[
            pltpu.VMEM((2, span, width), k_pool.dtype),
            pltpu.SemaphoreType.DMA((2, pp)),
            pltpu.VMEM((qb, 1), jnp.float32),
            pltpu.VMEM((qb, 1), jnp.float32),
            pltpu.VMEM((qb, rank), jnp.float32),
        ],
    )
    o = pl.pallas_call(
        functools.partial(_sparse_kernel, scale=scale, ps=ps, pp=pp,
                          heads=heads, rank=rank, compute_dtype=jnp.bfloat16),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((r, nq_pad, rank), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
            vmem_limit_bytes=48 * 2**20,
        ),
        interpret=_interpret(),
        name="ragged_paged_attention_sparse",
    )(tables, kv_len.astype(jnp.int32), chunk_len.astype(jnp.int32),
      jnp.reshape(layer, (1,)).astype(jnp.int32), q, sel, k_pool)
    return o[:, :nq]


def sparse_paged_sdpa(q_row, k_pool, tables, sel, kv_len, chunk_len=None, *,
                      rank: int, scale: float, layer=None):
    """The absorbed walk under a selection.  q_row [R, C, H, W] against the
    sparse latent pool's rows ``[c ; k_r ; 0]`` (whole with ``layer``, or one
    layer without); sel [R, C, S] which slots each query attends (within
    what it sees); kv_len / chunk_len as in :func:`latent_paged_sdpa`.
    Returns o_lat [R, C, H, rank] in q's dtype."""
    r, c, h, width = q_row.shape
    if layer is None:
        k_pool, layer = k_pool[None], 0
    if chunk_len is None:
        chunk_len = jnp.full((r,), c, jnp.int32)
    o = _sparse(q_row.reshape(r, c * h, width), k_pool, tables,
                sel.astype(jnp.int32), kv_len, chunk_len,
                jnp.asarray(layer, jnp.int32), scale=float(scale), heads=h,
                rank=rank)
    return o.reshape(r, c, h, rank)
