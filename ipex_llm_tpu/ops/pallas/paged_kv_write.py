"""Paged KV write: a tick's new K/V rows into the stacked pool, in place.

The write half of addressing the pool by ``(layer, page)`` (kv.py
``PagedKVCache``; the read half is ragged_paged_attention.py's index map).
The XLA form, ``PagedKVCache.write_layer``'s scatter on the carried stack,
is the fallback and the reference; on the chip it is not usable: XLA's
scatter wants the pool in a token-major layout and the attention kernel in
the default one, so the compiler re-lays the whole pool out around every
layer's write (the third rehearsal, PR 26).  This kernel keeps the pool in
its one layout: both stacks are aliased in to out
(``input_output_aliases``), and each grid step reads, patches and writes
back one ``[Hkv, sb, D]`` block of one page, the ``sb`` token slots (32, or
the page where it is smaller) that hold some of a row's new tokens, found
through scalar-prefetched ``(layer, page, block in page)``.  Everything
else of the pool is never touched.

Same bytes in the same places as the XLA form: rows arrive encoded to the
storage dtype (bf16 or e5m2), every one of a right-padded chunk's ``T``
tokens is written (the pad tail too), and a block past the table's width
or in an unallocated page goes to scratch page 0.  Several rows' blocks
can land on the scratch page in one call; which of them it keeps is not
defined (the scatter's own rule for duplicates), and nothing reads it.

Inside a block the new tokens sit at a traced offset.  They are moved
there by a rotation along the token axis (``pltpu.roll``) in f32, which
holds every bf16 and e5m2 value exactly, and merged under a mask, so the
kernel needs no store at a traced sublane offset.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ipex_llm_tpu.ops.pallas._compat import (
    interpret as _interpret,
    round_up as _round_up,
)

# token slots a grid step patches: a whole tile of either storage dtype
# (bf16 packs 16 rows a tile, e5m2 32)
_SLOT_BLOCK = 32


def _kernel(layer_ref, page_ref, blk_ref, t0_ref, shift_ref, *refs, t, sb, m,
            nsub):
    """Grid step (row, j): patch the j-th slot block this row's chunk
    touches, in each of the call's pools (``refs``: the new rows of every
    pool, then the pools in, then the pools out).  Row s of the block takes
    token ``t0 + s`` of the chunk where that is one of its ``t`` tokens, and
    keeps what it held elsewhere."""
    n = len(refs) // 3
    i = pl.program_id(0) * nsub + pl.program_id(1)
    tok = t0_ref[i] + jax.lax.broadcasted_iota(jnp.int32, (1, sb, 1), 1)
    mask = (tok >= 0) & (tok < t)
    for new_ref, src, dst in zip(refs[:n], refs[n:2 * n], refs[2 * n:]):
        new = new_ref[0]                                  # [Hkv, T8, D] f32
        if new.shape[1] < m:
            new = jnp.concatenate(
                [new, jnp.zeros((new.shape[0], m - new.shape[1],
                                 new.shape[2]), new.dtype)], axis=1)
        # rolled[s] = new[(s + t0) mod m]: the chunk's tokens moved to
        # where they sit in this block
        cand = pltpu.roll(new, shift_ref[i], axis=1)[:, :sb, :]
        tile = src[0].astype(jnp.float32)                 # [Hkv, sb, D]
        dst[0] = jnp.where(mask, cand, tile).astype(dst.dtype)


def _slot_blocks(pos, pages, t: int, ps: int, sb: int):
    """Where a chunk's ``t`` tokens from slot ``pos`` [B] land, in slot
    blocks of ``sb``: (nsub, page [B, nsub], block in page, t0, the chunk
    token in the block's row 0).  Slot block g of a row covers slots [g*sb,
    (g+1)*sb); past the chunk's last block the walk stays on it (same block
    index: Pallas moves nothing, and the step rewrites what the last one
    wrote)."""
    nsub = -(-(t - 1) // sb) + 1          # slot blocks a chunk can span
    g = jnp.minimum(pos[:, None] // sb + jnp.arange(nsub)[None, :],
                    ((pos + t - 1) // sb)[:, None])              # [B, nsub]
    page = jnp.take_along_axis(pages, g * sb // ps - (pos // ps)[:, None],
                               axis=1)
    return nsub, page, g % (ps // sb), g * sb - pos[:, None]


def _flat(a):
    return a.reshape(-1).astype(jnp.int32)


@jax.jit
def _write(k, v, layer, pages, pos, new_k, new_v):
    """k/v [L, P, Hkv, ps, D(v)] stacks; ``layer`` int32 scalar; ``pages``
    [B, nP] the routed page of each page index a row's chunk can touch
    (0 = scratch), first its page ``pos // ps``; ``pos`` [B] the chunk's
    first slot; new_k/new_v [B, T, Hkv, D(v)] in the storage dtype."""
    return _write_pools((k, v), layer, pages, pos, (new_k, new_v))


def _write_pools(pools, layer, pages, pos, news):
    """:func:`_write` over any number of pools that share the K/V layout
    ``[L, P, Hkv, ps, D]`` (token slots second-minor)."""
    b, t = news[0].shape[:2]
    _, _, hkv, ps, _ = pools[0].shape
    n = len(pools)
    sb = _SLOT_BLOCK if ps % _SLOT_BLOCK == 0 else ps
    t8 = _round_up(t, 8)
    m = max(t8, sb)
    nsub, page, blk, t0 = _slot_blocks(pos, pages, t, ps, sb)

    def rows(new):                        # [B, T, H, D] -> [B, H, T8, D] f32
        new = new.astype(jnp.float32).transpose(0, 2, 1, 3)
        return jnp.pad(new, ((0, 0), (0, 0), (0, t8 - t), (0, 0)))

    def pool_map(bi, ji, layer_ref, page_ref, blk_ref, t0_ref, shift_ref):
        i = bi * nsub + ji
        return (layer_ref[0], page_ref[i], 0, blk_ref[i], 0)

    def new_map(bi, ji, *_):
        return (bi, 0, 0, 0)

    def pool_spec(d):
        return pl.BlockSpec((None, 1, hkv, sb, d), pool_map)

    widths = [a.shape[-1] for a in pools]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5,
        grid=(b, nsub),
        in_specs=[pl.BlockSpec((1, hkv, t8, d), new_map) for d in widths]
        + [pool_spec(d) for d in widths],
        out_specs=[pool_spec(d) for d in widths],
    )
    return pl.pallas_call(
        functools.partial(_kernel, t=t, sb=sb, m=m, nsub=nsub),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct(a.shape, a.dtype) for a in pools],
        # operands count the scalar prefetch: the stacks follow it and the
        # new rows
        input_output_aliases={5 + n + i: i for i in range(n)},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
        ),
        interpret=_interpret(),
        name="paged_kv_write",
    )(jnp.reshape(layer, (1,)).astype(jnp.int32), _flat(page), _flat(blk),
      _flat(t0), _flat((-t0) % m), *(rows(a) for a in news), *pools)


def _lane_kernel(layer_ref, page_ref, t0_ref, shift_ref, new_ref, src, dst, *,
                 t, ps, m, nsub):
    """Grid step (row, j) over a TOKEN-MINOR pool: patch the j-th page this
    row's chunk touches.  Lane s of the page takes token ``t0 + s`` of the
    chunk where that is one of its ``t`` tokens."""
    i = pl.program_id(0) * nsub + pl.program_id(1)
    tok = t0_ref[i] + jax.lax.broadcasted_iota(jnp.int32, (1, ps), 1)
    mask = (tok >= 0) & (tok < t)
    new = new_ref[0]                                      # [D, Tp] f32
    if new.shape[1] < m:
        new = jnp.concatenate(
            [new, jnp.zeros((new.shape[0], m - new.shape[1]), new.dtype)],
            axis=1)
    cand = pltpu.roll(new, shift_ref[i], axis=1)[:, :ps]
    tile = src[0, 0].astype(jnp.float32)                  # [D, ps]
    dst[0, 0] = jnp.where(mask, cand, tile).astype(dst.dtype)


def _write_lanes(pool, layer, pages, pos, new):
    """The write into a token-minor pool ``[L, P, 1, D, ps]`` (a latent
    pool's shared rotated key, kv.py): new [B, T, 1, D]; a grid step patches
    one whole page ``[D, ps]``, the chunk's tokens rotated along the lanes
    to where they sit in it."""
    b, t = new.shape[:2]
    _, _, _, d, ps = pool.shape
    tp = _round_up(t, 128)
    m = max(tp, _round_up(ps, 128))
    nsub, page, _, t0 = _slot_blocks(pos, pages, t, ps, ps)

    def pool_map(bi, ji, layer_ref, page_ref, t0_ref, shift_ref):
        return (layer_ref[0], page_ref[bi * nsub + ji], 0, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(b, nsub),
        in_specs=[pl.BlockSpec((1, d, tp), lambda bi, ji, *_: (bi, 0, 0)),
                  pl.BlockSpec((None, 1, 1, d, ps), pool_map)],
        out_specs=pl.BlockSpec((None, 1, 1, d, ps), pool_map),
    )
    cols = jnp.pad(new[:, :, 0, :].astype(jnp.float32).transpose(0, 2, 1),
                   ((0, 0), (0, 0), (0, tp - t)))         # [B, D, Tp]
    return pl.pallas_call(
        functools.partial(_lane_kernel, t=t, ps=ps, m=m, nsub=nsub),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(pool.shape, pool.dtype),
        input_output_aliases={5: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
        ),
        interpret=_interpret(),
        name="paged_kv_write_lanes",
    )(jnp.reshape(layer, (1,)).astype(jnp.int32), _flat(page), _flat(t0),
      _flat((-t0) % m), cols, pool)


@jax.jit
def _write_latent(k, v, layer, pages, pos, new_c, new_r):
    """A latent pool's write: ``c`` rows into ``k`` [L, P, 1, ps, rank] like
    K rows, the rotated key into the token-minor ``v`` [L, P, 1, rope,
    ps]."""
    (k,) = _write_pools((k,), layer, pages, pos, (new_c,))
    return k, _write_lanes(v, layer, pages, pos, new_r)


def _chunk_pages(cache, t: int, pos):
    """The routed page of each page index a ``t``-token chunk from slot
    ``pos`` [B] can touch (0 = scratch), first its page ``pos // ps``."""
    ps = cache.page_size
    n_pages = -(-(t - 1) // ps) + 1
    return cache.route_pages(
        (pos // ps)[:, None] + jnp.arange(n_pages)[None, :])


@jax.jit
def _write_one(pool, layer, pages, pos, new):
    return _write_pools((pool,), layer, pages, pos, (new,))[0]


def paged_pool_write(cache, pool, layer, new, pos):
    """``cache.write_pool`` as a kernel: new [B, T, H, D] into ONE
    token-major stack ``pool`` [L, P, H, page, D] at ``(layer, page, :,
    offset)`` through ``cache.tables``, in place (a sparse latent pool's
    latent rows, or its index keys)."""
    b, t = new.shape[:2]
    if getattr(pos, "ndim", 0) == 0:
        pos = jnp.broadcast_to(pos, (b,))
    return _write_one(pool, jnp.asarray(layer, jnp.int32),
                      _chunk_pages(cache, t, pos), pos, cache.encode(new))


def paged_kv_write(cache, k, v, layer, new_k, new_v, pos, mesh=None):
    """``cache.write_layer`` as a kernel: write new_k/new_v [B, T, Hkv, D]
    into the stacks ``k``/``v`` [L, P, Hkv, page, D] at ``(layer, page, :,
    offset)`` through ``cache.tables`` and return the stacks, which the
    call updates in place.  With ``mesh`` (tensor parallelism under GSPMD)
    the stacks and the new rows split over their kv heads and every shard
    patches its heads of the same blocks; the routing is replicated.  A
    LATENT pool (``cache.latent``) takes its own two writes."""
    b, t = new_k.shape[:2]
    if getattr(pos, "ndim", 0) == 0:
        pos = jnp.broadcast_to(pos, (b,))
    pages = _chunk_pages(cache, t, pos)
    args = (k, v, jnp.asarray(layer, jnp.int32), pages, pos,
            cache.encode(new_k), cache.encode(new_v))
    if cache.latent:
        return _write_latent(*args)
    if mesh is None:
        return _write(*args)
    from jax.sharding import PartitionSpec as P

    pool_spec = P(None, None, "tp", None, None)
    new_spec = P(None, None, "tp", None)
    return jax.shard_map(
        _write, mesh=mesh, axis_names={"tp"},
        in_specs=(pool_spec, pool_spec, P(), P(None, None), P(None),
                  new_spec, new_spec),
        out_specs=(pool_spec, pool_spec),
        check_vma=False,
    )(*args)
