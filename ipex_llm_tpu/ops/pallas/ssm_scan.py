"""The Mamba-2 recurrence token by token, with a head group's state in VMEM
and the state stack read and written in place.

    h_t = exp(dt_t A) h_{t-1} + dt_t x_t (x) B_t;    y_t = C_t . h_t

``ops/ssm.py`` has the mathematics and the XLA form.  This kernel is the
chip's form for BOTH stages of a tick: the one-token decode step over every
row's state (``scan_stack``: the whole ``[L, R, H, P, N]`` stack stays in
HBM, layer ``l`` of it is addressed through a scalar-prefetched index, and
each grid step moves one row's block of heads in, advances it, and moves it
out: the state is read once and written once and nothing else of it is
touched), and the ragged prefill block (``scan_rows``: the rows' states
gathered by the caller, advanced by up to T tokens each, ``lens`` bounding
every row's loop).  Every token is computed by the same operations in the
same order however its prompt was chunked: what the chunked (matmul) form
cannot give.

Where the state is read from and where it is written may be two stacks
(``scan_stack(src, dst, ...)``): the serving tick reads the committed state
and writes the spare buffer, so the state before the tick stays whole for a
rollback and no copy is made.  ``dst`` is aliased to the output; with ``src
is dst`` the update is in place.

Layout inside a grid step (row, block of ``hs`` heads): a head's state is
``[P, N]``, P on sublanes and N on lanes (N a whole number of lane tiles:
128 for Nemotron-H, 256 for Falcon-H1).  A step's heads are bounded by
BYTES (``STEP_BYTES`` of state: :func:`_heads_per_step`), so a step holds
two whole groups, one, or PART of one; its ``B``/``C`` block is the group
or groups its heads belong to.  ``B_t`` and ``C_t`` arrive as
rows ``[1, N]`` and broadcast along sublanes; the decay arrives spread over
a full state row a head (the caller's cheap broadcast); ``dt_t x_t`` arrives
as a row of a ``[T, hs * P]`` block, and is turned into the column ``[P,
1]`` the outer product needs by a masked lane reduction against a shifted
identity (exact: one term and zeros), as ``y_t``'s column is turned back
into a row by the same mask and a sublane reduction.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ipex_llm_tpu.ops.pallas._compat import interpret as _interpret

LANES = 128


# the state a grid step holds, at most: it is in VMEM four times (in and out,
# each double-buffered) beside the step's other operands
STEP_BYTES = 1 << 20


def supported(cfg) -> bool:
    """Shapes the kernel's layout covers: the state's N is a whole number
    of lane tiles, a head's P packs a whole number of times into a lane
    row."""
    p, n = cfg.ssm_head_dim, cfg.ssm_state_size
    hpg = cfg.ssm_num_heads // cfg.ssm_n_groups
    return (n % LANES == 0 and p % 8 == 0 and LANES % p == 0
            and (hpg * p) % LANES == 0)


def _heads_per_step(heads: int, hpg: int, p: int, n: int) -> int:
    """Heads a grid step holds: two groups where the group count is even
    (0.5 MB of state a step at 16 heads of [64, 128]), else one; halved
    while that is over STEP_BYTES (Falcon-H1: a group is 16 heads of [128,
    256], 2 MB, and a step takes half of one), as long as a half is still
    whole groups or a whole part of one, and whole lane rows of ``dt x``."""
    groups = heads // hpg
    hs = hpg * (2 if groups % 2 == 0 else 1)

    def fits(h):
        return ((h % hpg == 0 or hpg % h == 0) and (h * p) % LANES == 0)

    while hs * p * n * 4 > STEP_BYTES and hs % 2 == 0 and fits(hs // 2):
        hs //= 2
    return hs


def _kernel(layer_ref, lens_ref, dtx_ref, dec_ref, b_ref, c_ref, h_in, *rest,
            hs, hpg, p, t_max):
    """Operand rows are (what, token): ``dtx``/``y`` [chunks * T, 128] (a
    chunk is the lane row that packs 128 / P heads), ``dec`` [hs * T, N],
    ``b``/``c`` [gs * T, N] (the step's ``gs`` groups; one where the step
    is part of a group); every load and store is whole lane rows."""
    del layer_ref
    y_ref, h_out = rest[-2], rest[-1]
    row = pl.program_id(0)
    n_tok = jnp.minimum(lens_ref[row], t_max)
    per_lane_row = LANES // p            # heads packed in one lane row
    sub = jax.lax.broadcasted_iota(jnp.int32, (p, LANES), 0)
    lane = jax.lax.broadcasted_iota(jnp.int32, (p, LANES), 1)
    masks = [lane == sub + o * p for o in range(per_lane_row)]
    y_ref[...] = jnp.zeros_like(y_ref)
    for j in range(hs):
        chunk, mask = j // per_lane_row, masks[j % per_lane_row]
        g = j // hpg

        def token(t, h, j=j, chunk=chunk, mask=mask, g=g):
            dtx = dtx_ref[pl.ds(chunk * t_max + t, 1), :]        # [1, 128]
            xcol = jnp.sum(jnp.where(mask, dtx, 0.0), axis=1,
                           keepdims=True)                        # [P, 1]
            dec = dec_ref[pl.ds(j * t_max + t, 1), :]            # [1, N]
            b = b_ref[pl.ds(g * t_max + t, 1), :]
            c = c_ref[pl.ds(g * t_max + t, 1), :]
            h = dec * h + xcol * b                               # [P, N]
            ycol = jnp.sum(h * c, axis=1, keepdims=True)         # [P, 1]
            yrow = jnp.sum(jnp.where(mask, ycol, 0.0), axis=0,
                           keepdims=True)                        # [1, 128]
            y_ref[pl.ds(chunk * t_max + t, 1), :] += yrow
            return h

        h_out[j] = jax.lax.fori_loop(0, n_tok, token, h_in[j])


@functools.partial(jax.jit, static_argnames=("hpg", "two_stacks"))
def _scan(src, dst, layer, lens, dtx, dec, b, c, *, hpg: int,
          two_stacks: bool):
    """src/dst [L, R, H, P, N]; lens [R]; dtx [R, S, chunks*T, 128]; dec [R,
    S, hs*T, N] with S = H / hs steps a row; b/c [R, G / gs, gs*T, N], a
    block the ``gs`` groups of a step (the one group of ``hpg / hs`` steps
    where a step is part of a group)."""
    _, r, heads, p, n = dst.shape
    hs = _heads_per_step(heads, hpg, p, n)
    steps = heads // hs
    t = dec.shape[2] // hs
    steps_a_block = max(1, hpg // hs)

    def blk(shape, steps=1):      # ``steps`` grid steps share one block
        return pl.BlockSpec((None, None) + shape,
                            lambda i, s, lyr, ln: (i, s // steps, 0, 0))

    state = pl.BlockSpec((None, None, hs, p, n),
                         lambda i, s, lyr, ln: (lyr[0], i, s, 0, 0))
    in_specs = [blk(dtx.shape[2:]), blk(dec.shape[2:]),
                blk(b.shape[2:], steps_a_block),
                blk(c.shape[2:], steps_a_block), state]
    args = [dtx, dec, b, c, src]
    if two_stacks:
        # the written stack rides as an operand only to be aliased to the
        # output: it stays in HBM and the kernel never reads it
        in_specs.append(pl.BlockSpec(memory_space=pl.ANY))
        args.append(dst)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2, grid=(r, steps), in_specs=in_specs,
        out_specs=[blk(dtx.shape[2:]), state])
    y, h = pl.pallas_call(
        functools.partial(_kernel, hs=hs, hpg=hpg, p=p, t_max=t),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct(dtx.shape, jnp.float32),
                   jax.ShapeDtypeStruct(dst.shape, dst.dtype)],
        # operands count the two scalar-prefetch arrays
        input_output_aliases={len(args) + 1: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=_interpret(),
        name="ssm_scan",
    )(jnp.reshape(layer, (1,)).astype(jnp.int32), lens.astype(jnp.int32),
      *args)
    return y, h


def _blocked(x, dt, a, b, c, hs: int, hpg: int):
    """The kernel's operand layouts, rows (what, token), from x [B, T, H,
    P], dt [B, T, H], a [H], b/c [B, T, G, N]."""
    bsz, t, heads, p = x.shape
    gs = max(1, hs // hpg)
    chunks = hs * p // LANES

    def rows(v, what):      # [B, T, S * what, W] -> [B, S, what * T, W]
        v = v.reshape(bsz, t, -1, what, v.shape[-1])
        return v.transpose(0, 2, 3, 1, 4).reshape(
            bsz, v.shape[2], what * t, v.shape[-1])

    dtx = rows((dt[..., None] * x).reshape(bsz, t, -1, LANES), chunks)
    dec = rows(jnp.broadcast_to(jnp.exp(dt * a)[..., None],
                                (bsz, t, heads, b.shape[-1])), hs)
    return dtx, dec, rows(b, gs), rows(c, gs)


def _unblocked(y, heads: int, p: int):
    """y [B, S, chunks * T, 128] -> [B, T, H, P]."""
    bsz, steps = y.shape[:2]
    chunks = heads * p // LANES // steps
    y = y.reshape(bsz, steps, chunks, -1, LANES).transpose(0, 3, 1, 2, 4)
    return y.reshape(bsz, y.shape[1], heads, p)


def scan_stack(src, dst, layer, x, dt, a, b, c, lens):
    """Advance EVERY row's state of layer ``layer``: read from ``src``,
    written to ``dst`` (both [L, R, H, P, N]; the same array for an update
    in place).  x [R, T, H, P]; dt [R, T, H] (0 at padding); a [H]; b/c
    [R, T, G, N]; lens [R] tokens a row.  Returns (y [R, T, H, P], the
    written stack)."""
    heads, p = x.shape[2], x.shape[3]
    hpg = heads // b.shape[2]
    hs = _heads_per_step(heads, hpg, p, b.shape[3])
    y, h = _scan(src, dst, layer, lens, *_blocked(x, dt, a, b, c, hs, hpg),
                 hpg=hpg, two_stacks=src is not dst)
    return _unblocked(y, heads, p), h


def scan_rows(h0, x, dt, a, b, c, lens):
    """Advance the gathered states ``h0`` [B, H, P, N] (a prefill block's
    rows) by up to T tokens each.  Returns (y [B, T, H, P], h [B, H, P,
    N])."""
    h1 = h0[None]           # one layer's stack, advanced in place
    y, h = scan_stack(h1, h1, jnp.zeros((), jnp.int32), x, dt, a, b, c, lens)
    return y, h[0]
