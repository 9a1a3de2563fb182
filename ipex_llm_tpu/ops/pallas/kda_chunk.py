"""The chunkwise delta rule of Kimi Delta Attention as a kernel: the chip's
form of a prefill block (``ops/kda.py`` has the mathematics; the body here IS
``kda.chunk_block``, the function the XLA form runs under ``vmap``).

Grid (row, head, block of ``kda.CHUNK`` tokens), the blocks of one (row,
head) in turn: the head's state ``[D, D]`` float32 is loaded when its first
block starts, handed from block to block in VMEM, and written when its last
one ends, so the state crosses HBM once each way a call whatever the chunk's
length; each step reads one ``[CHUNK, D]`` tile of ``q``, ``k``, ``b k``,
``v`` and the log-decay and writes one of ``o``.  Operands are HEAD-MAJOR
``[B, H, T, D]`` (the caller's transpose): a tile's last two dimensions are
then whole (8, 128) tiles.  Everything is float32 and every product is taken
at precision "highest": the solve ``(I + L)^-1`` amplifies what its operands
lose.

A block's arithmetic sees its own tile and the state before it and nothing
else, and the grid visits a row's blocks in order whatever the other rows
are: a token's bits depend on its block alone (``ops/kda.py``, the prompt
grid).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ipex_llm_tpu.ops.pallas._compat import interpret as _interpret


def supported(d: int) -> bool:
    """The layout covers a head whose dims are whole lane tiles."""
    return d % 128 == 0


def _kernel(s0_ref, q_ref, k_ref, kb_ref, v_ref, g_ref, o_ref, s1_ref, s_acc):
    from ipex_llm_tpu.ops import kda

    blk = pl.program_id(2)

    @pl.when(blk == 0)
    def _():
        s_acc[...] = s0_ref[...]

    o, s1 = kda.chunk_block(s_acc[...], q_ref[...], k_ref[...], kb_ref[...],
                            v_ref[...], g_ref[...])
    o_ref[...] = o
    s_acc[...] = s1

    @pl.when(blk == pl.num_programs(2) - 1)
    def _():
        s1_ref[...] = s1


@functools.partial(jax.jit, static_argnames=("chunk",))
def _call(s0, q, k, kb, v, g, *, chunk: int):
    b, h, t, d = q.shape
    tile = pl.BlockSpec((None, None, chunk, d), lambda i, j, n: (i, j, n, 0))
    state = pl.BlockSpec((None, None, d, d), lambda i, j, n: (i, j, 0, 0))
    n_blk = t // chunk
    flops = b * h * n_blk * block_flops(chunk, d)
    return pl.pallas_call(
        _kernel,
        grid=(b, h, n_blk),
        in_specs=[state] + [tile] * 5,
        out_specs=[tile, state],
        out_shape=[jax.ShapeDtypeStruct(q.shape, jnp.float32),
                   jax.ShapeDtypeStruct(s0.shape, jnp.float32)],
        scratch_shapes=[pltpu.VMEM((d, d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        cost_estimate=pl.CostEstimate(
            flops=flops, transcendentals=b * h * t * d * 8,
            bytes_accessed=4 * (6 * b * h * t * d + 2 * b * h * d * d)),
        interpret=_interpret(),
        name="kda_chunk",
    )(s0, q, k, kb, v, g)


def block_flops(c: int, d: int) -> int:
    """Multiply-adds x 2 of one (head, block) of :func:`kda.chunk_block`:
    the cumulative decay, the state's two products, the pairwise rows, the
    solve's off-diagonal products, the output and the state's update (the
    diagonal blocks' inverses are a few percent and left out)."""
    from ipex_llm_tpu.ops.kda import SUB

    n_sub = c // SUB
    return 2 * (c * c * d                # G = tril @ g
                + 2 * c * d * d          # [K exp(G); Q exp(G)] @ S0
                + n_sub * 2 * SUB * d * c  # A and P, a sub-block of rows
                + c * c * d // 2         # forward substitution
                + c * c * d              # P @ U
                + c * d * d)             # (kb decay)^T @ U


def chunk_rows(s0, q, k, kb, v, g):
    """``s0`` [B, H, D, D]; the others [B, H, T, D] float32, T a whole number
    of blocks.  Returns (o [B, H, T, D], s1 [B, H, D, D])."""
    from ipex_llm_tpu.ops.kda import CHUNK

    f32 = jnp.float32
    return _call(s0.astype(f32), *(x.astype(f32) for x in (q, k, kb, v, g)),
                 chunk=CHUNK)
