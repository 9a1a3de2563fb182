"""Decode-step (T=1) fused attention kernel.

The reference serves decode attention with ``xe_addons.sdp`` and its fp8
variants ``sdp_fp8*`` (models/common.py:273-286); the repo's r2 gap
(VERDICT weak#4) was that T=1 steps ran the jnp reference path: fp32
``[B,H,1,S_max]`` scores over the whole static capacity, plus — on the fp8
cache — a full bf16 materialization of every layer's K/V before attention.

This kernel is built for the decode hot loop:

- K/V stream from HBM **in the cache's native head-major ``[B, Hkv, S, D]``
  layout and storage dtype** — no XLA-level transpose or cast of the cache
  ever materializes, and each grid step's ``[S_block, D]`` tile is a
  contiguous per-head stream (Mosaic's last-two-dims tile requirement).
  fp8(e5m2) tiles are widened to bf16 *inside* the kernel, so fp8 KV
  actually halves HBM traffic (the reason the format exists).
- Grid ``(B, Hkv, S_blocks)``: one q-head group (the GQA group of
  ``Hq/Hkv`` heads) per kv head, flash-style online softmax over KV tiles
  held in VMEM scratch.
- Tiles fully outside ``[kv_start, kv_len)`` skip their compute via
  ``pl.when`` (their DMA still runs — grid shapes are static; capacity
  bucketing in generation.py keeps dead slack ≤ one DECODE_BLOCK).

Masking semantics match ``ops.attention.sdpa_reference`` for a T=1 query at
absolute position ``kv_len - 1``: slots ``[kv_start, kv_len)`` are valid,
sliding window (traced enable flag) and softcap as in the prefill kernel.
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


def _kernel(len_ref, start_ref, won_ref, q_ref, k_ref, v_ref, o_ref,
            m_ref, l_ref, acc_ref, *, scale, window, softcap, bs_kv,
            compute_dtype):
    b = pl.program_id(0)
    si = pl.program_id(2)

    @pl.when(si == 0)
    def _():
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    kv_len = len_ref[b]
    kv_start = start_ref[b]
    # tile intersects the valid slot range [kv_start, kv_len)?
    lo = si * bs_kv
    tile_live = (lo < kv_len) & (lo + bs_kv > kv_start)

    @pl.when(tile_live)
    def _():
        q = q_ref[0, 0].astype(jnp.float32)            # [G, D]
        k = k_ref[0, 0].astype(compute_dtype).astype(jnp.float32)
        s = jax.lax.dot_general(                        # [G, BS]
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale
        if softcap is not None:
            s = jnp.tanh(s / softcap) * softcap

        g = s.shape[0]
        kpos = lo + jax.lax.broadcasted_iota(jnp.int32, (g, bs_kv), 1)
        mask = (kpos < kv_len) & (kpos >= kv_start)
        if window is not None:
            in_window = kpos > (kv_len - 1) - window
            mask &= in_window | (won_ref[0] == 0)
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_ref[:]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        m_safe = jnp.maximum(m_new, -1e29)
        p = jnp.exp(s - m_safe)
        alpha = jnp.exp(jnp.maximum(m_prev, -1e29) - m_safe)
        v = v_ref[0, 0].astype(compute_dtype)           # [BS, Dv]
        l_ref[:] = l_ref[:] * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[:] = acc_ref[:] * alpha + jax.lax.dot_general(
            p.astype(compute_dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        m_ref[:] = m_new

    @pl.when(si == pl.num_programs(2) - 1)
    def _():
        denom = jnp.where(l_ref[:] == 0.0, 1.0, l_ref[:])
        o_ref[0, 0] = (acc_ref[:] / denom).astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("scale", "window", "softcap", "out_dtype"),
)
def _decode(q, k, v, kv_len, kv_start, won, *, scale, window, softcap,
            out_dtype):
    """q [B, Hkv, G, D]; k [B, Hkv, S, D]; v [B, Hkv, S, Dv] (storage
    dtype, possibly fp8); kv_len/kv_start/won [B] int32."""
    b, hkv, g, d = q.shape
    s, dv = k.shape[2], v.shape[3]

    g_pad = _round_up(g, 8)
    d_pad = _round_up(d, 128)
    dv_pad = _round_up(dv, 128)
    bs_kv = min(512, _round_up(s, 128))
    sp = _round_up(s, bs_kv)
    if (g_pad, d_pad) != (g, d):
        q = jnp.pad(q, ((0, 0), (0, 0), (0, g_pad - g), (0, d_pad - d)))
    if (sp, d_pad) != (s, d):
        k = jnp.pad(k, ((0, 0), (0, 0), (0, sp - s), (0, d_pad - d)))
    if (sp, dv_pad) != (s, dv):
        v = jnp.pad(v, ((0, 0), (0, 0), (0, sp - s), (0, dv_pad - dv)))

    grid = (b, hkv, sp // bs_kv)
    out = pl.pallas_call(
        functools.partial(
            _kernel, scale=scale, window=window, softcap=softcap,
            bs_kv=bs_kv, compute_dtype=jnp.bfloat16,
        ),
        grid=grid,
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),   # kv_len [B]
            pl.BlockSpec(memory_space=pltpu.SMEM),   # kv_start [B]
            pl.BlockSpec(memory_space=pltpu.SMEM),   # window enable [B]
            pl.BlockSpec((1, 1, g_pad, d_pad), lambda bi, hi, si: (bi, hi, 0, 0)),
            pl.BlockSpec((1, 1, bs_kv, d_pad), lambda bi, hi, si: (bi, hi, si, 0)),
            pl.BlockSpec((1, 1, bs_kv, dv_pad), lambda bi, hi, si: (bi, hi, si, 0)),
        ],
        out_specs=pl.BlockSpec(
            (1, 1, g_pad, dv_pad), lambda bi, hi, si: (bi, hi, 0, 0)
        ),
        out_shape=jax.ShapeDtypeStruct((b, hkv, g_pad, dv_pad), out_dtype),
        scratch_shapes=[
            pltpu.VMEM((g_pad, 1), jnp.float32),
            pltpu.VMEM((g_pad, 1), jnp.float32),
            pltpu.VMEM((g_pad, dv_pad), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        cost_estimate=pl.CostEstimate(
            flops=4 * b * hkv * g_pad * sp * d_pad,
            bytes_accessed=(
                b * sp * hkv * (d_pad + dv_pad) * k.dtype.itemsize
                + b * hkv * g_pad * d_pad * 2
            ),
            transcendentals=b * hkv * g_pad * sp,
        ),
        interpret=_interpret(),
        name="decode_attention",
    )(kv_len.astype(jnp.int32), kv_start.astype(jnp.int32),
      won.astype(jnp.int32), q, k, v)
    return out[:, :, :g, :dv]


def decode_sdpa(
    q: jnp.ndarray,            # [B, 1, Hq, D]
    k_raw: jnp.ndarray,        # [B, Hkv, S, D] cache storage layout/dtype
    v_raw: jnp.ndarray,        # [B, Hkv, S, Dv]
    *,
    scale: float | None = None,
    kv_len: jnp.ndarray | None = None,
    kv_start: jnp.ndarray | None = None,
    window: int | None = None,
    window_on: jnp.ndarray | bool = True,
    softcap: float | None = None,
) -> jnp.ndarray:
    """T=1 attention over the raw (possibly fp8) head-major KV cache.

    Returns [B, 1, Hq, Dv] in q.dtype.  The query is assumed to sit at
    absolute position ``kv_len - 1`` (the decode-loop invariant), which
    subsumes the causal mask.
    """
    b, t, hq, d = q.shape
    assert t == 1, "decode kernel is specialized for single-token steps"
    hkv, s, dv = k_raw.shape[1], k_raw.shape[2], v_raw.shape[3]
    if hq % hkv:
        raise NotImplementedError("Hq must be a multiple of Hkv")
    g = hq // hkv
    if scale is None:
        scale = d ** -0.5
    if kv_len is None:
        kv_len = jnp.full((b,), s, jnp.int32)
    if kv_start is None:
        kv_start = jnp.zeros((b,), jnp.int32)
    won = jnp.broadcast_to(jnp.asarray(window_on, jnp.int32), (b,))

    # [B, 1, Hq, D] -> [B, Hkv, G, D]: head h of kv-group kvh is q-head
    # kvh*G + h, matching sdpa_reference's _repeat_kv expansion order
    qg = q[:, 0].reshape(b, hkv, g, d)
    out = _decode(
        qg, k_raw, v_raw, kv_len, kv_start, won,
        scale=float(scale),
        window=None if window is None else int(window),
        softcap=None if softcap is None else float(softcap),
        out_dtype=q.dtype,
    )
    return out.reshape(b, 1, hq, dv)


def decode_sdpa_sharded(q, k_raw, v_raw, mesh, **kwargs):
    """Tensor-parallel decode attention: heads are sharded over ``tp``
    (cache_sharding in parallel/shard.py), so the kernel runs per-shard
    under ``jax.shard_map`` with only ``tp`` manual — no collective needed
    (attention is head-local; the following o-proj row-psum combines)."""
    from jax.sharding import PartitionSpec as P

    tp = mesh.shape["tp"]
    hq, hkv = q.shape[2], k_raw.shape[1]
    if not heads_shardable(hq, hkv, tp):
        raise NotImplementedError(
            f"unsupported head/tp factorization: {hq} q / {hkv} kv heads "
            f"over tp={tp}")
    if hkv % tp:
        # GQA with fewer kv heads than chips (70B north-star: 8 kv heads,
        # tp=16): repeat kv heads up to tp — repeat-of-replicated feeding a
        # head-sharded consumer lowers to a local per-shard slice, so each
        # chip reads only the kv head its q-head group attends to
        rep = tp // hkv
        k_raw = jnp.repeat(k_raw, rep, axis=1)
        v_raw = jnp.repeat(v_raw, rep, axis=1)

    def run(ql, kl, vl):
        return decode_sdpa(ql, kl, vl, **kwargs)

    q_spec = P(None, None, "tp", None)
    kv_spec = P(None, "tp", None, None)
    return jax.shard_map(
        run, mesh=mesh, axis_names={"tp"},
        in_specs=(q_spec, kv_spec, kv_spec), out_specs=q_spec,
        check_vma=False,
    )(q, k_raw, v_raw)
