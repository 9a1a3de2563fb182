"""Flash (tiled online-softmax) SDPA kernel.

The ``xe_addons.sdp / sdp_causal`` equivalent (reference models/common.py:
219-306, §2.3), built the TPU way: one grid step per (batch·head, Q tile,
KV tile), running softmax statistics (max, denominator) held in VMEM scratch
across the KV-tile sweep, so the [T, S] score matrix never exists in HBM.

Masking semantics match ``ops.attention.sdpa_reference`` exactly (the test
oracle): static-capacity KV buffer with validity from integer ``kv_len`` /
``kv_start`` per row, causal against absolute ``q_positions``, optional
sliding window with a *traced* per-layer enable flag (gemma2 alternation
enters the kernel as data, not Python control flow), and Gemma-style logit
softcapping.

GQA never materializes repeated K/V: the kv-head for each q-head is picked by
the BlockSpec index map, so K/V tiles stream from HBM once per kv-head.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ipex_llm_tpu.ops.pallas._compat import (
    NEG_INF,
    interpret as _interpret,
    round_up as _round_up,
)


def _kernel(qpos_ref, kvlen_ref, kvstart_ref, won_ref, q_ref, k_ref, v_ref,
            o_ref, m_ref, l_ref, acc_ref, *,
            scale, causal, window, softcap, bs_kv):
    si = pl.program_id(2)

    @pl.when(si == 0)
    def _():
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    q = q_ref[0].astype(jnp.float32)          # [BT, D]
    k = k_ref[0].astype(jnp.float32)          # [BS, D]
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    ) * scale                                  # [BT, BS]
    if softcap is not None:
        s = jnp.tanh(s / softcap) * softcap

    bt = s.shape[0]
    kpos = si * bs_kv + jax.lax.broadcasted_iota(jnp.int32, (bt, bs_kv), 1)
    mask = (kpos < kvlen_ref[0, 0]) & (kpos >= kvstart_ref[0, 0])
    if causal:
        qpos = qpos_ref[0]                     # [BT, 1]
        mask &= kpos <= qpos
        if window is not None:
            in_window = kpos > qpos - window
            mask &= in_window | (won_ref[0, 0] == 0)
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_ref[:]                          # [BT, 1]
    m_cur = jnp.max(s, axis=-1, keepdims=True)
    m_new = jnp.maximum(m_prev, m_cur)
    # keep the running max finite so fully-masked tiles contribute exp(-big)=0
    # without producing NaN via exp(NEG_INF - NEG_INF)
    m_safe = jnp.maximum(m_new, -1e29)
    p = jnp.exp(s - m_safe)                    # [BT, BS]
    alpha = jnp.exp(jnp.maximum(m_prev, -1e29) - m_safe)
    l_ref[:] = l_ref[:] * alpha + jnp.sum(p, axis=-1, keepdims=True)
    acc_ref[:] = acc_ref[:] * alpha + jax.lax.dot_general(
        p.astype(v_ref.dtype), v_ref[0], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    m_ref[:] = m_new

    @pl.when(si == pl.num_programs(2) - 1)
    def _():
        denom = jnp.where(l_ref[:] == 0.0, 1.0, l_ref[:])
        o_ref[0] = (acc_ref[:] / denom).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("scale", "causal", "window", "softcap", "n_rep"),
)
def _flash(q, k, v, qpos, kv_len, kv_start, won, *,
           scale, causal, window, softcap, n_rep):
    """q [BH, T, D]; k/v [BKV, S, D]; qpos [B, T]; kv_len/kv_start [B];
    won [B] int32 (per-call window enable, broadcast of the traced flag)."""
    bh, t, d = q.shape
    bkv, s, dv = k.shape[0], k.shape[1], v.shape[2]
    b = qpos.shape[0]
    h = bh // b
    hkv = bkv // b

    bt = min(256, _round_up(t, 16))
    bs_kv = min(512, _round_up(s, 128))
    d_pad = _round_up(d, 128)
    dv_pad = _round_up(dv, 128)
    tp, sp = _round_up(t, bt), _round_up(s, bs_kv)
    if (tp, d_pad) != (t, d):
        q = jnp.pad(q, ((0, 0), (0, tp - t), (0, d_pad - d)))
    if (sp, d_pad) != (s, d):
        k = jnp.pad(k, ((0, 0), (0, sp - s), (0, d_pad - d)))
    if (sp, dv_pad) != (s, dv):
        v = jnp.pad(v, ((0, 0), (0, sp - s), (0, dv_pad - dv)))
    if tp != t:
        # padded q rows attend to slot 0 only; sliced off below either way
        qpos = jnp.pad(qpos, ((0, 0), (0, tp - t)))
    qpos = qpos.astype(jnp.int32)[:, :, None]   # [B, T, 1] column layout

    grid = (bh, tp // bt, sp // bs_kv)

    def b_of(bhi):
        return bhi // h

    def kv_of(bhi):
        return (bhi // h) * hkv + (bhi % h) // n_rep

    out = pl.pallas_call(
        functools.partial(
            _kernel, scale=scale, causal=causal, window=window,
            softcap=softcap, bs_kv=bs_kv,
        ),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bt, 1), lambda bhi, ti, si: (b_of(bhi), ti, 0)),
            pl.BlockSpec((1, 1), lambda bhi, ti, si: (b_of(bhi), 0),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec((1, 1), lambda bhi, ti, si: (b_of(bhi), 0),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec((1, 1), lambda bhi, ti, si: (b_of(bhi), 0),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec((1, bt, d_pad), lambda bhi, ti, si: (bhi, ti, 0)),
            pl.BlockSpec((1, bs_kv, d_pad), lambda bhi, ti, si: (kv_of(bhi), si, 0)),
            pl.BlockSpec((1, bs_kv, dv_pad), lambda bhi, ti, si: (kv_of(bhi), si, 0)),
        ],
        out_specs=pl.BlockSpec((1, bt, dv_pad), lambda bhi, ti, si: (bhi, ti, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, tp, dv_pad), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bt, 1), jnp.float32),
            pltpu.VMEM((bt, 1), jnp.float32),
            pltpu.VMEM((bt, dv_pad), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        cost_estimate=pl.CostEstimate(
            flops=4 * bh * tp * sp * d_pad,
            bytes_accessed=2 * (bh * tp * d_pad + 2 * bkv * sp * d_pad),
            transcendentals=bh * tp * sp,
        ),
        interpret=_interpret(),
        name="flash_attention",
    )(qpos, kv_len.reshape(-1, 1).astype(jnp.int32),
      kv_start.reshape(-1, 1).astype(jnp.int32),
      won.reshape(-1, 1).astype(jnp.int32), q, k, v)
    return out[:, :t, :dv]


def flash_sdpa(
    q: jnp.ndarray,          # [B, T, Hq, D]
    k: jnp.ndarray,          # [B, S, Hkv, D]
    v: jnp.ndarray,          # [B, S, Hkv, Dv]
    *,
    scale: float | None = None,
    causal: bool = True,
    q_positions: jnp.ndarray | None = None,
    kv_len: jnp.ndarray | None = None,
    kv_start: jnp.ndarray | None = None,
    window: int | None = None,
    window_on: jnp.ndarray | bool = True,
    softcap: float | None = None,
    bias: jnp.ndarray | None = None,
) -> jnp.ndarray:
    """Same contract as ``ops.attention.sdpa_reference``; returns
    [B, T, Hq, Dv] in q.dtype."""
    if bias is not None:
        raise NotImplementedError("bias not supported by the flash kernel")
    b, t, hq, d = q.shape
    s, hkv, dv = k.shape[1], k.shape[2], v.shape[3]
    n_rep = hq // hkv
    if scale is None:
        scale = d ** -0.5

    if q_positions is None:
        q_positions = jnp.broadcast_to(jnp.arange(t, dtype=jnp.int32)[None], (b, t))
    if kv_len is None:
        kv_len = jnp.full((b,), s, jnp.int32)
    if kv_start is None:
        kv_start = jnp.zeros((b,), jnp.int32)
    won = jnp.broadcast_to(
        jnp.asarray(window_on, jnp.int32).astype(jnp.int32), (b,)
    )

    qt = q.transpose(0, 2, 1, 3).reshape(b * hq, t, d)
    kt = k.transpose(0, 2, 1, 3).reshape(b * hkv, s, d)
    vt = v.transpose(0, 2, 1, 3).reshape(b * hkv, s, dv)
    out = _flash(
        qt, kt, vt, q_positions, kv_len, kv_start, won,
        scale=float(scale), causal=causal,
        window=None if window is None else int(window),
        softcap=None if softcap is None else float(softcap),
        n_rep=n_rep,
    )
    return out.reshape(b, hq, t, dv).transpose(0, 2, 1, 3)


def flash_sdpa_sharded(q, k, v, mesh, *, q_positions=None, kv_len=None,
                       kv_start=None, window_on=True, **static_kwargs):
    """Tensor-parallel flash SDPA: heads sharded over ``tp``, kernel runs
    per-shard under ``jax.shard_map`` (attention is head-local, so no
    collective; only ``tp`` is manual, dp/pp/cp stay under GSPMD)."""
    from jax.sharding import PartitionSpec as P

    b, t, hq, d = q.shape
    s, hkv = k.shape[1], k.shape[2]
    tp = mesh.shape["tp"]
    if hq % tp or hkv % tp or (hq // tp) % (hkv // tp):
        raise NotImplementedError("head counts must divide tp")
    if q_positions is None:
        q_positions = jnp.broadcast_to(
            jnp.arange(t, dtype=jnp.int32)[None], (b, t)
        )
    if kv_len is None:
        kv_len = jnp.full((b,), s, jnp.int32)
    if kv_start is None:
        kv_start = jnp.zeros((b,), jnp.int32)
    won = jnp.broadcast_to(jnp.asarray(window_on, jnp.int32), (b,))

    def run(ql, kl, vl, qpos, klen, kstart, wl):
        return flash_sdpa(
            ql, kl, vl, q_positions=qpos, kv_len=klen, kv_start=kstart,
            window_on=wl, **static_kwargs,
        )

    hspec = P(None, None, "tp", None)
    rep2, rep1 = P(None, None), P(None)
    return jax.shard_map(
        run, mesh=mesh, axis_names={"tp"},
        in_specs=(hspec, hspec, hspec, rep2, rep1, rep1, rep1),
        out_specs=hspec, check_vma=False,
    )(q, k, v, q_positions.astype(jnp.int32), kv_len, kv_start, won)
