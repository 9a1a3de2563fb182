"""Sparse Mixture-of-Experts dispatch.

Reference counterparts: ``xe_linear.moe_forward_vec`` + ``moe_group_topk``
(reference deepseek.py:293-322, models/common.py:342-375) and the FlashMoE
CPU-offload runtime (docs/mddocs/Quickstart/flashmoe_quickstart.md).  The r2
decoder computed EVERY expert on EVERY token (dense-compute MoE) — correct
but E/k× wasted FLOPs and full-expert HBM traffic each step.

TPU-native sparse design, all shapes static (SURVEY.md §7 hard part (b)):

- **gather mode** (decode / tiny batches): for each (token, top-k) pair,
  gather just that expert's packed weight planes from the stacked expert
  QTensor with a dynamic index — XLA lowers to an HBM gather that reads
  only the addressed experts, so decode weight traffic drops from E experts
  to ≤ N·k (4× for Mixtral's E=8,k=2 at batch 1).
- **capacity mode** (prefill / training): sort the (token, expert) pairs by
  expert, scatter into a ``[E, C, H]`` bucket tensor (capacity
  ``C = min(N, ceil(N·k/E · cf))``), run ONE vmapped expert computation
  over the expert axis (a batched matmul GSPMD shards over ``ep`` with no
  sequential scan), and scatter-add the weighted results back.  Tokens
  beyond an expert's capacity are dropped (standard capacity-factor
  semantics; cf defaults to 2.0 ⇒ drops only under >2× imbalance).

The dense all-experts scan remains in models/decoder.py as the oracle and
the fallback for odd configs (IPEX_LLM_TPU_DENSE_MOE=1).
"""

from __future__ import annotations

import os
from functools import partial

import jax
import jax.numpy as jnp

from ipex_llm_tpu.ops import linear as linear_ops
from ipex_llm_tpu.ops import mlp as mlp_ops

# pairs at or below this run gather mode (decode-shaped batches)
GATHER_PAIR_LIMIT = 32


def capacity_factor() -> float:
    return float(os.environ.get("IPEX_LLM_TPU_MOE_CF", "2.0"))


def use_sparse() -> bool:
    return os.environ.get("IPEX_LLM_TPU_DENSE_MOE", "0") != "1"


def _round_up(n: int, m: int) -> int:
    return ((n + m - 1) // m) * m


def _take_expert(qt_or_arr, idx):
    """Index the leading expert axis of a stacked weight (QTensor-aware)."""
    return jax.tree_util.tree_map(lambda x: x[idx], qt_or_arr)


def _expert_ffn(x, gate_up, down, act, gated=True, ub=None, db=None):
    """x [..., H] through one expert's FFN (dequant fused by XLA): gated
    SwiGLU (mixtral-style) or fc1 -> act -> fc2 with biases (phixtral,
    reference phixtral.py:phixtral_mlp_forward)."""
    inner = linear_ops.linear(x, gate_up)
    if ub is not None:
        inner = inner + ub.astype(inner.dtype)
    if gated:
        gate, up = mlp_ops.split_gate_up(inner)
        y = mlp_ops.gated_act_mul(gate, up, act)
    else:
        y = mlp_ops.act(inner, act)
    out = linear_ops.linear(y, down)
    if db is not None:
        out = out + db.astype(out.dtype)
    return out


def moe_gather(h, w, idx, gate_up, down, act, gated=True,
               up_bias=None, down_bias=None):
    """Per-pair expert gather: h [B,T,H], w/idx [B,T,k].

    Weight traffic ∝ number of pairs, not E — the decode-path win.
    """
    b, t, hidden = h.shape
    k = idx.shape[-1]
    n = b * t
    hf = h.reshape(n, hidden)
    idx_f = idx.reshape(n * k)
    w_f = w.reshape(n * k)
    tok_f = jnp.repeat(jnp.arange(n), k)

    # None bias leaves vanish from the pytree, so ONE vmap serves both
    ew = {"gu": gate_up, "dn": down, "ub": up_bias, "db": down_bias}
    pair_w = _take_expert(ew, idx_f)           # [P, ...] packed planes
    xi = hf[tok_f]                             # [P, H]

    y = jax.vmap(
        lambda x_, pw: _expert_ffn(x_[None], pw["gu"], pw["dn"], act,
                                   gated, pw.get("ub"), pw.get("db"))[0]
    )(xi, pair_w)                              # [P, H]
    y = y * w_f[:, None].astype(y.dtype)
    out = jnp.zeros((n, hidden), y.dtype).at[tok_f].add(y)
    return out.reshape(b, t, hidden)


def _dequant_stack(qt_or_arr):
    """Stacked expert weight [E, ...] -> dense [E, K, N] bf16."""
    from ipex_llm_tpu.quantize import core as qcore
    from ipex_llm_tpu.quantize.core import QTensor

    if isinstance(qt_or_arr, QTensor):
        return jax.vmap(qcore.dequantize)(qt_or_arr).astype(jnp.bfloat16)
    return qt_or_arr.astype(jnp.bfloat16)


def moe_ragged(h, w, idx, gate_up, down, act, n_experts: int, gated=True,
               up_bias=None, down_bias=None):
    """Exact sorted dispatch via ``lax.ragged_dot`` (MXU group-gemm).

    Tokens sort by expert and run ONE ragged matmul per projection over
    the expert-major dense weight stack — exact results (no capacity
    drops), FLOPs proportional to routed pairs, one pass of expert
    weight traffic (the same traffic dense-all-experts pays, E/k fewer
    FLOPs).  This is the single-mesh prefill path; the capacity-bucketed
    form below remains for ``ep``-sharded meshes where the expert axis
    is partitioned.
    """
    b, t, hidden = h.shape
    k = idx.shape[-1]
    n = b * t
    hf = h.reshape(n, hidden).astype(jnp.bfloat16)
    e_f = idx.reshape(n * k)
    w_f = w.reshape(n * k)
    tok_f = jnp.repeat(jnp.arange(n), k)

    order = jnp.argsort(e_f)
    tok_s = tok_f[order]
    w_s = w_f[order]
    counts = jnp.bincount(e_f, length=n_experts)

    e_s = e_f[order]
    x = hf[tok_s]                                   # [P, H]
    gu = _dequant_stack(gate_up)                    # [E, H, 2I]
    inner = jax.lax.ragged_dot(
        x, gu, counts, preferred_element_type=jnp.float32
    )
    if up_bias is not None:
        inner = inner + up_bias[e_s].astype(inner.dtype)
    if gated:
        gate, up = mlp_ops.split_gate_up(inner)
        act_x = mlp_ops.gated_act_mul(gate, up, act).astype(jnp.bfloat16)
    else:
        act_x = mlp_ops.act(inner, act).astype(jnp.bfloat16)
    dn = _dequant_stack(down)                       # [E, I, H]
    y = jax.lax.ragged_dot(
        act_x, dn, counts, preferred_element_type=jnp.float32
    )
    if down_bias is not None:
        y = y + down_bias[e_s].astype(y.dtype)
    y = y * w_s[:, None].astype(y.dtype)
    out = jnp.zeros((n, hidden), y.dtype).at[tok_s].add(y)
    return out.reshape(b, t, hidden).astype(h.dtype)


def moe_capacity(h, w, idx, gate_up, down, act, n_experts: int,
                 cf: float | None = None, gated=True,
                 up_bias=None, down_bias=None):
    """Capacity-bucketed sort dispatch: h [B,T,H], w/idx [B,T,k]."""
    b, t, hidden = h.shape
    k = idx.shape[-1]
    n = b * t
    cf = capacity_factor() if cf is None else cf
    cap = min(n, _round_up(max(int(n * k / n_experts * cf), 1), 8))

    hf = h.reshape(n, hidden)
    e_f = idx.reshape(n * k)
    w_f = w.reshape(n * k)
    tok_f = jnp.repeat(jnp.arange(n), k)

    order = jnp.argsort(e_f)                   # stable: token order per expert
    e_sorted = e_f[order]
    tok_sorted = tok_f[order]
    w_sorted = w_f[order]
    counts = jnp.bincount(e_f, length=n_experts)
    starts = jnp.cumsum(counts) - counts       # exclusive prefix
    pos_in_e = jnp.arange(n * k) - starts[e_sorted]
    keep = pos_in_e < cap
    # dropped pairs land in a scratch row past the real buckets
    slot = jnp.where(keep, e_sorted * cap + pos_in_e, n_experts * cap)

    x_buckets = jnp.zeros((n_experts * cap + 1, hidden), hf.dtype)
    x_buckets = x_buckets.at[slot].set(hf[tok_sorted])
    x_buckets = x_buckets[:-1].reshape(n_experts, cap, hidden)

    ew = {"gu": gate_up, "dn": down, "ub": up_bias, "db": down_bias}
    y = jax.vmap(
        lambda xe, ew_: _expert_ffn(xe, ew_["gu"], ew_["dn"], act,
                                    gated, ew_.get("ub"), ew_.get("db"))
    )(x_buckets, ew)                           # [E, C, H]

    y_pairs = y.reshape(n_experts * cap, hidden)[
        jnp.clip(slot, 0, n_experts * cap - 1)
    ]
    y_pairs = jnp.where(keep[:, None], y_pairs, 0.0)
    y_pairs = y_pairs * w_sorted[:, None].astype(y_pairs.dtype)
    out = jnp.zeros((n, hidden), y_pairs.dtype).at[tok_sorted].add(y_pairs)
    return out.reshape(b, t, hidden)


def _grouped_kernel_covers(stack) -> bool:
    """Whether the chip's grouped dequant-matmul serves this expert stack
    (ops/pallas/qmatmul.py ``qmatmul_pallas_grouped``)."""
    from ipex_llm_tpu.ops import dispatch
    from ipex_llm_tpu.quantize.core import QTensor

    if not (isinstance(stack, QTensor) and dispatch.use_pallas()):
        return False
    from ipex_llm_tpu.ops.pallas import qmatmul as pallas_qmatmul

    return pallas_qmatmul.grouped_supported(stack)


def _expert_act(inner, act, gated, limit=None):
    if gated:
        return mlp_ops.gated_act_mul(*mlp_ops.split_gate_up(inner), act,
                                     limit)
    return mlp_ops.act(inner, act)


def _weighed(g, y):
    """``g * y`` (g float32, y bf16) rounded to float32 ONCE, whatever the
    compiler does with the sum it then joins: a backend that contracts a
    product and the add after it into one fused multiply-add (XLA's CPU
    does, inside a loop body and not beside it) would skip the product's
    rounding in one form of ``moe_held`` and not in the other.  The gate's
    top 8 significant bits and its rest each times a bf16 value are EXACT
    in float32, and their sum rounds once, to ``round(g * y)``."""
    bits = jax.lax.bitcast_convert_type(g, jnp.uint32) & jnp.uint32(0xFFFF0000)
    hi = jax.lax.bitcast_convert_type(bits, jnp.float32)
    y = y.astype(jnp.float32)
    return hi * y + (g - hi) * y


def row_tile(n: int, top_k: int, n_routed: int) -> int:
    """The row tile of a block of ``n`` tokens whose rows are grouped by
    expert: the rows an expert expects (``top_k`` of ``n_routed`` experts a
    token) plus two standard deviations of a count that size, in whole bf16
    sublane tiles (16), at most the MXU's 128.  A tile is one pass over an
    expert's planes whatever it holds: an expert that outgrows its tile
    pays a second pass (a tile of the MEAN would make four experts in ten
    pay it), an emptier tile only its rows' share of the matmul (on a v5e
    a 512-token chunk of A.X-K1's or GLM-5.2's reads 1.25 / 1.33 ms a layer
    at 32 rows, 1.90 / 1.71 at 16, 1.55 / 1.67 at 128: PERF.md, PR 41)."""
    mean = n * top_k / n_routed
    return min(128, _round_up(max(1, round(mean + 2 * mean ** 0.5)), 16))


def _held_by_slot(h, gate, gate_up, down, act, gated, first, limit=None):
    """``moe_held`` for a block of ONE row tile (N <= 128): slot s of the
    kernel's grid is the s-th hit expert and multiplies the block's N rows
    as they lie, so an expert's planes are dequantized once a projection.
    Returns (out, rows multiplied)."""
    from ipex_llm_tpu.ops.pallas.qmatmul import qmatmul_pallas_grouped

    n, n_held = gate.shape
    hit = jnp.any(gate != 0, axis=0)                     # [E_held]
    n_hit = jnp.sum(hit).astype(jnp.int32)
    order = jnp.argsort(~hit, stable=True).astype(jnp.int32)   # hit ones first
    inner = qmatmul_pallas_grouped(h, gate_up, order, n_hit, first)
    a = _expert_act(inner.astype(h.dtype), act, gated, limit)
    y = qmatmul_pallas_grouped(a, down, order, n_hit, first)
    # the slots past the hit ones were not in the grid: nothing was written
    live = (jnp.arange(n_held) < n_hit)[:, None, None]
    y = jnp.where(live, y, 0.0).astype(h.dtype)
    g_slots = gate[:, order].T[..., None]                # [E_held, N, 1]
    out = _weighed(g_slots[0], y[0])
    for s in range(1, n_held):                           # one fusion, in order
        out = out + _weighed(g_slots[s], y[s])
    return out, n_hit * n


def _held_by_rows(h, gate, gate_up, down, act, gated, first, bm, kmax,
                  limit=None):
    """``moe_held`` for a block of MORE rows than one tile (a prefill
    chunk): the routed PAIRS are multiplied, not tokens x hit experts.
    Every pair gets a row in an expert-major layout (expert e's rows start
    at a multiple of ``bm``; a pair's place in its group is the running
    count of ``gate[:, e] != 0``: ascending token order, no sort), the
    kernel runs over the live row tiles, each naming its expert, and the
    activation and the down projection stay in that layout.  The combine
    reads each pair's row once: a token's held experts in ascending order,
    term by term in float32.  ``kmax`` bounds the pairs a token can have;
    the static tile count is the worst case ``cdiv(N * kmax, bm) +
    E_held``, of which only the live tiles are in the kernel's grid.
    Returns (out, rows multiplied)."""
    from ipex_llm_tpu.ops.pallas.qmatmul import qmatmul_pallas_grouped

    n, n_held = gate.shape
    nz = gate != 0
    seen = jnp.cumsum(nz, axis=0, dtype=jnp.int32)       # [N, E] running count
    tiles = -(-seen[-1] // bm)                           # [E] row tiles each
    tile_end = jnp.cumsum(tiles)
    tile_first = tile_end - tiles
    n_live = tile_end[-1]
    t = jnp.arange(-(-n * kmax // bm) + n_held, dtype=jnp.int32)
    tile_e = jnp.minimum(
        jnp.sum(tile_end[None, :] <= t[:, None], axis=1), n_held - 1)
    # place p of expert e holds the token that has p routed before it
    place = ((t - tile_first[tile_e]) * bm)[:, None] + jnp.arange(bm)
    row_tok = jnp.sum(seen.T[tile_e][:, None, :] <= place[:, :, None],
                      axis=-1)                           # [T, bm]; N: padding
    x = jnp.pad(h, ((0, 1), (0, 0)))[row_tok]            # [T, bm, H]
    inner = qmatmul_pallas_grouped(x, gate_up, tile_e, n_live, first)
    a = _expert_act(inner.astype(h.dtype), act, gated, limit)
    y = qmatmul_pallas_grouped(a, down, tile_e, n_live, first)
    y = y.reshape(-1, y.shape[-1])                       # [T x bm, H]

    row = (tile_first * bm)[None, :] + seen - 1          # [N, E] a pair's row
    rank = jnp.cumsum(nz, axis=1, dtype=jnp.int32) - 1   # among the token's

    def term(j, out):
        pick = nz & (rank == j)                          # one expert a token
        y_j = y[jnp.sum(jnp.where(pick, row, 0), axis=1)].astype(h.dtype)
        g_j = jnp.sum(jnp.where(pick, gate, 0.0), axis=1, keepdims=True)
        return out + jnp.where(jnp.any(pick, axis=1, keepdims=True),
                               _weighed(g_j, y_j), 0.0)

    out = jax.lax.fori_loop(0, jnp.max(rank) + 1, term,
                            jnp.zeros((n, y.shape[-1]), jnp.float32))
    return out, n_live * bm


def moe_held(h, gate, gate_up, down, act, gated=True, layer=None,
             top_k=None, n_routed=None, limit=None):
    """The part of an expert layer that the experts HELD here contribute:
    h [N, H]; gate [N, E_held] float32, token n's weight for held expert e
    and 0 where it is not routed there (a pair that lands on an absent
    expert, a padding position).  ``gate_up``/``down`` are the layer's
    stacks [E_held, ...], or with ``layer`` (traced) the whole model's [L,
    E_held, ...], of which that layer is read in place: a scan that sliced
    a layer's stack out as its ``xs`` would copy every held expert's planes
    every call.  ``top_k`` of ``n_routed`` experts is what a token was
    routed to (static; E_held of E_held where not told: every token
    everywhere).  ``limit`` (static): the gated activation's clamp
    (ops/mlp.py ``gated_act_mul``), applied between the two kernel calls, so
    a model without one compiles what it compiled.  Returns (out [N, H]
    float32, experts hit, rows the expert matmuls ran over).

    One pass over the experts that are HIT, in ascending order: no pair is
    dropped whatever the imbalance, the stack is never dequantized at full
    width, and the weight traffic is the hit experts' planes.  On the chip
    the pass is two calls of the grouped dequant-matmul kernel (every hit
    expert's planes read in place through its index maps, the experts that
    are not hit left out of the grid), in one of two forms chosen by the
    block's STATIC N alone.  N <= 128 (a decode stage, a short prefill
    block): the block is one row tile, every hit expert multiplies all of
    it and a gate column weighs the result (``_held_by_slot``); grouping
    rows there would add a gather and save nothing, a hit expert's planes
    are dequantized once either way.  N > 128 (a prefill chunk): the rows
    are grouped by expert into tiles of ``row_tile`` rows and only routed
    pairs are multiplied (``_held_by_rows``).  Elsewhere a loop that slices
    one expert's planes at a time.

    A token gets the SAME BITS from every form, so it does not matter that
    which one it meets depends on the block it rides in (512 tokens of a
    chunk, the rows of a decode stage) or on where its prompt was chunked:
    a matmul row's bits depend on its own K sweep and not on the tile's
    height or the rows beside it, everything between the two calls is
    element-wise a row, and a token's sum runs over its own experts in
    ascending order, term by term in float32, with exact zeros (or nothing)
    between."""
    n, n_held = gate.shape
    first = 0
    if layer is not None:
        gate_up, down = (jax.tree_util.tree_map(
            lambda a: a.reshape((-1,) + a.shape[2:]), w)
            for w in (gate_up, down))
        first = layer * n_held

    hit = jnp.any(gate != 0, axis=0)                     # [E_held]
    n_hit = jnp.sum(hit).astype(jnp.int32)

    if _grouped_kernel_covers(gate_up) and _grouped_kernel_covers(down):
        if n <= 128:
            out, rows = _held_by_slot(h, gate, gate_up, down, act, gated,
                                      first, limit)
        else:
            top_k, n_routed = top_k or n_held, n_routed or n_held
            out, rows = _held_by_rows(
                h, gate, gate_up, down, act, gated, first,
                row_tile(n, top_k, n_routed), min(top_k, n_held), limit)
        return out, n_hit, rows

    order = jnp.argsort(~hit, stable=True).astype(jnp.int32)   # hit ones first

    def one(i, acc):
        e = order[i]
        inner = linear_ops.linear(h, _take_expert(gate_up, first + e))
        y = linear_ops.linear(_expert_act(inner, act, gated, limit),
                              _take_expert(down, first + e))
        g_e = jax.lax.dynamic_slice_in_dim(gate, e, 1, axis=1)   # [N, 1]
        return acc + g_e * y.astype(jnp.float32)

    acc = jnp.zeros((h.shape[0], down.shape[-1]), jnp.float32)
    return jax.lax.fori_loop(0, n_hit, one, acc), n_hit, n_hit * n


def moe_ffn(h, w, idx, gate_up, down, act, n_experts: int, gated=True,
            up_bias=None, down_bias=None):
    """Route by static pair count and mesh: gather (decode), ragged
    group-gemm (exact, single-mesh prefill), capacity buckets (ep)."""
    from ipex_llm_tpu.ops import dispatch

    kw = dict(gated=gated, up_bias=up_bias, down_bias=down_bias)
    n_pairs = h.shape[0] * h.shape[1] * idx.shape[-1]
    if n_pairs <= GATHER_PAIR_LIMIT:
        return moe_gather(h, w, idx, gate_up, down, act, **kw)
    mesh = dispatch.spmd_mesh()
    if mesh is not None and mesh.shape.get("ep", 1) > 1:
        return moe_capacity(h, w, idx, gate_up, down, act, n_experts, **kw)
    return moe_ragged(h, w, idx, gate_up, down, act, n_experts, **kw)
