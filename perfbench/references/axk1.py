"""The plain reference for ``model_type: axk1`` (SKT A.X-K1; its config's
keys are the DeepSeek-V3 block's, and the equations below are that block's as
published, Liu et al. 2024, sections 2.1.1-2.1.2): float32 ``jax.numpy``,
matmul precision "highest", the full forward over the whole sequence.  No
cache, no latent pool, no absorbed products, no kernels, no batching.  It
imports nothing of the program: it reads the HF config keys itself, builds
its own YaRN frequencies, and unpacks the SERVED int4 planes by its own code,
a layer (and inside an expert layer an expert) at a time, so it fits beside
the loaded engine at the published widths.

The model.  ``x <- x + Attn(RMSNorm(x))``; ``x <- x + FFN(RMSNorm(x))``; after
the last layer the final norm and the head.

- Attention (MLA): ``c_q = RMSNorm(h W_qa)``; ``q_i = c_q W_qb,i = [q_nope_i
  (128) ; q_rope_i (64)]`` a head; ``[c_kv (512) ; k_r (64)] = h W_kva``; ``c =
  RMSNorm(c_kv)``; ``[k_nope_i ; v_i] = c W_kvb,i``.  ``q_rope_i`` and the ONE
  ``k_r`` all heads share are rotated: interleaved pairs ``(x[2j], x[2j+1])``
  by ``pos * f_j`` with YaRN's frequencies (factor, original length, beta 32
  and 1 from ``rope_scaling``), cos and sin multiplied by ``m(mscale) /
  m(mscale_all_dim)``, ``m(s) = 0.1 s ln(factor) + 1``.  Logit of query t,
  key j, head i: ``s (q_nope_i . k_nope_ij + q_rope_i . k_r_j)`` with ``s =
  (128 + 64)^-1/2 m(mscale_all_dim)^2``; causal softmax; ``o_i = sum_j p_ij
  v_ij``; the output is ``[o_1 .. o_H] W_o``.
- Layer 0 (``first_k_dense_replace`` = 1): ``W_d (silu(h W_g) * h W_u)``.
- Expert layers: ``sigma = sigmoid(h W_r)`` in float32 over ALL routed experts
  of the published model (the router's width, ``n_router_experts``);
  ``n_group`` groups of consecutive experts, a group's score the sum of its
  two largest ``sigma``; the best ``topk_group`` groups stay; the
  ``num_experts_per_tok`` largest ``sigma`` among their experts are chosen;
  ``w_e = routed_scaling_factor sigma_e / (sum over the chosen + 1e-20)``; ``y
  = sum_e w_e E_e(h) + E_shared(h)`` with the dense layer's form at the
  experts' width.  ``topk_method: "none"`` is read as "no
  e_score_correction_bias" (the key that carries one is ``noaux_tc``).

The share.  The configuration is one chip of a deployment that divides the
routed experts and the vocabulary: the tree holds experts
``first_routed_expert .. + n_routed_experts`` and ``vocab_size`` rows.  The
reference computes exactly that share: it routes over the router's full
width, normalizes over all chosen experts, adds the held ones' outputs and
leaves the absent ones' out (no stand-in for the other chips), and takes the
log-softmax over the held vocabulary slice.

Router ties.  ``lax.top_k`` in float32, the lower index first among equals,
for the groups and then for the experts.  ``logprobs`` also returns, for every
position, the narrowest gap over the expert layers between the last chosen
and the first passed-over score, of groups and of experts.  With 96 eligible
experts and 7 expert layers that gap is 0.0001-0.003 at most positions (my
chip run, PR 37), which is what bf16 activations move a score by, so in the
served path some routing decision of most positions differs from this float32
one though neither is wrong; here it costs little, because a held expert takes
part in one pair of sixteen (the probes' largest error at such positions:
0.035).  ``compare`` holds two limits, as the hybrid family's does: the
LARGEST error over the positions whose narrowest gap is at least ROUTER_TIE
(only the all but exact ties are left out, and counted), and the MEDIAN error
over all positions.

Plane layout assumed (``quantize/core.py`` docstrings; a mismatch shows as
``correct: false``): a weight is ``[in, out]``; ``scales`` ``[in/32, out]``
fp16; ``data`` ``[in/2, out]`` uint8 where, inside each block of 32 input
rows, byte j holds row j in its low nibble and row j + 16 in its high nibble;
a code c stands for (c - 8) * scale.  Planes may be PADDED with inert rows and
columns past the logical ``shape``: the reference cuts them off.  ``gate_up``
planes hold the gate's columns first, then the up projection's.
"""

from __future__ import annotations

import functools
import math

BLOCK = 32
QUERY_BLOCK = 256          # queries attended at a time (memory, not math)

# --- limits ------------------------------------------------------------------
# Set from two readings at the published widths on the chip (PERF.md section
# 6, PR 37; my chip runs), each limit between them with room on both sides.
# (1) The served path, bf16 activations with float32 accumulation, router,
# softmax and norms as the configuration states: 20 probes of 8 positions
# over 10 seeds read a median error of 0.0061-0.0272 (the four largest
# 0.0221, 0.0235, 0.0272, 0.0218), a largest error of at most 0.0853 and an
# argmax margin of at most 0.0445; at the cell's lengths (a 4096-token prompt
# and 64 decoded tokens, alone and beside 63 rows) 0.0118 / 0.0492 / 0.0445.
# This reference against itself with the activations rounded to bf16
# (``lower="bf16"``) reads medians of 0.0031 and 0.0085, and with EVERY
# operation in bfloat16 (``lower="bf16_all"``) 0.0043, 0.0295 and, at the
# cell's lengths, 0.0151: on this chip that is the served path's own
# precision (the MXU accumulates in float32 whatever is asked), so "bf16 where
# float32 is stated" CANNOT be told from the served path by any limit and is
# not the control.
# (2) The nearest precision below the configuration's bf16 activations, fp8
# (``lower="fp8"``: every activation through e4m3), playing the server
# greedily on two probes: medians 0.0774 and 0.0901, largest errors 0.1135 and
# 0.2667; teacher-forced at the cell's lengths: median 0.0415, largest 0.2434.
# It is not correct by MEDIAN_TOL in all three (and by LOGPROB_TOL in one).
# MEDIAN_TOL 0.04 lies 1.47x over the served path's largest median and 1.9x
# under fp8's smallest at the probes' length, which is what ``correct``
# compares (at the cell's lengths, over 64 positions, fp8 reads 0.0415: the
# room there is thin and is the builder's check, not ``correct``'s).
# LOGPROB_TOL and ARGMAX_MARGIN_TOL stand 2.9x and 5.6x over the served
# path's largest readings and catch a gross fault (a dropped rotary slice
# reads 0.62 / 0.34 at a tiny size); fp8's largest errors straddle them.
LOGPROB_TOL = 0.25          # largest |log-probability error|, kept positions
MEDIAN_TOL = 0.04           # median error over all positions
ARGMAX_MARGIN_TOL = 0.25    # how far a chosen token may trail the argmax
ROUTER_TIE = 1e-4           # a narrower gap than this leaves a position out
KEEP_SHARE = 0.5            # at least this share of positions must be kept


def dequantize(data, scales, shape):
    """One served sym_int4 plane -> float32 ``shape`` = [in, out]."""
    import jax.numpy as jnp

    half = BLOCK // 2
    blocks = data.shape[0] // half
    d = data.reshape(blocks, half, data.shape[1])
    codes = jnp.concatenate([d & 0x0F, d >> 4], axis=1).astype(jnp.float32)
    w = (codes - 8.0) * scales.astype(jnp.float32)[:, None, :]
    return w.reshape(blocks * BLOCK, data.shape[1])[: shape[0], : shape[1]]


def yarn_frequencies(hf: dict):
    """(inverse frequencies [rope/2] float32, the factor on cos and sin, the
    factor on the softmax scale), from the config's ``rope_scaling``."""
    import numpy as np

    d = int(hf["qk_rope_head_dim"])
    base = float(hf.get("rope_theta", 10000.0))
    freq = 1.0 / base ** (np.arange(0, d, 2, dtype=np.float64) / d)
    rs = hf.get("rope_scaling") or {}
    if rs.get("type", rs.get("rope_type", "default")) != "yarn":
        return freq.astype(np.float32), 1.0, 1.0
    factor = float(rs["factor"])
    orig = float(rs["original_max_position_embeddings"])

    def correction_dim(rotations):
        return d * math.log(orig / (rotations * 2 * math.pi)) / (
            2 * math.log(base))

    low = max(math.floor(correction_dim(float(rs.get("beta_fast", 32)))), 0)
    high = min(math.ceil(correction_dim(float(rs.get("beta_slow", 1)))),
               d - 1)
    ramp = np.clip((np.arange(d // 2, dtype=np.float64) - low)
                   / max(high - low, 1e-3), 0.0, 1.0)
    inv = freq / factor * ramp + freq * (1.0 - ramp)

    def m(s):
        return 0.1 * s * math.log(factor) + 1.0 if factor > 1 else 1.0

    m_all = m(float(rs.get("mscale_all_dim") or 0.0))
    return (inv.astype(np.float32), m(float(rs.get("mscale", 1.0))) / m_all,
            m_all * m_all)


def _dtype(lower):
    import jax.numpy as jnp

    return jnp.bfloat16 if lower == "bf16_all" else jnp.float32


def _act(x, lower):
    """An activation as the served path holds it between operations."""
    import jax.numpy as jnp

    if lower in ("bf16", "bf16_all"):
        return x.astype(jnp.bfloat16).astype(_dtype(lower))
    if lower == "fp8":
        return x.astype(jnp.float8_e4m3fn).astype(jnp.float32)
    return x


def _mm(a, w, lower):
    import jax.numpy as jnp

    dt = _dtype(lower)
    return jnp.matmul(a.astype(dt), w.astype(dt), preferred_element_type=dt)


def _rms_norm(x, w, eps, lower):
    import jax
    import jax.numpy as jnp

    dt = _dtype(lower)
    x = x.astype(dt)
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + jnp.asarray(eps, dt)) * w.astype(dt)


def _rope(x, pos, inv_freq, factor):
    """Interleaved pairs (x[2j], x[2j+1]) of x [T, ..., d] rotated by pos *
    inv_freq[j]."""
    import jax.numpy as jnp

    ang = pos.astype(jnp.float32)[:, None] * inv_freq[None, :]
    cos, sin = jnp.cos(ang) * factor, jnp.sin(ang) * factor
    while cos.ndim < x.ndim:
        cos, sin = cos[:, None], sin[:, None]
    x1, x2 = x[..., 0::2].astype(jnp.float32), x[..., 1::2].astype(jnp.float32)
    out = jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.reshape(x.shape).astype(x.dtype)


@functools.lru_cache(maxsize=8)
def _attn_fn(heads: int, nope: int, rope: int, vdim: int, rank: int,
             eps: float, scale: float, cos_factor: float, shapes: tuple,
             lower):
    import jax
    import jax.numpy as jnp

    qa_s, qb_s, kva_s, kvb_s, o_s = shapes
    drop_rope = lower == "no_rope"
    lower = None if drop_rope else lower

    def block(x, lp, inv_freq):
        t = x.shape[0]
        dt = _dtype(lower)
        h = _act(_rms_norm(x, lp["attn_norm"], eps, lower), lower)
        c_q = _act(_rms_norm(_act(_mm(h, dequantize(*lp["q_a"], qa_s), lower),
                                  lower), lp["q_a_norm"], eps, lower), lower)
        q = _act(_mm(c_q, dequantize(*lp["q_b"], qb_s), lower),
                 lower).reshape(t, heads, nope + rope)
        ckv = _act(_mm(h, dequantize(*lp["kv_a"], kva_s), lower), lower)
        c = _act(_rms_norm(ckv[:, :rank], lp["kv_a_norm"], eps, lower), lower)
        pos = jnp.arange(t)
        q_r = _act(_rope(q[..., nope:], pos, inv_freq, cos_factor), lower)
        k_r = _act(_rope(ckv[:, rank:], pos, inv_freq, cos_factor), lower)
        kv = _act(_mm(c, dequantize(*lp["kv_b"], kvb_s), lower),
                  lower).reshape(t, heads, nope + vdim)
        q_n, k_n, v = q[..., :nope], kv[..., :nope], kv[..., nope:]
        outs = []
        for q0 in range(0, t, QUERY_BLOCK):
            q1 = min(q0 + QUERY_BLOCK, t)
            s = jnp.einsum("qhd,khd->hqk", q_n[q0:q1].astype(dt),
                           k_n[:q1].astype(dt), preferred_element_type=dt)
            if not drop_rope:
                s = s + jnp.einsum("qhd,kd->hqk", q_r[q0:q1].astype(dt),
                                   k_r[:q1].astype(dt),
                                   preferred_element_type=dt)
            s = s * jnp.asarray(scale, dt)
            live = (jnp.arange(q1)[None, :]
                    <= jnp.arange(q0, q1)[:, None])[None]
            p = jax.nn.softmax(jnp.where(live, s, -jnp.inf), axis=-1)
            outs.append(jnp.einsum("hqk,khd->qhd", p.astype(dt),
                                   v[:q1].astype(dt),
                                   preferred_element_type=dt))
        a = _act(jnp.concatenate(outs, axis=0).reshape(t, heads * vdim), lower)
        return _act(x.astype(dt) + _act(
            _mm(a, dequantize(*lp["o"], o_s), lower), lower), lower)

    return jax.jit(block)


def _gated_mlp(h, gate_up, down, width: int, lower):
    import jax

    g = _mm(h, gate_up[:, :width], lower)
    u = _mm(h, gate_up[:, width:], lower)
    return _mm(_act(jax.nn.silu(g) * u, lower), down, lower)


@functools.lru_cache(maxsize=8)
def _dense_fn(eps: float, shapes: tuple, lower):
    import jax

    lower = None if lower == "no_rope" else lower
    gu_s, d_s = shapes

    def block(x, lp):
        h = _act(_rms_norm(x, lp["mlp_norm"], eps, lower), lower)
        y = _gated_mlp(h, dequantize(*lp["gate_up"], gu_s),
                       dequantize(*lp["down"], d_s), gu_s[1] // 2, lower)
        return _act(x.astype(_dtype(lower)) + _act(y, lower), lower)

    return jax.jit(block)


@functools.lru_cache(maxsize=8)
def _moe_fn(top_k: int, first: int, n_group: int, topk_group: int,
            scaling: float, eps: float, shapes: tuple, lower):
    import jax
    import jax.numpy as jnp

    lower = None if lower == "no_rope" else lower
    gu_s, d_s, sgu_s, sd_s = shapes

    def block(x, lp):
        dt = _dtype(lower)
        h = _act(_rms_norm(x, lp["mlp_norm"], eps, lower), lower)
        sigma = jax.nn.sigmoid(jnp.matmul(
            h.astype(dt), lp["router"].astype(dt),
            preferred_element_type=dt)).astype(jnp.float32)       # [T, E]
        t, width = sigma.shape
        groups = sigma.reshape(t, n_group, width // n_group)
        top2 = jax.lax.top_k(groups, 2)[0]
        g_score = top2[..., 0] + top2[..., 1]
        g_sorted, g_idx = jax.lax.top_k(g_score, min(topk_group + 1, n_group))
        stay = jnp.zeros((t, n_group), bool).at[
            jnp.arange(t)[:, None], g_idx[:, :topk_group]].set(True)
        eligible = jnp.where(stay[..., None], groups, 0.0).reshape(t, width)
        e_sorted, e_idx = jax.lax.top_k(eligible, top_k + 1)
        idx, chosen = e_idx[:, :top_k], jnp.take_along_axis(
            sigma, e_idx[:, :top_k], axis=1)
        w = chosen / (jnp.sum(chosen, -1, keepdims=True) + 1e-20) * scaling
        gap = e_sorted[:, top_k - 1] - e_sorted[:, top_k]
        if topk_group < n_group:
            gap = jnp.minimum(
                gap, g_sorted[:, topk_group - 1] - g_sorted[:, topk_group])
        y = _gated_mlp(h, dequantize(*lp["shared_gate_up"], sgu_s),
                       dequantize(*lp["shared_down"], sd_s), sgu_s[1] // 2,
                       lower).astype(jnp.float32)
        held = lp["moe_gate_up"][0].shape[0]
        for e in range(held):
            gate = jnp.sum(jnp.where(idx == first + e, w, 0.0), axis=1)
            out = _gated_mlp(
                h, dequantize(lp["moe_gate_up"][0][e], lp["moe_gate_up"][1][e],
                              gu_s),
                dequantize(lp["moe_down"][0][e], lp["moe_down"][1][e], d_s),
                gu_s[1] // 2, lower)
            y = y + gate[:, None] * _act(out, lower).astype(jnp.float32)
        return _act(x.astype(dt) + _act(y.astype(dt), lower), lower), gap

    return jax.jit(block)


@functools.lru_cache(maxsize=8)
def _head_fn(eps: float, shape: tuple, lower):
    import jax
    import jax.numpy as jnp

    lower = None if lower == "no_rope" else lower

    def head(x, norm, plane):
        logits = _mm(_act(_rms_norm(x, norm, eps, lower), lower),
                     dequantize(*plane, shape), lower)
        return jax.nn.log_softmax(logits, -1).astype(jnp.float32)

    return jax.jit(head)


def _plane(q):
    return (q.data, q.scales)


def _layer_tree(stack: dict, i: int) -> dict:
    """Layer ``i`` of a stacked tree, QTensors as their (data, scales)."""
    out = {}
    for name, leaf in stack.items():
        if hasattr(leaf, "scales"):
            out[name] = (leaf.data[i], leaf.scales[i])
        else:
            out[name] = leaf[i]
    return out


def logprobs(hf_config: dict, params: dict, ids: list[int], lower=None,
             first_row: int = 0):
    """(log-softmax of the next-token logits at positions ``first_row`` on of
    ``ids`` over the held vocabulary, ``[T - first_row, vocab]`` float32; each
    of those positions' narrowest router gap over the expert layers), as
    numpy.

    ``lower="fp8"`` passes the activations between operations (where the
    served path holds bf16) through float8_e4m3 and keeps every operation
    float32: the nearest precision below the configuration's bf16, the
    reading that has to fail ``compare``.
    ``lower="bf16_all"`` computes the same model with EVERY operation in
    bfloat16 (products accumulated to bf16; router, softmax and norms in
    bf16): the precision below the configuration's, for the reading that has
    to fail ``compare``.  ``lower="bf16"`` rounds the activations between
    operations to bfloat16 where the served path does and keeps every
    operation float32: what rounding alone, at the configuration's own
    precision, does to the log-probabilities.  ``lower="no_rope"`` drops the
    rotated slice's part of every logit (float32): what a dropped slice
    reads."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    hf = hf_config
    eps = float(hf.get("rms_norm_eps", 1e-6))
    heads = int(hf["num_attention_heads"])
    nope, rope = int(hf["qk_nope_head_dim"]), int(hf["qk_rope_head_dim"])
    vdim, rank = int(hf["v_head_dim"]), int(hf["kv_lora_rank"])
    n_dense = int(hf.get("first_k_dense_replace", 0))
    n_layers = int(hf["num_hidden_layers"])
    if hf.get("topk_method", "none") != "none":
        raise ValueError("this reference has no score-correction bias")
    if hf.get("scoring_func", "sigmoid") != "sigmoid":
        raise ValueError("this reference scores with a sigmoid")
    if not hf.get("norm_topk_prob", True):
        raise ValueError("this reference normalizes the chosen weights")
    inv_freq, cos_factor, scale_factor = yarn_frequencies(hf)
    scale = (nope + rope) ** -0.5 * scale_factor
    dense, moe_tree = params.get("layers_dense"), params["layers"]

    def shapes(tree, *names):
        return tuple(tuple(tree[m].shape) for m in names)

    attn_names = ("q_a", "q_b", "kv_a", "kv_b", "o")
    moe = _moe_fn(int(hf["num_experts_per_tok"]),
                  int(hf.get("first_routed_expert", 0)), int(hf["n_group"]),
                  int(hf["topk_group"]), float(hf["routed_scaling_factor"]),
                  eps, shapes(moe_tree, "moe_gate_up", "moe_down",
                              "shared_gate_up", "shared_down"), lower)
    head = params["lm_head"]
    t = len(ids)
    tie = jnp.full((t,), jnp.inf)
    with jax.default_matmul_precision("highest"):
        x = params["embed"][jnp.asarray(ids, jnp.int32)].astype(_dtype(lower))
        for i in range(n_layers):
            tree = dense if i < n_dense else moe_tree
            lp = _layer_tree(tree, i if i < n_dense else i - n_dense)
            x = _attn_fn(heads, nope, rope, vdim, rank, eps, scale,
                         cos_factor, shapes(tree, *attn_names), lower)(
                x, lp, jnp.asarray(inv_freq))
            if i < n_dense:
                x = _dense_fn(eps, shapes(tree, "gate_up", "down"), lower)(
                    x, lp)
            else:
                x, gap = moe(x, lp)
                tie = jnp.minimum(tie, gap)
        out = _head_fn(eps, tuple(head.shape), lower)(
            x[first_row:], params["final_norm"], _plane(head))
    return np.asarray(out, np.float32), np.asarray(tie[first_row:],
                                                   np.float32)


def compare(ref, prompt_len: int, tokens: list[int],
            server_logprobs: list[float]) -> dict:
    """Per emitted position: the server's chosen-token log-probability
    against the reference's, and how far the chosen token's reference logit
    trails the reference's argmax.  The largest of each over the positions
    whose narrowest router gap is at least ROUTER_TIE, and the median error
    over all of them.  ``prompt_len`` counts from the first row ``ref``
    holds."""
    import numpy as np

    rows, tie = ref
    errs, margins, gaps = [], [], []
    for j, (tok, lp) in enumerate(zip(tokens, server_logprobs)):
        row = rows[prompt_len - 1 + j]
        errs.append(abs(float(row[tok]) - float(lp)))
        margins.append(float(np.max(row) - row[tok]))
        gaps.append(float(tie[prompt_len - 1 + j]))
    kept = [j for j, g in enumerate(gaps) if g >= ROUTER_TIE]
    lp_err = max((errs[j] for j in kept), default=0.0)
    margin = max((margins[j] for j in kept), default=0.0)
    median = float(np.median(errs)) if errs else 0.0
    ok = (len(tokens) == len(server_logprobs) and len(tokens) > 0
          and len(kept) >= KEEP_SHARE * len(tokens)
          and lp_err <= LOGPROB_TOL and margin <= ARGMAX_MARGIN_TOL
          and median <= MEDIAN_TOL)
    return {"ok": bool(ok), "logprob_err": lp_err, "argmax_margin": margin,
            "logprob_err_median": median,
            "router_ties_left_out": len(tokens) - len(kept),
            "logprob_tol": LOGPROB_TOL, "margin_tol": ARGMAX_MARGIN_TOL,
            "median_tol": MEDIAN_TOL, "router_tie": ROUTER_TIE,
            "errors": [round(e, 4) for e in errs],
            "gaps": [round(g, 6) for g in gaps]}
