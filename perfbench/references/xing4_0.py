"""The plain reference for ``model_type: xing4_0`` (XingChen-AGI Xing4.0; its
config's keys are the DeepSeek-V3 block's, Liu et al. 2024, sections
2.1.1-2.1.2, on a residual path of manifold-constrained hyper-connections,
mHC: Xie et al., arXiv:2512.24880, over Zhu et al., Hyper-Connections,
arXiv:2409.19606): float32 ``jax.numpy``, matmul precision "highest", the full
forward over the whole sequence.  No cache, no latent pool, no absorbed
products, no kernels, no batching.  It imports nothing of the program: it
reads the HF config keys itself, builds its own YaRN frequencies, and unpacks
the SERVED int4 planes by its own code, a layer (and inside an expert layer an
expert) at a time, so it fits beside the loaded engine at the published
widths.

The residual path.  A token's residual is not one vector but ``n = hc_mult``
streams ``X in R^{n x C}``.  The embedding is copied into all n streams; EVERY
sublayer ``F`` (attention; the dense MLP of layers ``< first_k_dense_replace``;
the expert layer of the others) is wrapped so, for a token::

    v      = vec(X) / sqrt(mean(vec(X)^2) + hc_eps)       one RMS over n*C values
    Hpre~  = a_pre  * (v P_pre)  + b_pre        P_pre  [n*C, n]
    Hpost~ = a_post * (v P_post) + b_post       P_post [n*C, n]
    Hres~  = a_res  * mat(v P_res) + b_res      P_res  [n*C, n*n], b_res [n, n]
    Hpre = sigmoid(Hpre~);  Hpost = 2 sigmoid(Hpost~)
    M0 = exp(clip(Hres~, mhc_h_res_clamp_min, mhc_h_res_clamp_max))
    hc_sinkhorn_iters times:  M <- M / (colsum(M) + hc_eps);  M <- M / (rowsum(M) + hc_eps)
    Hres = M
    h  = sum_j Hpre[j] X[j]                       what F's own RMSNorm and F see
    X'[i] = sum_j Hres[i, j] X[j] + Hpost[i] F(RMSNorm(h))

and after the last layer the streams are SUMMED, then the final norm and the
head.  ``vec`` is stream-major (stream 0's C values first), ``mat`` row-major
(``Hres~[i, j]`` is output ``i * n + j`` of ``P_res``); ``colsum`` sums over
``i``, ``rowsum`` over ``j``.  The tree holds a sublayer's three ``P`` as ONE
float32 plane ``hc_<sub>_proj`` ``[n + n + n*n, n*C]`` whose rows are
``P_pre``'s, ``P_post``'s and ``P_res``'s columns in that order,
``hc_<sub>_scale`` ``[3]`` = ``(a_pre, a_post, a_res)`` and ``hc_<sub>_bias``
``[n + n + n*n]`` likewise (``<sub>`` is ``attn`` or ``mlp``).

``assumed`` (in the configuration's file; the config gives the four ``hc_*`` /
``mhc_*`` numbers and no code): the copy into all streams and the sum before
the final norm (Zhu et al. section 3); column then row, and ``hc_eps`` in the
RMS and in both Sinkhorn denominators (Xie et al. eqs. 7-9 as read here); the
``P``, ``a`` and ``b`` float32 and never quantized.

The sublayers, with ``h`` the normed mix:

- Attention (MLA): ``c_q = RMSNorm(h W_qa)``; ``q_i = c_q W_qb,i = [q_nope_i
  (128) ; q_rope_i (64)]`` a head; ``[c_kv (512) ; k_r (64)] = h W_kva``; ``c =
  RMSNorm(c_kv)``; ``[k_nope_i ; v_i] = c W_kvb,i``.  ``q_rope_i`` and the ONE
  ``k_r`` all heads share are rotated: interleaved pairs ``(x[2j], x[2j+1])``
  by ``pos * f_j`` with YaRN's frequencies (factor 64 over 4096, beta 32 and 1),
  cos and sin multiplied by ``m(mscale) / m(mscale_all_dim)`` (= 1 here),
  ``m(s) = 0.1 s ln(factor) + 1``.  Logit of query t, key j, head i: ``s
  (q_nope_i . k_nope_ij + q_rope_i . k_r_j)`` with ``s = (128 + 64)^-1/2
  m(mscale_all_dim)^2``; causal softmax; the output is ``[o_1 .. o_H] W_o``.
- Layers ``< first_k_dense_replace``: ``W_d (silu(h W_g) * h W_u)``.
- Expert layers: ``sigma = sigmoid(h W_r)`` in float32 over ALL routed experts
  of the published model (the router's width, ``n_router_experts``); the
  ``num_experts_per_tok`` largest ``sigma + b`` (``e_score_correction_bias``;
  ``n_group`` 1: no group step) are chosen; ``w_e = routed_scaling_factor
  sigma_e / (sum over the chosen + 1e-20)`` (the RAW scores); ``y = sum_e w_e
  E_e(h) + E_shared(h)`` with the dense layer's form at the experts' width.

Departure from the published model, also the served path's: the
multi-token-prediction layer (``num_nextn_predict_layers``) is not built: a
draft head, on which the model's logits do not depend.

The share.  The configuration is one chip of a deployment that divides the
routed experts and the vocabulary: the tree holds experts
``first_routed_expert .. + n_routed_experts`` and ``vocab_size`` rows.  The
reference computes exactly that share: it routes over the router's full
width, normalizes over all chosen experts, adds the held ones' outputs and
leaves the absent ones' out (no stand-in for the other chips), and takes the
log-softmax over the held vocabulary slice.  The hyper-connection and the
shared expert are every chip's alike.

Router ties.  ``lax.top_k`` in float32, the lower index first among equals.
``logprobs`` also returns, for every position, the narrowest gap over the
expert layers between the last chosen and the first passed-over ``sigma + b``;
``compare`` leaves the all but exact ties (under ROUTER_TIE) out of its
LARGEST-error limits, counted, as the other expert families' references do,
and holds the MEAN over all positions.

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
# Set once, BEFORE the readings (PERF.md section 6, PR 44 second session:
# predicted from a depth-40 copy on the CPU), and held by two readings at the
# published widths on the chip, every run made AT these values, each limit
# between them. What is compared is a probe's 8 emitted positions; the
# 128-position comparisons of scripts/xing4_reasoning_check.py also give their
# 16 windows of 8.
# (1) The served path, bf16 streams and activations with float32 accumulation;
# router, softmax, norms and the hyper-connections' coefficients float32 as the
# configuration states.  A probe's MEAN error 0.008-0.029 (36 probes, 12
# seeds), 0.010-0.032 over the 32 windows of the last 128 of 1100 decoded
# tokens (0.016 and 0.020 over all 128, two seeds, alone and beside 15 rows
# alike); its largest error at most 0.071 (0.086 a window); the argmax margin
# at most 0.091 (the next 0.059).  This reference against itself with the SAME
# places rounded to bf16 (``lower="bf16"``) reads the same: 0.012-0.016 a
# probe, 0.004-0.029 a window: the served path's error is its rounding, not a
# defect.
# (2) The nearest precision below the configuration's bf16 activations, fp8
# (``lower="fp8"``: the streams and every activation through e4m3): playing the
# server greedily on four probes (two seeds), mean 0.158-0.189, largest error
# 0.27-0.46, margin 0.20-0.42; teacher-forced over those 128 positions, 0.156
# and 0.174, a window 0.095-0.253, its largest error 0.21-0.71.  Not correct by
# EACH of the three limits in all four probes, by MEAN_TOL and LOGPROB_TOL in
# all 32 windows.  The planted faults: a dropped dynamic term
# (``lower="static_coef"``) 0.089-0.214 (largest 0.24-0.37; margin 0.33-0.60);
# the held experts taken for their neighbours (``"expert_shift"``) 0.122-0.172,
# a window 0.060-0.244.  Not correct either, in every reading.
# MEAN_TOL tells the precisions apart: 0.045 is 1.4x over the served path's
# largest (0.032) and 2.1x under fp8's smallest (0.095; 1.3x under the smallest
# reading of any fault, 0.060); fp8's smallest is 2.9x the served path's
# largest, its typical reading (0.16) ten times the served path's (0.016).  The
# mean, not the median: a fault's errors are heavy above (a few positions of
# 0.3-0.7), rounding's are not, and over 8 positions the mean keeps what the
# median throws away.
# LOGPROB_TOL 0.2 is 2.3x over the served path's largest error and under every
# fp8 reading (0.21 at least); ARGMAX_MARGIN_TOL 0.15 is 1.65x over the served
# path's largest (2.5x over the next) and under all four fp8 probes' (0.20 at
# least; a teacher-forced control's margin is the served tokens' own and says
# nothing).  A position whose narrowest router gap is under ROUTER_TIE is left
# out of the two maxima (its routing is decided by the last bit) and counted:
# up to 4 of a probe's 8 and 23 of 128 here; the mean takes all.
# WHY these readings part at all: the generator draws the routed experts' down
# projections at a quarter of the other blocks' gain (generators/xing4_0.py,
# ROUTED_GAIN).  At the full gain one routing decision flipped by rounding
# moves the later routers' inputs by more than their gaps, flips breed flips
# over 38 expert layers, and any rounding grows to an error set by the weights
# (a probe's median then read 0.04-0.14 served against 0.18-0.41 through fp8);
# a depth-40 copy of the tiny model on the CPU shows both regimes in minutes
# (PERF.md section 6).  Change that gain and these limits are to be read anew.
# The hyper-connections' coefficients in bfloat16 (``lower="coef_bf16"``) are
# STILL not told from the served path by these limits at 8 positions
# (0.020-0.044 a probe, 0.015-0.042 a window, 0.029 and 0.030 over 128
# positions against the served 0.016 and 0.020: half again to twice it, inside
# its windows' range): they are held to float32 where they are computed, in the
# TICK's own program (tests/test_xing4.py walks ``_ragged_tick``'s jaxpr under
# scope ``mhc/coef``: float32 operands, precision "highest", no other floating
# type), and ``hyper_coefficients`` against ipex_llm_tpu/ops/hyper.py on the
# device at the published width agrees to 1.1e-6 (limit 1e-4) where the
# bfloat16 coefficients miss by 7.9e-3 and 8.3e-3
# (scripts/xing4_reasoning_check.py).
LOGPROB_TOL = 0.2           # largest |log-probability error|, kept positions
MEAN_TOL = 0.045            # mean error over ALL positions
ARGMAX_MARGIN_TOL = 0.15    # how far a chosen token may trail the argmax
ROUTER_TIE = 1e-4           # a narrower gap than this leaves a position out
KEEP_SHARE = 0.25           # fewer kept positions than this share fails


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


def _act(x, lower):
    """An activation (or the streams) as the served path holds it between
    operations."""
    import jax.numpy as jnp

    if lower in ("bf16", "coef_bf16"):
        return x.astype(jnp.bfloat16).astype(jnp.float32)
    if lower == "fp8":
        return x.astype(jnp.float8_e4m3fn).astype(jnp.float32)
    return x


def _rms_norm(x, w, eps):
    import jax
    import jax.numpy as jnp

    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * w


def _rope(x, pos, inv_freq, factor):
    """Interleaved pairs (x[2j], x[2j+1]) of x [T, ..., d] rotated by pos *
    inv_freq[j]."""
    import jax.numpy as jnp

    ang = pos.astype(jnp.float32)[:, None] * inv_freq[None, :]
    cos, sin = jnp.cos(ang) * factor, jnp.sin(ang) * factor
    while cos.ndim < x.ndim:
        cos, sin = cos[:, None], sin[:, None]
    x1, x2 = x[..., 0::2], x[..., 1::2]
    out = jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.reshape(x.shape)


# --- the hyper-connection ------------------------------------------------------

def hyper_coefficients(x, proj, scale, bias, iters: int, eps: float, lo: float,
                       hi: float, lower=None):
    """(Hpre [T, n], Hpost [T, n], Hres [T, n, n]) of the streams ``x``
    [T, n, C], the module docstring's equations in their order.

    ``lower="coef_bf16"`` computes all of it in bfloat16 (the product
    accumulated to bf16, the RMS, the sigmoids and the Sinkhorn rounds): one
    precision below the float32 the configuration states for it.
    ``lower="static_coef"`` drops the token-dependent term (``P`` zeroed: the
    coefficients of the biases alone, the same for every token)."""
    import jax
    import jax.numpy as jnp

    t, n, c = x.shape
    dt = jnp.bfloat16 if lower == "coef_bf16" else jnp.float32
    if lower == "static_coef":
        proj = jnp.zeros_like(proj)
    flat = x.reshape(t, n * c).astype(dt)
    v = flat * jax.lax.rsqrt(jnp.mean(flat * flat, axis=-1, keepdims=True)
                             + jnp.asarray(eps, dt))
    raw = jnp.matmul(v, proj.T.astype(dt), preferred_element_type=dt)
    pre = jax.nn.sigmoid(scale[0].astype(dt) * raw[:, :n]
                         + bias[:n].astype(dt))
    post = 2 * jax.nn.sigmoid(scale[1].astype(dt) * raw[:, n:2 * n]
                              + bias[n:2 * n].astype(dt))
    res = (scale[2].astype(dt) * raw[:, 2 * n:]
           + bias[2 * n:].astype(dt)).reshape(t, n, n)
    m = jnp.exp(jnp.clip(res, lo, hi))
    for _ in range(iters):
        m = m / (jnp.sum(m, axis=1, keepdims=True) + jnp.asarray(eps, dt))
        m = m / (jnp.sum(m, axis=2, keepdims=True) + jnp.asarray(eps, dt))
    f32 = jnp.float32
    return pre.astype(f32), post.astype(f32), m.astype(f32)


@functools.lru_cache(maxsize=8)
def _hyper_fn(iters: int, eps: float, lo: float, hi: float, lower):
    """``connect(x [T, n, C], proj, scale, bias, f)``: one wrapped sublayer,
    ``f(h [T, C]) -> (F(h), extra)``; returns (X', extra)."""
    import jax.numpy as jnp

    def connect(x, proj, scale, bias, f):
        pre, post, res = hyper_coefficients(x, proj, scale, bias, iters, eps,
                                            lo, hi, lower)
        h = _act(jnp.einsum("tj,tjc->tc", pre, x), lower)
        out, extra = f(h)
        new = (jnp.einsum("tij,tjc->tic", res, x)
               + post[:, :, None] * out[:, None, :])
        return _act(new, lower), extra

    return connect


# --- the sublayers: each returns F(RMSNorm(h)), no residual ---------------------

def _attn(h_in, lp, inv_freq, *, heads, nope, rope, vdim, rank, eps, scale,
          cos_factor, shapes, lower):
    import jax
    import jax.numpy as jnp

    qa_s, qb_s, kva_s, kvb_s, o_s = shapes
    t = h_in.shape[0]
    h = _act(_rms_norm(h_in, lp["attn_norm"], eps), lower)
    c_q = _act(_rms_norm(_act(h @ dequantize(*lp["q_a"], qa_s), lower),
                         lp["q_a_norm"], eps), lower)
    q = _act(c_q @ dequantize(*lp["q_b"], qb_s), lower).reshape(
        t, heads, nope + rope)
    ckv = _act(h @ dequantize(*lp["kv_a"], kva_s), lower)
    c = _act(_rms_norm(ckv[:, :rank], lp["kv_a_norm"], eps), lower)
    pos = jnp.arange(t)
    q_r = _act(_rope(q[..., nope:], pos, inv_freq, cos_factor), lower)
    k_r = _act(_rope(ckv[:, rank:], pos, inv_freq, cos_factor), lower)
    kv = _act(c @ dequantize(*lp["kv_b"], kvb_s), lower).reshape(
        t, heads, nope + vdim)
    q_n, k_n, v = q[..., :nope], kv[..., :nope], kv[..., nope:]
    outs = []
    for q0 in range(0, t, QUERY_BLOCK):
        q1 = min(q0 + QUERY_BLOCK, t)
        s = (jnp.einsum("qhd,khd->hqk", q_n[q0:q1], k_n[:q1])
             + jnp.einsum("qhd,kd->hqk", q_r[q0:q1], k_r[:q1])) * scale
        live = (jnp.arange(q1)[None, :] <= jnp.arange(q0, q1)[:, None])[None]
        p = jax.nn.softmax(jnp.where(live, s, -jnp.inf), axis=-1)
        outs.append(jnp.einsum("hqk,khd->qhd", p, v[:q1]))
    a = _act(jnp.concatenate(outs, axis=0).reshape(t, heads * vdim), lower)
    return _act(a @ dequantize(*lp["o"], o_s), lower)


def _gated_mlp(h, gate_up, down, width: int, lower):
    import jax

    g = h @ gate_up[:, :width]
    u = h @ gate_up[:, width:]
    return _act(jax.nn.silu(g) * u, lower) @ down


def _dense(h_in, lp, *, eps, shapes, lower):
    gu_s, d_s = shapes
    h = _act(_rms_norm(h_in, lp["mlp_norm"], eps), lower)
    return _act(_gated_mlp(h, dequantize(*lp["gate_up"], gu_s),
                           dequantize(*lp["down"], d_s), gu_s[1] // 2, lower),
                lower)


def _moe(h_in, lp, *, top_k, first, scaling, eps, shapes, lower,
         shared: bool = True):
    """(the held experts' part + the shared expert [T, C], the narrowest
    router gap [T])."""
    import jax
    import jax.numpy as jnp

    gu_s, d_s, sgu_s, sd_s = shapes
    h = _act(_rms_norm(h_in, lp["mlp_norm"], eps), lower)
    sigma = jax.nn.sigmoid(h @ lp["router"])                       # [T, E]
    s_sorted, e_idx = jax.lax.top_k(sigma + lp["router_bias"], top_k + 1)
    idx = e_idx[:, :top_k]
    chosen = jnp.take_along_axis(sigma, idx, axis=1)
    w = chosen / (jnp.sum(chosen, -1, keepdims=True) + 1e-20) * scaling
    gap = s_sorted[:, top_k - 1] - s_sorted[:, top_k]
    y = jnp.zeros_like(h_in)
    if shared:
        y = _gated_mlp(h, dequantize(*lp["shared_gate_up"], sgu_s),
                       dequantize(*lp["shared_down"], sd_s), sgu_s[1] // 2,
                       lower)
    held = lp["moe_gate_up"][0].shape[0]
    for e in range(held):
        gate = jnp.sum(jnp.where(idx == first + e, w, 0.0), axis=1)
        out = _gated_mlp(
            h, dequantize(lp["moe_gate_up"][0][e], lp["moe_gate_up"][1][e],
                          gu_s),
            dequantize(lp["moe_down"][0][e], lp["moe_down"][1][e], d_s),
            gu_s[1] // 2, lower)
        y = y + gate[:, None] * _act(out, lower)
    return _act(y, lower), gap


@functools.lru_cache(maxsize=16)
def _layer_fn(kind: str, hyper: tuple, attn: tuple, ffn: tuple, lower):
    """One whole layer, jitted: ``layer(x [T, n, C], lp, inv_freq) -> (x',
    router gap [T])``, both sublayers wrapped."""
    import jax
    import jax.numpy as jnp

    connect = _hyper_fn(*hyper, lower)
    attn_kw, ffn_kw = dict(attn), dict(ffn)

    def layer(x, lp, inv_freq):
        x, _ = connect(
            x, lp["hc_attn_proj"], lp["hc_attn_scale"], lp["hc_attn_bias"],
            lambda h: (_attn(h, lp, inv_freq, lower=lower, **attn_kw), None))
        if kind == "dense":
            return connect(
                x, lp["hc_mlp_proj"], lp["hc_mlp_scale"], lp["hc_mlp_bias"],
                lambda h: (_dense(h, lp, lower=lower, **ffn_kw),
                           jnp.full((x.shape[0],), jnp.inf)))
        return connect(
            x, lp["hc_mlp_proj"], lp["hc_mlp_scale"], lp["hc_mlp_bias"],
            lambda h: _moe(h, lp, lower=lower, **ffn_kw))

    return jax.jit(layer)


@functools.lru_cache(maxsize=8)
def _head_fn(eps: float, shape: tuple, lower):
    import jax

    def head(x, norm, plane):
        logits = _act(_rms_norm(x, norm, eps), lower) @ dequantize(
            *plane, shape)
        return jax.nn.log_softmax(logits, -1)

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


def settings(hf: dict, params: dict) -> dict:
    """The static arguments of the reference's functions, from the config's
    keys and the tree's logical plane shapes."""
    eps = float(hf.get("rms_norm_eps", 1e-6))
    nope, rope = int(hf["qk_nope_head_dim"]), int(hf["qk_rope_head_dim"])
    if hf.get("topk_method", "noaux_tc") != "noaux_tc":
        raise ValueError("this reference selects on score + bias (noaux_tc)")
    if hf.get("scoring_func", "sigmoid") != "sigmoid":
        raise ValueError("this reference scores with a sigmoid")
    if not hf.get("norm_topk_prob", True):
        raise ValueError("this reference normalizes the chosen weights")
    if int(hf.get("n_group") or 1) != 1:
        raise ValueError("this reference has no group step (n_group 1)")
    inv_freq, cos_factor, scale_factor = yarn_frequencies(hf)
    dense, moe_tree = params.get("layers_dense"), params["layers"]

    def shapes(tree, *names):
        return tuple(tuple(tree[m].shape) for m in names)

    def attn(tree):
        return tuple(dict(
            heads=int(hf["num_attention_heads"]), nope=nope, rope=rope,
            vdim=int(hf["v_head_dim"]), rank=int(hf["kv_lora_rank"]), eps=eps,
            scale=(nope + rope) ** -0.5 * scale_factor,
            cos_factor=cos_factor,
            shapes=shapes(tree, "q_a", "q_b", "kv_a", "kv_b", "o")).items())

    out = {
        "inv_freq": inv_freq,
        "hyper": (int(hf["hc_sinkhorn_iters"]), float(hf["hc_eps"]),
                  float(hf["mhc_h_res_clamp_min"]),
                  float(hf["mhc_h_res_clamp_max"])),
        "moe": (attn(moe_tree), tuple(dict(
            top_k=int(hf["num_experts_per_tok"]),
            first=int(hf.get("first_routed_expert", 0)),
            scaling=float(hf["routed_scaling_factor"]), eps=eps,
            shapes=shapes(moe_tree, "moe_gate_up", "moe_down",
                          "shared_gate_up", "shared_down")).items())),
    }
    if dense is not None:
        out["dense"] = (attn(dense), tuple(dict(
            eps=eps, shapes=shapes(dense, "gate_up", "down")).items()))
    return out


def logprobs(hf_config: dict, params: dict, ids: list[int], lower=None,
             first_row: int = 0):
    """(log-softmax of the next-token logits at positions ``first_row`` on of
    ``ids`` over the held vocabulary, ``[T - first_row, vocab]`` float32; each
    of those positions' narrowest router gap over the expert layers), as
    numpy.

    ``lower="fp8"`` passes the streams and the activations between operations
    (where the served path holds bf16) through float8_e4m3 and keeps every
    operation float32: the nearest precision below the configuration's bf16,
    the reading that has to fail ``compare``.  ``lower="coef_bf16"`` is
    ``"bf16"`` with the hyper-connections' coefficients computed in bfloat16
    where the configuration states float32.  ``lower="bf16"`` rounds the
    streams and
    the activations between operations to bfloat16 where the served path does
    and keeps every operation float32: what rounding alone, at the
    configuration's own precision, does to the log-probabilities.
    ``lower="static_coef"`` is float32 with the hyper-connections' dynamic
    term dropped, ``lower="expert_shift"`` float32 with every held expert
    taken for its neighbour (the tree's first expert read as index
    ``first_routed_expert + 1``): planted faults, which have to fail
    ``compare``."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    hf = hf_config
    if lower == "expert_shift":
        hf, lower = dict(hf, first_routed_expert=int(
            hf.get("first_routed_expert", 0)) + 1), None
    st = settings(hf, params)
    eps = float(hf.get("rms_norm_eps", 1e-6))
    n_dense = int(hf.get("first_k_dense_replace", 0))
    n_layers, n = int(hf["num_hidden_layers"]), int(hf["hc_mult"])
    dense, moe_tree = params.get("layers_dense"), params["layers"]
    head = params["lm_head"]
    t = len(ids)
    tie = jnp.full((t,), jnp.inf)
    inv_freq = jnp.asarray(st["inv_freq"])
    with jax.default_matmul_precision("highest"):
        x = params["embed"][jnp.asarray(ids, jnp.int32)].astype(jnp.float32)
        x = jnp.broadcast_to(x[:, None, :], (t, n, x.shape[-1]))
        for i in range(n_layers):
            kind = "dense" if i < n_dense else "moe"
            tree = dense if i < n_dense else moe_tree
            lp = _layer_tree(tree, i if i < n_dense else i - n_dense)
            x, gap = _layer_fn(kind, st["hyper"], *st[kind], lower)(
                x, lp, inv_freq)
            tie = jnp.minimum(tie, gap)
        out = _head_fn(eps, tuple(head.shape), lower)(
            _act(jnp.sum(x[first_row:], axis=1), lower),
            params["final_norm"], _plane(head))
    return np.asarray(out, np.float32), np.asarray(tie[first_row:],
                                                   np.float32)


def compare(ref, prompt_len: int, tokens: list[int],
            server_logprobs: list[float]) -> dict:
    """Per emitted position: the server's chosen-token log-probability
    against the reference's, and how far the chosen token's reference logit
    trails the reference's argmax.  The MEAN error over all positions, and
    the largest of each over the positions whose narrowest router gap is at
    least ROUTER_TIE.  ``prompt_len`` counts from the first row ``ref``
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
    mean = float(np.mean(errs)) if errs else 0.0
    ok = (len(tokens) == len(server_logprobs) and len(tokens) > 0
          and len(kept) >= KEEP_SHARE * len(tokens)
          and lp_err <= LOGPROB_TOL and margin <= ARGMAX_MARGIN_TOL
          and mean <= MEAN_TOL)
    return {"ok": bool(ok), "logprob_err": lp_err, "argmax_margin": margin,
            "logprob_err_mean": mean,
            "router_ties_left_out": len(tokens) - len(kept),
            "logprob_tol": LOGPROB_TOL, "margin_tol": ARGMAX_MARGIN_TOL,
            "mean_tol": MEAN_TOL, "router_tie": ROUTER_TIE,
            "errors": [round(e, 4) for e in errs],
            "gaps": [round(g, 6) for g in gaps]}
