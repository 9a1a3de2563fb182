"""The plain reference for ``model_type: glm5_next_text`` (zai-org GLM-5.3):
float32 ``jax.numpy``, matmul precision "highest", the full forward over the
whole sequence.  No cache, no pool, no recurrent-state buffers, no chunked
recurrence, no absorbed products, no kernels, no batching: the recurrence is
a ``lax.scan`` over tokens, a query's selection is ``lax.top_k`` of its block
scores.  It imports nothing of the program: it reads the HF config keys
itself and unpacks the SERVED int4 planes by its own code, a layer (and inside
an expert layer an expert) at a time, attention a block of queries and a group
of heads at a time, so that 13k positions fit beside the loaded engine at the
published widths.

The residual path is ``n = hc_mult`` streams ``X in R^{n x C}`` a token; the
embedding is copied into all n, EVERY sublayer ``F`` (a mixer; the dense MLP
of layers ``< first_k_dense_replace``; the expert layer of the others) is
wrapped in a manifold-constrained hyper-connection, and after the last layer
the streams are summed, then the final norm and the head.  For a token::

    v      = vec(X) / sqrt(mean(vec(X)^2) + hc_eps)
    Hpre   = sigmoid(a_pre (v P_pre) + b_pre);  Hpost = 2 sigmoid(a_post (v P_post) + b_post)
    M0     = exp(a_res mat(v P_res) + b_res)            no clamp (none published)
    hc_sinkhorn_iters times:  M <- M / (colsum(M) + hc_eps);  M <- M / (rowsum(M) + hc_eps)
    h  = sum_j Hpre[j] X[j];   X'[i] = sum_j M[i, j] X[j] + Hpost[i] F(RMSNorm(h))

(the tree's ``hc_<sub>_proj`` / ``_scale`` / ``_bias`` as references/xing4_0.py
reads them).  The mixers, by ``layer_types``, with ``x`` the normed mix:

- ``linear_attention``: Kimi Delta Attention (arXiv:2510.26692),
  ``linear_attn_config``'s H heads of D.  ``[q~ | k~ | v~ | f1 | g1 | b~] = x
  W_in``; ``q = l2norm_head(silu(conv(q~)))``, ``k`` likewise, ``v =
  silu(conv(v~))``, ``conv`` a causal depthwise convolution over the last
  ``short_conv_kernel_size`` positions, no bias (tap j of ``conv_w`` weighs
  the input K-1-j positions back); ``g = gate_lower_bound sigmoid(exp(A_log)[h]
  (f1 W_f2 + dt_bias))``; ``b = sigmoid(b~)``; a head's state ``S`` [D keys, D
  values] float32 starts at zero and, a token at a time: ``S' = diag(exp(g))
  S``; ``S = S' + b k (v - S'^T k)^T``; ``o = S^T q D^-1/2``; out =
  ``(rmsnorm_head(o) w_o * sigmoid(g1 W_g2)) W_o``.
- ``deepseek_sparse_attention``: latent attention with NO rotated slice
  (``qk_rope_head_dim`` 0): ``c_q = RMSNorm(x W_qa)``; ``q_i = c_q W_qb,i``;
  ``c = RMSNorm(x W_kva)``; ``[k_i ; v_i] = c W_kvb,i``; logit
  ``qk_nope_head_dim^-1/2 q_i . k_is``, softmax over the SELECTED ``s``.  The
  indexer: ``q^I_tj`` = head j of ``c_q W^I_q``, ``k^I_s = LayerNorm(x_s
  W^I_k)`` (weight and bias, epsilon 1e-6), ``w_t = x_t W^I_w index_n_heads^-1/2
  index_head_dim^-1/2``; the key of block ``j`` is the MEAN of ``k^I`` over
  positions ``[P j, P j + P)``, ``P = index_kpool``; ``I_tj = sum_h w_th
  relu(q^I_th . key_j)`` for the complete blocks that end before ``t`` (``j <
  floor(t / P)``); the ``index_topk / P`` blocks of largest score are kept
  (all while there are no more; equal scores to the earlier block), their
  positions attended, and ALWAYS the positions ``P floor(t / P) .. t``.
- MLPs: ``W_d (silu(min(x W_g, l)) * clip(x W_u, -l, l))``, ``l =
  swiglu_limit``, dense and experts alike.  Expert layers: ``sigma =
  sigmoid(x W_r)`` in float32 over ALL routed experts of the published model
  (``n_router_experts``); the ``num_experts_per_tok`` largest ``sigma + b``
  are chosen; ``w_e = routed_scaling_factor sigma_e / (sum over the chosen +
  1e-20)``; ``y = sum_e w_e E_e(x) + E_shared(x)``.

Every departure from the published description is a line under ``assumed`` in
the configuration's file.  Not built: the multi-token-prediction layer (a
draft head).

The share.  The configuration is one chip of a deployment that divides the
routed experts and the vocabulary: the tree holds experts
``first_routed_expert .. + n_routed_experts`` and ``vocab_size`` rows.  The
reference computes exactly that share: it routes over the router's full
width, normalizes over all chosen experts, adds the held ones' outputs and
leaves the absent ones' out, and takes the log-softmax over the held
vocabulary slice.

Ties.  Router: ``lax.top_k`` in float32; ``logprobs`` returns every position's
narrowest gap between the last chosen and the first passed-over ``sigma + b``
and ``compare`` leaves the all but exact ties out of its LARGEST-error limits,
counted.  Selection: ``logprobs`` returns how many blocks a position's
sparse-attention layers saw within INDEX_TIE of its selection's edge
(``index_ties``); they stay in.

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

BLOCK = 32
QUERY_BLOCK = 256          # queries attended at a time (memory, not math)
HEAD_GROUP = 16            # heads whose keys and values are live at a time
INDEX_NORM_EPS = 1e-6
L2_EPS = 1e-6

# --- limits ------------------------------------------------------------------
# Set from two readings at the published widths on the chip (PERF.md section
# 6, PR 50; my chip runs), each limit between them.
# (1) The served path, bf16 streams and activations with float32 accumulation;
# router, coefficients, softmax, norms, the KDA state and its decays float32:
# over 32 probes of 8 positions (16 seeds) a probe's MEAN error read
# 0.0053-0.0319 (the four largest 0.0319, 0.0286, 0.0248, 0.0244), its largest
# error 0.0135-0.1464 (a probe's one position an eighth off: a router or a
# rounding decision that fell the other side), the argmax margin at most
# 0.046; at the cell's lengths (scripts/glm53flash_long_context_check.py: a
# 9,216-token prompt in chunks, 64 decoded positions, the same bits alone,
# beside 31 rows and behind two other prompts; two seeds) mean 0.0154 and
# 0.0145, largest 0.0855 and 0.1147, margin 0.0531 and 0.0048, with ~4,900
# blocks inside INDEX_TIE of a selection's edge.  This reference against
# itself with the same places rounded to bf16 (``lower="bf16"``) reads the
# same: playing the server greedily on two probes, mean 0.0286 and 0.0075
# (largest 0.197 and 0.033); teacher-forced at the cell's lengths mean 0.0082
# and 0.0101, largest 0.126 and 0.060.
# (2) The nearest precision below the configuration's bf16, fp8 activations
# (``lower="fp8"``: the streams and every activation through e4m3, the state
# float32): playing the server greedily on two probes, mean 0.1166 and 0.0751
# (largest 0.219 and 0.131, margin 0.301 and 0); teacher-forced at the cell's
# lengths mean 0.0776 and 0.0728, largest 0.250 and 0.277.  Not correct by
# MEAN_TOL in all four (and by LOGPROB_TOL or the margin in three).
# MEAN_TOL 0.048 is the geometric middle of the served path's largest mean
# (0.0319) and fp8's smallest (0.0728): 1.5x of room on either side.
# LOGPROB_TOL 0.25 and ARGMAX_MARGIN_TOL 0.15 stand 1.7x and 2.8x over the
# largest the served path read and catch a gross fault; rounding's own largest
# (0.197) is under the first and fp8's largest errors straddle it.  A position
# whose narrowest router gap is under ROUTER_TIE is left out of the two maxima
# and counted (0-3 of a probe's 8, 13-14 of 64 at the cell's lengths); the
# mean takes all.
LOGPROB_TOL = 0.25          # largest |log-probability error|, kept positions
MEAN_TOL = 0.048            # mean error over ALL positions
ARGMAX_MARGIN_TOL = 0.15    # how far a chosen token may trail the argmax
ROUTER_TIE = 1e-4           # a narrower gap than this leaves a position out
KEEP_SHARE = 0.25           # fewer kept positions than this share fails
INDEX_TIE = 2.0 ** -8       # of the spread of a query's visible scores


def dequantize(data, scales, shape):
    """One served sym_int4 plane -> float32 ``shape`` = [in, out]."""
    import jax.numpy as jnp

    half = BLOCK // 2
    blocks = data.shape[0] // half
    d = data.reshape(blocks, half, data.shape[1])
    codes = jnp.concatenate([d & 0x0F, d >> 4], axis=1).astype(jnp.float32)
    w = (codes - 8.0) * scales.astype(jnp.float32)[:, None, :]
    return w.reshape(blocks * BLOCK, data.shape[1])[: shape[0], : shape[1]]


def _act(x, lower):
    """An activation (or the streams) as the served path holds it between
    operations."""
    import jax.numpy as jnp

    if lower == "bf16":
        return x.astype(jnp.bfloat16).astype(jnp.float32)
    if lower == "fp8":
        return x.astype(jnp.float8_e4m3fn).astype(jnp.float32)
    return x


def _rms_norm(x, w, eps):
    import jax
    import jax.numpy as jnp

    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * w


def _layer_norm(x, w, b, eps):
    import jax
    import jax.numpy as jnp

    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, axis=-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * w + b


# --- the hyper-connection ------------------------------------------------------

def hyper_coefficients(x, proj, scale, bias, iters: int, eps: float):
    """(Hpre [T, n], Hpost [T, n], Hres [T, n, n]) of the streams ``x``
    [T, n, C], the module docstring's equations in their order."""
    import jax
    import jax.numpy as jnp

    t, n, c = x.shape
    flat = x.reshape(t, n * c)
    v = flat * jax.lax.rsqrt(jnp.mean(flat * flat, axis=-1, keepdims=True)
                             + eps)
    raw = v @ proj.T
    pre = jax.nn.sigmoid(scale[0] * raw[:, :n] + bias[:n])
    post = 2 * jax.nn.sigmoid(scale[1] * raw[:, n:2 * n] + bias[n:2 * n])
    m = jnp.exp((scale[2] * raw[:, 2 * n:] + bias[2 * n:]).reshape(t, n, n))
    for _ in range(iters):
        m = m / (jnp.sum(m, axis=1, keepdims=True) + eps)
        m = m / (jnp.sum(m, axis=2, keepdims=True) + eps)
    return pre, post, m


def _connect(x, lp, sub: str, hyper: tuple, lower, f):
    """One wrapped sublayer: ``f(h [T, C]) -> (F(h), extra)``; returns (X',
    extra)."""
    import jax.numpy as jnp

    pre, post, res = hyper_coefficients(
        x, lp[f"hc_{sub}_proj"], lp[f"hc_{sub}_scale"], lp[f"hc_{sub}_bias"],
        *hyper)
    h = _act(jnp.einsum("tj,tjc->tc", pre, x), lower)
    out, extra = f(h)
    new = (jnp.einsum("tij,tjc->tic", res, x)
           + post[:, :, None] * out[:, None, :])
    return _act(new, lower), extra


# --- Kimi Delta Attention -------------------------------------------------------

def recurrence(q, k, v, g, b, state=None):
    """The delta rule token by token: q, k, v, g [T, H, D], b [T, H], all
    float32; the state [H, D keys, D values] starts at zero.  Returns (o [T,
    H, D] unscaled, the last state)."""
    import jax
    import jax.numpy as jnp

    _, heads, d = q.shape
    if state is None:
        state = jnp.zeros((heads, d, d), jnp.float32)

    def step(s, xs):
        q_t, k_t, v_t, g_t, b_t = xs
        s = jnp.exp(g_t)[:, :, None] * s
        r = jnp.einsum("hkv,hk->hv", s, k_t)
        s = s + (b_t[:, None] * k_t)[:, :, None] * (v_t - r)[:, None, :]
        return s, jnp.einsum("hkv,hk->hv", s, q_t)

    state, o = jax.lax.scan(step, state, (q, k, v, g, b))
    return o, state


def _kda(h_in, lp, *, heads, d, kernel, lower_bound, eps, shapes, lower):
    import jax
    import jax.numpy as jnp

    in_s, fb_s, gb_s, o_s = shapes
    t = h_in.shape[0]
    hd = heads * d
    x = _act(_rms_norm(h_in, lp["attn_norm"], eps), lower)
    z = _act(x @ dequantize(*lp["in_proj"], in_s), lower)
    qkv, f1 = z[:, :3 * hd], z[:, 3 * hd:3 * hd + d]
    g1, b_raw = z[:, 3 * hd + d:3 * hd + 2 * d], z[:, 3 * hd + 2 * d:]
    padded = jnp.concatenate([jnp.zeros((kernel - 1, 3 * hd)), qkv], axis=0)
    conv = sum(padded[j:j + t] * lp["conv_w"][j] for j in range(kernel))
    qkv = jax.nn.silu(conv).reshape(t, 3, heads, d)

    def l2(a):
        return a * jax.lax.rsqrt(jnp.sum(a * a, axis=-1, keepdims=True)
                                 + L2_EPS)

    q, k, v = l2(qkv[:, 0]), l2(qkv[:, 1]), qkv[:, 2]
    f = _act(f1 @ dequantize(*lp["f_b"], fb_s), lower).reshape(t, heads, d)
    g = lower_bound * jax.nn.sigmoid(
        jnp.exp(lp["A_log"])[None, :, None]
        * (f + lp["dt_bias"].reshape(heads, d)))
    b = jax.nn.sigmoid(b_raw)
    gate = jax.nn.sigmoid(_act(g1 @ dequantize(*lp["g_b"], gb_s), lower))
    o, _ = recurrence(q, k, v, g, b)
    o = _rms_norm(o * d ** -0.5, lp["o_norm"], eps).reshape(t, hd) * gate
    return _act(_act(o, lower) @ dequantize(*lp["o"], o_s), lower)


# --- sparse latent attention -----------------------------------------------------

def pooled_selection(h, c_q, ix, *, n_heads, dim, top, pool, shapes, lower):
    """(the positions each query attends, [T, T] bool; each query's count of
    blocks inside INDEX_TIE of its selection's edge)."""
    import jax
    import jax.numpy as jnp

    wq_s, wk_s = shapes
    t = h.shape[0]
    pos = jnp.arange(t)
    q = _act(c_q @ dequantize(*ix["wq_b"], wq_s), lower).reshape(
        t, n_heads, dim)
    k = _act(_layer_norm(_act(h @ dequantize(*ix["wk"], wk_s), lower),
                         ix["k_norm"], ix["k_norm_bias"], INDEX_NORM_EPS),
             lower)
    w = (h @ ix["w_proj"]) * (n_heads ** -0.5 * dim ** -0.5)
    nb = t // pool
    tail = (pos[None, :] >= (pos // pool * pool)[:, None]) & (
        pos[None, :] <= pos[:, None])
    if nb == 0:
        return tail, jnp.zeros((t,), jnp.int32)
    keys = _act(jnp.mean(k[:nb * pool].reshape(nb, pool, dim), axis=1), lower)
    keep = min(top // pool, nb)
    masks, ties = [], []
    for q0 in range(0, t, QUERY_BLOCK):
        q1 = min(q0 + QUERY_BLOCK, t)
        dots = jnp.einsum("qjd,kd->qjk", q[q0:q1], keys)
        score = jnp.einsum("qj,qjk->qk", w[q0:q1], jnp.maximum(dots, 0.0))
        seen = jnp.arange(nb)[None, :] < (pos[q0:q1] // pool)[:, None]
        score = jnp.where(seen, score, -jnp.inf)
        vals, idx = jax.lax.top_k(score, keep)
        rows = jnp.arange(q1 - q0)[:, None]
        chosen = jnp.zeros((q1 - q0, nb), bool).at[rows, idx].set(
            vals > -jnp.inf)
        masks.append(jnp.repeat(chosen, pool, axis=1))
        hi = jnp.max(score, axis=-1)
        lo = jnp.min(jnp.where(seen, score, jnp.inf), axis=-1)
        near = (jnp.abs(score - vals[:, -1:])
                <= INDEX_TIE * (hi - lo)[:, None]) & seen
        ties.append(jnp.where(pos[q0:q1] // pool > keep,
                              jnp.sum(near, axis=-1) - 1, 0))
    mask = jnp.concatenate(masks, 0)
    mask = jnp.pad(mask, ((0, 0), (0, t - mask.shape[1])))
    return mask | tail, jnp.concatenate(ties, 0).astype(jnp.int32)


def _mla(h_in, lp, ix, *, heads, nope, vdim, rank, eps, shapes, index, lower):
    import jax
    import jax.numpy as jnp

    qa_s, qb_s, kva_s, kvb_s, o_s = shapes
    t = h_in.shape[0]
    h = _act(_rms_norm(h_in, lp["attn_norm"], eps), lower)
    c_q = _act(_rms_norm(_act(h @ dequantize(*lp["q_a"], qa_s), lower),
                         lp["q_a_norm"], eps), lower)
    chosen, near = pooled_selection(h, c_q, ix, lower=lower, **dict(index))
    q = _act(c_q @ dequantize(*lp["q_b"], qb_s), lower).reshape(
        t, heads, nope)
    c = _act(_rms_norm(_act(h @ dequantize(*lp["kv_a"], kva_s),
                            lower)[:, :rank], lp["kv_a_norm"], eps), lower)
    w_kvb = dequantize(*lp["kv_b"], kvb_s).reshape(rank, heads, nope + vdim)
    scale = nope ** -0.5
    groups = []
    for h0 in range(0, heads, HEAD_GROUP):
        h1 = min(h0 + HEAD_GROUP, heads)
        kv = _act(jnp.einsum("tc,chd->thd", c, w_kvb[:, h0:h1]), lower)
        k_n, v = kv[..., :nope], kv[..., nope:]
        outs = []
        for q0 in range(0, t, QUERY_BLOCK):
            q1 = min(q0 + QUERY_BLOCK, t)
            s = jnp.einsum("qhd,khd->hqk", q[q0:q1, h0:h1], k_n[:q1]) * scale
            p = jax.nn.softmax(
                jnp.where(chosen[q0:q1, :q1][None], s, -jnp.inf), axis=-1)
            outs.append(jnp.einsum("hqk,khd->qhd", p, v[:q1]))
        groups.append(jnp.concatenate(outs, axis=0))
    a = _act(jnp.concatenate(groups, axis=1).reshape(t, heads * vdim), lower)
    return _act(a @ dequantize(*lp["o"], o_s), lower), near


# --- MLPs -----------------------------------------------------------------------

def _gated_mlp(h, gate_up, down, width: int, limit, lower):
    import jax
    import jax.numpy as jnp

    g = h @ gate_up[:, :width]
    u = h @ gate_up[:, width:]
    if limit is not None:
        g, u = jnp.minimum(g, limit), jnp.clip(u, -limit, limit)
    return _act(jax.nn.silu(g) * u, lower) @ down


def _dense(h_in, lp, *, eps, limit, shapes, lower):
    gu_s, d_s = shapes
    h = _act(_rms_norm(h_in, lp["mlp_norm"], eps), lower)
    return _act(_gated_mlp(h, dequantize(*lp["gate_up"], gu_s),
                           dequantize(*lp["down"], d_s), gu_s[1] // 2, limit,
                           lower), lower)


def _moe(h_in, lp, *, top_k, first, scaling, eps, limit, shapes, lower):
    import jax
    import jax.numpy as jnp

    gu_s, d_s, sgu_s, sd_s = shapes
    h = _act(_rms_norm(h_in, lp["mlp_norm"], eps), lower)
    sigma = jax.nn.sigmoid(h @ lp["router"])                      # [T, E]
    s_sorted, e_idx = jax.lax.top_k(sigma + lp["router_bias"], top_k + 1)
    idx = e_idx[:, :top_k]
    chosen = jnp.take_along_axis(sigma, idx, axis=1)
    w = chosen / (jnp.sum(chosen, -1, keepdims=True) + 1e-20) * scaling
    gap = s_sorted[:, top_k - 1] - s_sorted[:, top_k]
    y = _gated_mlp(h, dequantize(*lp["shared_gate_up"], sgu_s),
                   dequantize(*lp["shared_down"], sd_s), sgu_s[1] // 2, limit,
                   lower)
    held = lp["moe_gate_up"][0].shape[0]
    for e in range(held):
        gate = jnp.sum(jnp.where(idx == first + e, w, 0.0), axis=1)
        out = _gated_mlp(
            h, dequantize(lp["moe_gate_up"][0][e], lp["moe_gate_up"][1][e],
                          gu_s),
            dequantize(lp["moe_down"][0][e], lp["moe_down"][1][e], d_s),
            gu_s[1] // 2, limit, lower)
        y = y + gate[:, None] * _act(out, lower)
    return _act(y, lower), gap


@functools.lru_cache(maxsize=16)
def _layer_fn(mixer: str, ffn: str, hyper: tuple, mix_kw: tuple, ffn_kw: tuple,
              lower):
    """One whole layer, jitted: ``layer(x [T, n, C], lp, kind tree, indexer)
    -> (x', router gap [T], index ties [T])``, both sublayers wrapped."""
    import jax
    import jax.numpy as jnp

    mix_kw, ffn_kw = dict(mix_kw), dict(ffn_kw)

    def layer(x, lp, kp, ix):
        t = x.shape[0]
        both = dict(lp, **kp)
        if mixer == "kda":
            x, near = _connect(x, lp, "attn", hyper, lower, lambda h: (
                _kda(h, both, lower=lower, **mix_kw),
                jnp.zeros((t,), jnp.int32)))
        else:
            x, near = _connect(x, lp, "attn", hyper, lower, lambda h: _mla(
                h, both, ix, lower=lower, **mix_kw))
        if ffn == "dense":
            x, gap = _connect(x, lp, "mlp", hyper, lower, lambda h: (
                _dense(h, lp, lower=lower, **ffn_kw), jnp.full((t,), jnp.inf)))
        else:
            x, gap = _connect(x, lp, "mlp", hyper, lower, lambda h: _moe(
                h, lp, lower=lower, **ffn_kw))
        return x, gap, near

    return jax.jit(layer)


@functools.lru_cache(maxsize=8)
def _head_fn(eps: float, shape: tuple, lower):
    import jax

    def head(x, norm, plane):
        logits = _act(_rms_norm(x, norm, eps), lower) @ dequantize(
            *plane, shape)
        return jax.nn.log_softmax(logits, -1)

    return jax.jit(head)


def _entry(stack: dict, i: int) -> dict:
    """Entry ``i`` of a stacked tree, QTensors as their (data, scales); the
    per-kind sub-trees are left to the caller."""
    out = {}
    for name, leaf in stack.items():
        if isinstance(leaf, dict):
            continue
        if hasattr(leaf, "scales"):
            out[name] = (leaf.data[i], leaf.scales[i])
        else:
            out[name] = leaf[i]
    return out


def _shapes(tree, *names):
    return tuple(tuple(tree[m].shape) for m in names)


def settings(hf: dict) -> dict:
    """What the reference reads of the configuration, refusing what it does
    not compute."""
    if hf.get("topk_method", "noaux_tc") != "noaux_tc":
        raise ValueError("this reference selects on score + bias (noaux_tc)")
    if hf.get("scoring_func", "sigmoid") != "sigmoid":
        raise ValueError("this reference scores with a sigmoid")
    if not hf.get("norm_topk_prob", True):
        raise ValueError("this reference normalizes the chosen weights")
    if int(hf.get("n_group") or 1) != 1:
        raise ValueError("this reference has no group step (n_group 1)")
    if int(hf.get("qk_rope_head_dim") or 0):
        raise ValueError("this reference rotates nothing (qk_rope_head_dim 0)")
    kinds = {"linear_attention": "kda", "deepseek_sparse_attention": "mla"}
    la = hf["linear_attn_config"]
    limit = hf.get("swiglu_limit")
    return {
        "eps": float(hf.get("rms_norm_eps", 1e-6)),
        "mixers": [kinds[t] for t in hf["layer_types"]],
        "n_dense": int(hf.get("first_k_dense_replace", 0)),
        "hyper": (int(hf["hc_sinkhorn_iters"]), float(hf["hc_eps"])),
        "kda": dict(heads=int(la["num_heads"]), d=int(la["head_dim"]),
                    kernel=int(la.get("short_conv_kernel_size", 4)),
                    lower_bound=float(la.get("gate_lower_bound", -5.0))),
        "mla": dict(heads=int(hf["num_attention_heads"]),
                    nope=int(hf["qk_nope_head_dim"]),
                    vdim=int(hf["v_head_dim"]),
                    rank=int(hf["kv_lora_rank"])),
        "index": dict(n_heads=int(hf["index_n_heads"]),
                      dim=int(hf["index_head_dim"]),
                      top=int(hf["index_topk"]),
                      pool=int(hf.get("index_kpool", 1))),
        "limit": None if limit is None else float(limit),
        "moe": dict(top_k=int(hf["num_experts_per_tok"]),
                    first=int(hf.get("first_routed_expert", 0)),
                    scaling=float(hf["routed_scaling_factor"])),
    }


def logprobs(hf_config: dict, params: dict, ids: list[int], lower=None,
             first_row: int = 0):
    """(log-softmax of the next-token logits at positions ``first_row`` on of
    ``ids`` over the held vocabulary, ``[T - first_row, vocab]`` float32; each
    of those positions' narrowest router gap over the expert layers; each of
    those positions' count of blocks inside INDEX_TIE of a selection's edge),
    as numpy.

    ``lower="fp8"`` passes the streams and the activations between operations
    (where the served path holds bf16) through float8_e4m3 and keeps every
    operation, the KDA state among them, float32: the nearest precision below
    the configuration's bf16, the reading that has to fail ``compare``.
    ``lower="bf16"`` rounds the same places to bfloat16: what rounding alone,
    at the configuration's own precision, does."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    st = settings(hf_config)
    eps, n = st["eps"], int(hf_config["hc_mult"])
    t = len(ids)
    tie = jnp.full((t,), jnp.inf)
    edge = jnp.zeros((t,), jnp.int32)
    seen = {}
    with jax.default_matmul_precision("highest"):
        x = params["embed"][jnp.asarray(ids, jnp.int32)].astype(jnp.float32)
        x = jnp.broadcast_to(x[:, None, :], (t, n, x.shape[-1]))
        for i, mixer in enumerate(st["mixers"]):
            dense = i < st["n_dense"]
            group = "layers_dense" if dense else "layers"
            tree = params[group]
            lp = _entry(tree, i if dense else i - st["n_dense"])
            j = seen.get((group, mixer), 0)
            seen[group, mixer] = j + 1
            kp = _entry(tree[mixer], j)
            ix = _entry(tree["indexer"], j) if mixer == "mla" else None
            if mixer == "kda":
                mix_kw = dict(st["kda"], eps=eps, shapes=_shapes(
                    tree["kda"], "in_proj", "f_b", "g_b", "o"))
            else:
                mix_kw = dict(st["mla"], eps=eps, shapes=_shapes(
                    tree["mla"], "q_a", "q_b", "kv_a", "kv_b", "o"),
                    index=tuple(dict(st["index"], shapes=_shapes(
                        tree["indexer"], "wq_b", "wk")).items()))
            if dense:
                ffn_kw = dict(eps=eps, limit=st["limit"],
                              shapes=_shapes(tree, "gate_up", "down"))
            else:
                ffn_kw = dict(st["moe"], eps=eps, limit=st["limit"],
                              shapes=_shapes(tree, "moe_gate_up", "moe_down",
                                             "shared_gate_up", "shared_down"))
            x, gap, near = _layer_fn(
                mixer, "dense" if dense else "moe", st["hyper"],
                tuple(mix_kw.items()), tuple(ffn_kw.items()), lower)(
                x, lp, kp, ix)
            tie = jnp.minimum(tie, gap)
            edge = edge + near
        head = params["lm_head"]
        out = _head_fn(eps, tuple(head.shape), lower)(
            _act(jnp.sum(x[first_row:], axis=1), lower),
            params["final_norm"], (head.data, head.scales))
    return (np.asarray(out, np.float32),
            np.asarray(tie[first_row:], np.float32),
            np.asarray(edge[first_row:], np.int64))


def compare(ref, prompt_len: int, tokens: list[int],
            server_logprobs: list[float]) -> dict:
    """Per emitted position: the server's chosen-token log-probability
    against the reference's, and how far the chosen token's reference logit
    trails the reference's argmax.  The MEAN error over all positions, and
    the largest of each over the positions whose narrowest router gap is at
    least ROUTER_TIE; ``index_ties`` counts the blocks inside INDEX_TIE of a
    selection's edge over the emitted positions (they stay in).
    ``prompt_len`` counts from the first row ``ref`` holds."""
    import numpy as np

    rows, tie, edge = ref
    errs, margins, gaps, near = [], [], [], 0
    for j, (tok, lp) in enumerate(zip(tokens, server_logprobs)):
        row = rows[prompt_len - 1 + j]
        errs.append(abs(float(row[tok]) - float(lp)))
        margins.append(float(np.max(row) - row[tok]))
        gaps.append(float(tie[prompt_len - 1 + j]))
        near += int(edge[prompt_len - 1 + j])
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
            "index_ties": near,
            "logprob_tol": LOGPROB_TOL, "margin_tol": ARGMAX_MARGIN_TOL,
            "mean_tol": MEAN_TOL, "router_tie": ROUTER_TIE,
            "index_tie": INDEX_TIE,
            "errors": [round(e, 4) for e in errs],
            "gaps": [round(g, 6) for g in gaps]}
