"""Manifold-constrained hyper-connections (mHC: Xie et al., arXiv:2512.24880,
over Zhu et al., Hyper-Connections, arXiv:2409.19606): a residual path of
``n = cfg.hc_mult`` streams a token, ``X in R^{n x C}``.  Every sublayer ``F``
(attention, MLP, expert layer) reads ONE learned mix of the streams and
writes back through two more.  For a token::

    v      = vec(X) / sqrt(mean(vec(X)^2) + eps)         one RMS over n*C values
    Hpre~  = a_pre  * (v P_pre)  + b_pre                 [n]
    Hpost~ = a_post * (v P_post) + b_post                [n]
    Hres~  = a_res  * mat(v P_res) + b_res               [n, n]
    Hpre = sigmoid(Hpre~);  Hpost = 2 sigmoid(Hpost~)
    M = exp(clip(Hres~, lo, hi));  ``iters`` times:
        M <- M / (colsum(M) + eps);  M <- M / (rowsum(M) + eps);   Hres = M
    h  = sum_j Hpre[j] X[j]                              what F's own norm sees
    X'[i] = sum_j Hres[i, j] X[j] + Hpost[i] F(h)

``Hres`` is, to ``iters`` rounds, doubly stochastic: the mix cannot amplify.

Layout.  The streams LEAD: ``X`` is ``[n, B, T, C]`` in the activations'
type, so a stream is a whole ``[B, T, C]`` slab tiled as every other model's
``x`` is (as ``[B, T, n, C]`` a decode tick's ``[n, C]`` minor tile would be
padded from 4 rows to 16).  The coefficients are float32 with the TOKENS
minor, ``[n, B, T]`` / ``[n, n, B, T]``: the Sinkhorn rounds are then
elementwise over whole lanes, their 4-term sums written term by term (no
reduction whose order XLA picks by the block's shape, as ``_sum_chosen`` in
models/decoder.py), and fuse into one loop.  The three projections are one
float32 plane ``proj`` ``[n + n + n*n, n*C]`` (rows: pre, post, res
row-major; columns stream-major, ``vec(X)``'s order), multiplied at precision
"highest" as a router is; ``scale`` ``[3]`` and ``bias`` ``[n + n + n*n]``
follow the same order.  ``(v P) = (vec(X) P) / rms``: the product is taken on
the streams as they are and the RMS divides its ``n + n + n*n`` outputs.

Tracing: everything here runs in scope ``layer/mhc``, the coefficients in
``mhc/coef`` and the mixes in ``mhc/mix`` nested in it (perfbench/SPANS_mhc.md).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from ipex_llm_tpu.ops.norms import _rows


def expand(x, n: int):
    """An embedding ``[B, T, C]`` copied into all ``n`` streams."""
    return jnp.broadcast_to(x[None], (n, *x.shape))


def _terms(parts):
    total = parts[0]
    for p in parts[1:]:
        total = total + p
    return total


def collapse(x):
    """The streams summed, term by term: what the final norm sees."""
    return _terms([x[j].astype(jnp.float32)
                   for j in range(x.shape[0])]).astype(x.dtype)


def sinkhorn(logits, iters: int, eps: float, clamp):
    """``logits`` [n, n, ...] float32 (row i, column j leading) -> the
    Sinkhorn-normalised ``exp(clip(logits))``: ``iters`` rounds of columns
    then rows, ``eps`` in both denominators."""
    n = logits.shape[0]
    m = jnp.exp(jnp.clip(logits, clamp[0], clamp[1]))
    for _ in range(iters):
        m = m / (_terms([m[i] for i in range(n)]) + eps)[None]
        m = m / (_terms([m[:, j] for j in range(n)]) + eps)[:, None]
    return m


@jax.named_scope("layer/mhc")
@jax.named_scope("mhc/coef")
def coefficients(cfg, lp, sub: str, x):
    """Sublayer ``sub``'s (``attn`` | ``mlp``: the layer's ``hc_<sub>_proj``,
    ``_scale``, ``_bias`` of ``lp``) coefficients (Hpre [n, B, T], Hpost
    [n, B, T], Hres [n, n, B, T]) from its input streams ``x`` [n, B, T, C],
    float32."""
    proj, scale, bias = (lp[f"hc_{sub}_{part}"]
                         for part in ("proj", "scale", "bias"))
    n, b, t, c = x.shape
    xf = x.astype(jnp.float32)
    # a stream's sum of squares behind the barrier of ops/norms.py: on the
    # chip XLA orders a row reduction by how the leading dimensions factor,
    # and a token's coefficients must not depend on the block it rides in
    # ([1, 512] alone, [8, 64] beside seven other prompts)
    rows = _rows(xf)
    sq = jnp.sum(rows * rows, axis=-1).reshape(n, b, t)
    mean_sq = _terms([sq[j] for j in range(n)]) / (n * c)
    raw = jnp.einsum("nbtc,knc->kbt", xf, proj.reshape(-1, n, c),
                     precision=jax.lax.Precision.HIGHEST,
                     preferred_element_type=jnp.float32)
    raw = raw * jax.lax.rsqrt(mean_sq + cfg.hc_eps)[None]
    gain = jnp.concatenate([jnp.broadcast_to(scale[i], (w,))
                            for i, w in enumerate((n, n, n * n))])
    raw = raw * gain[:, None, None] + bias[:, None, None]
    pre = jax.nn.sigmoid(raw[:n])
    post = 2.0 * jax.nn.sigmoid(raw[n:2 * n])
    res = sinkhorn(raw[2 * n:].reshape(n, n, b, t), cfg.hc_sinkhorn_iters,
                   cfg.hc_eps, cfg.hc_res_clamp)
    return pre, post, res


@jax.named_scope("layer/mhc")
@jax.named_scope("mhc/mix")
def pre_mix(coef, x):
    """``h = sum_j Hpre[j] X[j]`` [B, T, C]: the sublayer's input."""
    pre = coef[0]
    return _terms([pre[j][..., None] * x[j].astype(jnp.float32)
                   for j in range(x.shape[0])]).astype(x.dtype)


@jax.named_scope("layer/mhc")
@jax.named_scope("mhc/mix")
def post_mix(coef, x, out):
    """``X'[i] = sum_j Hres[i, j] X[j] + Hpost[i] F(h)`` [n, B, T, C]."""
    _, post, res = coef
    n = x.shape[0]
    xf = [x[j].astype(jnp.float32) for j in range(n)]
    of = out.astype(jnp.float32)
    return jnp.stack([
        _terms([res[i, j][..., None] * xf[j] for j in range(n)])
        + post[i][..., None] * of for i in range(n)]).astype(x.dtype)
