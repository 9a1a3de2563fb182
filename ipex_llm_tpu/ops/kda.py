"""Kimi Delta Attention (Kimi Linear, arXiv:2510.26692) for the decoder: the
mixer of a ``linear_attention`` layer (``models/decoder.py`` ``run_layers``;
``cfg.mixer_types``).  With ``x`` the normed input of one token, ``H`` heads
of ``D`` (key and value dims alike):

    [q~ | k~ | v~ | f1 | g1 | b~] = in_proj(x)
    q = l2norm_head(silu(conv_K(q~)));  k likewise;  v = silu(conv_K(v~))
    g = lower * sigmoid(exp(A_log)[h] * (f_b(f1) + dt_bias))   in (lower, 0)
    b = sigmoid(b~)                                            a head
    S' = diag(exp(g)) S;   S = S' + b k (v - S'^T k)^T         S in R^{D x D}
    o = S^T q * D^-1/2
    y = o_proj(rmsnorm_head(o) * sigmoid(g_b(g1)))

``conv_K`` is a causal depthwise convolution over the last K positions (no
bias); the gate is the BOUNDED form (the config names ``gate_lower_bound``
and nothing else).  State and decay are float32.

The recurrence exists twice on the served path:

- :func:`step`: one token a row (a decode row): the state read, decayed,
  corrected by its one rank-1 term and written, ``kda/step``.
- :func:`chunk`: a prefill block, ``kda/chunk``: the CHUNKWISE form on blocks
  of ``CHUNK`` tokens (:func:`chunk_block` has the arithmetic; the chip runs
  it as ``ops/pallas/kda_chunk.py``, everything else under ``vmap``).  With
  ``G`` the cumulative log-decay inside a block and ``kb = b k``:
  ``L[t, s] = sum_i k[t, i] kb[s, i] exp(G[t, i] - G[s, i])`` for ``s < t``;
  ``U = (I + L)^-1 (V - (K exp(G)) S0)``;
  ``O = (Q exp(G)) S0 + tril(P) U`` with ``P`` as ``L`` with ``q`` for ``k``
  and the diagonal kept; ``S1 = diag(exp(G_C)) S0 + (kb exp(G_C - G))^T U``.

A token's result must not depend on how its prompt was cut or on the other
rows of its tick.  A block's arithmetic sees that block's ``CHUNK`` tokens and
the state before them and nothing else, so it is enough that every prompt is
cut at multiples of ``CHUNK`` (its last piece apart; ``cfg.chunk_grid``,
serving/engine.py ``_mixed_step``): the same blocks then meet the same
operations whatever tick they ride in.  Padding (``valid`` false) has ``g =
0`` and ``kb = 0``: it leaves the state as it is.

State lives beside the paged pool in the cache object (``kv.py``
``recurrent_state``): ``kda_s`` ``[KDA layers, R, H, D, D]`` float32 and
``kda_conv`` ``[KDA layers, R, K-1, 3 H D]`` bf16 (the conv inputs before the
row's next position), one slot a ROW.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from ipex_llm_tpu.ops import linear as linear_ops
from ipex_llm_tpu.ops.norms import _rows

CHUNK = 64          # tokens a block of the chunked form; the prompt grid
SUB = 16            # rows whose pairwise decays share one reference
# exp() of a sub-block's span stays finite: SUB * |gate_lower_bound| <= this
_EXP_CLIP = 80.0
_L2_EPS = 1e-6
_HI = jax.lax.Precision.HIGHEST


def _dot(a, b):
    return jnp.dot(a, b, precision=_HI, preferred_element_type=jnp.float32)


def _dot_nt(a, b):
    """``a @ b.T``."""
    return jax.lax.dot_general(a, b, (((1,), (1,)), ((), ())), precision=_HI,
                               preferred_element_type=jnp.float32)


def _iota(shape, axis):
    return jax.lax.broadcasted_iota(jnp.int32, shape, axis)


def chunk_block(s0, q, k, kb, v, g):
    """One block of the chunkwise delta rule for one head: ``s0`` [D, D]
    (keys x values), ``q``/``k``/``kb``/``v``/``g`` [C, D] float32 (``kb`` is
    ``b k``; ``g`` the log-decay, <= 0; both 0 at padding).  Two-dimensional
    operations only, so that the SAME function is the body of the chip's
    kernel (ops/pallas/kda_chunk.py) and, under ``vmap``, the XLA form.
    Returns (o [C, D] unscaled, s1 [D, D]).

    Pairwise decays ``exp(G[t] - G[s])`` are never formed as a quotient of
    two cumulative products (``exp(-G)`` overflows float32 after a dozen
    strongly decayed tokens): each sub-block of ``SUB`` rows takes the
    cumulative decay before its first row as the reference, so the left
    factor is ``exp(<= 0)`` and the right one at most ``exp(SUB x |lower
    bound|)``."""
    c, d = q.shape
    n_sub = c // SUB
    tri = (_iota((c, c), 0) >= _iota((c, c), 1)).astype(jnp.float32)
    gc = _dot(tri, g)                                    # inclusive cumsum
    decay = jnp.exp(gc)
    ks = _dot(jnp.concatenate([k * decay, q * decay], axis=0), s0)
    rhs, qs = v - ks[:c], ks[c:]

    # A[t, s] (s < t) and P[t, s] (s <= t), a sub-block of rows at a time
    a_rows, p_rows = [], []
    for i in range(n_sub):
        r0, r1 = i * SUB, (i + 1) * SUB
        ref = gc[r0 - 1:r0] if i else jnp.zeros((1, d), jnp.float32)
        left = jnp.exp(gc[r0:r1] - ref)
        right = kb * jnp.exp(jnp.minimum(ref - gc, _EXP_CLIP))
        ap = _dot_nt(jnp.concatenate([k[r0:r1] * left, q[r0:r1] * left],
                                     axis=0), right)     # [2 SUB, C]
        row = _iota((SUB, c), 0) + r0
        col = _iota((SUB, c), 1)
        a_rows.append(jnp.where(col < row, ap[:SUB], 0.0))
        p_rows.append(jnp.where(col <= row, ap[SUB:], 0.0))

    # (I + A) U = rhs by block forward substitution; a diagonal block's
    # inverse by the Neumann product of its nilpotent part (N^SUB = 0)
    eye = (_iota((SUB, SUB), 0) == _iota((SUB, SUB), 1)).astype(jnp.float32)
    u_blocks = []
    for i in range(n_sub):
        r0, r1 = i * SUB, (i + 1) * SUB
        n_ = -a_rows[i][:, r0:r1]
        inv = eye + n_
        span = 2
        while span < SUB:
            n_ = _dot(n_, n_)
            inv = inv + _dot(inv, n_)
            span *= 2
        r = rhs[r0:r1]
        if i:
            r = r - _dot(a_rows[i][:, :r0], jnp.concatenate(u_blocks, axis=0))
        u_blocks.append(_dot(inv, r))
    u = jnp.concatenate(u_blocks, axis=0)                # [C, D]
    o = qs + _dot(jnp.concatenate(p_rows, axis=0), u)
    last = gc[c - 1:c]
    s1 = jnp.exp(last).T * s0 + _dot((kb * jnp.exp(last - gc)).T, u)
    return o, s1


def _chunk_xla(s0, q, k, kb, v, g):
    """:func:`chunk_block` over [B, H, T, D] operands (T a whole number of
    blocks) and states [B, H, D, D]: blocks in turn, rows and heads under
    ``vmap``."""
    b, h, t, d = q.shape
    blocks = tuple(x.reshape(b, h, t // CHUNK, CHUNK, d).transpose(
        2, 0, 1, 3, 4) for x in (q, k, kb, v, g))
    one = jax.vmap(jax.vmap(chunk_block))

    def body(s, xs):
        o, s = one(s, *xs)
        return s, o

    s1, o = jax.lax.scan(body, s0, blocks)
    return o.transpose(1, 2, 0, 3, 4).reshape(b, h, t, d), s1


def chunk(s0, q, k, v, g, beta):
    """The chunkwise recurrence over a prefill block: ``s0`` [B, H, D, D];
    ``q``/``k``/``v``/``g`` [B, T, H, D] float32 and ``beta`` [B, T, H] (``g``
    and ``beta`` 0 at padding).  Returns (o [B, T, H, D] unscaled, s1)."""
    from ipex_llm_tpu.ops import dispatch
    from ipex_llm_tpu.ops.pallas import kda_chunk

    t = q.shape[1]
    pad = -t % CHUNK
    kb = k * beta[..., None]
    ops = [jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0))).transpose(
        0, 2, 1, 3) for x in (q, k, kb, v, g)]
    if dispatch.use_pallas() and kda_chunk.supported(q.shape[-1]):
        o, s1 = kda_chunk.chunk_rows(s0, *ops)
    else:
        o, s1 = _chunk_xla(s0, *ops)
    return o.transpose(0, 2, 1, 3)[:, :t], s1


def step(s, q, k, v, g, beta):
    """One token a row: ``s`` [B, H, D, D]; ``q``/``k``/``v``/``g`` [B, H, D]
    float32, ``beta`` [B, H] (``g`` and ``beta`` 0 for a row that sits the
    step out: its state comes back as it is).  Returns (o [B, H, D]
    unscaled, the new state)."""
    sd = s * jnp.exp(g)[..., None]
    r = jnp.sum(sd * k[..., None], axis=-2)              # S'^T k
    s1 = sd + (beta[..., None] * k)[..., None] * (v - r)[..., None, :]
    return jnp.sum(s1 * q[..., None], axis=-2), s1


def sequential(s0, q, k, v, g, beta):
    """The recurrence token by token over [B, T, H, D] operands: the oracle
    the chunked form is tested against."""
    def body(s, xs):
        o, s = step(s, *xs)
        return s, o

    s1, o = jax.lax.scan(body, s0, tuple(
        jnp.moveaxis(x, 1, 0) for x in (q, k, v, g, beta)))
    return jnp.moveaxis(o, 0, 1), s1


def _l2norm_head(x):
    """``x`` [..., D] over its last axis, behind the barrier of
    ops/norms.py (a reduction must not depend on how the batch factors)."""
    xf = _rows(x)
    return (xf * jax.lax.rsqrt(jnp.sum(xf * xf, axis=-1, keepdims=True)
                               + _L2_EPS)).reshape(x.shape)


def kda_mixer(cfg, lp: dict, u, state: dict, src: dict, layer, valid,
              rows=None, fresh=None):
    """The KDA mixer on the normed input ``u`` [B, T, hidden].

    ``state``: the recurrent state's leaves (``kda_s``, ``kda_conv``), the
    buffer that is WRITTEN; ``src``: the one that is READ (the same dict, or
    the tick's read-only source where the tick reads one buffer and writes
    another); ``layer`` this mixer's index among the KDA layers; ``valid``
    [B, T] the positions that are tokens.  ``rows`` None: batch entry i IS
    state row i (a decode stage: T == 1 takes :func:`step`); ``rows`` [B]: a
    prefill block's map to state rows (R = a pad slot, dropped), ``fresh``
    [B] rows start from a zero state, and the chunked form runs.  Returns
    (out [B, T, hidden], (tokens through the chunked form, rows stepped),
    the state with this layer's slots rewritten)."""
    from ipex_llm_tpu.ops.ssm import causal_conv

    heads, d = cfg.kda_num_heads, cfg.kda_head_dim
    hd = heads * d
    bsz, t, _ = u.shape
    f32 = jnp.float32
    if SUB * abs(cfg.kda_gate_lower_bound) > _EXP_CLIP:
        raise ValueError(
            f"kda: gate_lower_bound {cfg.kda_gate_lower_bound} over {SUB} "
            f"rows passes exp({_EXP_CLIP}): shrink ops/kda.py SUB")

    def read(name):
        if rows is None:
            return jax.lax.dynamic_index_in_dim(src[name], layer, 0, False)
        got = src[name][layer, jnp.clip(rows, 0, src[name].shape[1] - 1)]
        return jnp.where(fresh.reshape((-1,) + (1,) * (got.ndim - 1)), 0, got)

    def write(name, new):
        if rows is None:
            return jax.lax.dynamic_update_index_in_dim(
                state[name], new.astype(state[name].dtype), layer, 0)
        return state[name].at[layer, rows].set(
            new.astype(state[name].dtype), mode="drop")

    with jax.named_scope("kda/proj"):
        z = linear_ops.linear(u, lp["in_proj"])
        qkv, f1 = z[..., :3 * hd], z[..., 3 * hd:3 * hd + d]
        g1 = z[..., 3 * hd + d:3 * hd + 2 * d]
        b_raw = z[..., 3 * hd + 2 * d:3 * hd + 2 * d + heads]
    with jax.named_scope("kda/conv"):
        qkv, tail = causal_conv(qkv, read("kda_conv"), lp["conv_w"],
                                jnp.zeros((3 * hd,), f32), valid)
        conv = write("kda_conv", tail)
        q = _l2norm_head(qkv[..., :hd].reshape(bsz, t, heads, d))
        k = _l2norm_head(qkv[..., hd:2 * hd].reshape(bsz, t, heads, d))
        v = qkv[..., 2 * hd:].reshape(bsz, t, heads, d)
    with jax.named_scope("kda/gate"):
        f = linear_ops.linear(f1, lp["f_b"]).astype(f32).reshape(
            bsz, t, heads, d)
        rate = jnp.exp(lp["A_log"].astype(f32))[:, None]
        g = cfg.kda_gate_lower_bound * jax.nn.sigmoid(
            rate * (f + lp["dt_bias"].astype(f32).reshape(heads, d)))
        g = jnp.where(valid[..., None, None], g, 0.0)
        beta = jnp.where(valid[..., None],
                         jax.nn.sigmoid(b_raw.astype(f32)), 0.0)
        out_gate = jax.nn.sigmoid(
            linear_ops.linear(g1, lp["g_b"]).astype(f32))
    n_valid = jnp.sum(valid).astype(jnp.int32)
    zero = jnp.zeros((), jnp.int32)
    if t == 1 and rows is None:
        with jax.named_scope("kda/step"):
            o, s1 = step(read("kda_s"), q[:, 0], k[:, 0], v[:, 0], g[:, 0],
                         beta[:, 0])
            o = o[:, None]
        counts = (zero, n_valid)
    else:
        with jax.named_scope("kda/chunk"):
            o, s1 = chunk(read("kda_s"), q, k, v, g, beta)
        counts = (n_valid, zero)
    kda_s = write("kda_s", s1)
    with jax.named_scope("kda/out"):
        of = _rows(o * (d ** -0.5))
        of = of * jax.lax.rsqrt(
            jnp.mean(of * of, axis=-1, keepdims=True) + cfg.norm_eps)
        y = (of.reshape(bsz, t, heads, d) * lp["o_norm"].astype(f32)
             ).reshape(bsz, t, hd) * out_gate
        out = linear_ops.linear(y.astype(u.dtype), lp["o"])
    return out, counts, dict(state, kda_s=kda_s, kda_conv=conv)
