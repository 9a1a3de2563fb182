"""Mamba-2 (state-space duality, Dao & Gu 2024) for the hybrid decoder.

One mixer of a Nemotron-H block, or the state-space branch of a Falcon-H1
block (``models/decoder.py`` walks the schedule):

    [z | xBC | dt] = in_proj(u)         Falcon-H1: mup (.) in_proj(m_in u), and
                                        m_out out_proj(y) at the end
    xBC  <- silu(causal depthwise conv_K(xBC) + conv_bias)
    [x | B | C] = xBC           x as [H, P]; B, C as [G, N], shared by H/G heads
    dt   <- softplus(dt + dt_bias);   A = -exp(A_log)
    h_t  = exp(dt_t A) h_{t-1} + dt_t x_t (x) B_t          h in R^{H x P x N}
    y_t  = C_t . h_t + D x_t
    y    <- GroupRMSNorm(y * silu(z)) * w;   out_proj(y)

The recurrence exists twice, both token by token and float32 in state and
decay: a token's result must not depend on how its prompt was cut into
chunks or on the other rows of the tick (the benchmark sends one probe alone
and again under load and demands the same tokens), and only the sequential
form computes every token by the same operations whatever the chunking (the
chunked matmul form of the paper does not, and is not kept).

- :func:`ssd_sequential`: under ``lax.scan``; the XLA path (the CPU) and
  the tests' oracle.  One token is its T == 1 case.
- ``ops/pallas/ssm_scan.py``: the same as a kernel that keeps a head
  group's state in VMEM and reads and writes the state stack in place; the
  chip's form for decode AND for the ragged prefill block.

State lives beside the paged KV pool in the cache object (``kv.py``):
``h`` ``[layers of this kind, R, H, P, N]`` float32 and ``conv`` ``[layers,
R, K-1, C]`` (the last K-1 conv inputs, bf16), one slot a ROW.  Positions
that are padding (``valid`` false) have dt = 0, so they leave ``h`` as it is,
and do not enter the conv tail.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from ipex_llm_tpu.ops import linear as linear_ops
from ipex_llm_tpu.ops.norms import _rows


def ssd_sequential(h0, x, dt, a, b, c):
    """The recurrence token by token.  h0 [B, H, P, N]; x [B, T, H, P]; dt
    [B, T, H] (0 at padding); a [H]; b, c [B, T, H, N] (already spread over
    the heads of a group).  Returns (y [B, T, H, P] without the D term, h)."""
    def step(h, inp):
        x_t, dt_t, b_t, c_t = inp
        h = (jnp.exp(dt_t * a)[..., None, None] * h
             + (dt_t[..., None] * x_t)[..., None] * b_t[:, :, None, :])
        return h, jnp.einsum("bhpn,bhn->bhp", h, c_t)

    xs = tuple(jnp.moveaxis(v, 1, 0) for v in (x, dt, b, c))
    h, y = jax.lax.scan(step, h0, xs)
    return jnp.moveaxis(y, 0, 1), h


def causal_conv(xbc, tail, w, bias, valid):
    """Depthwise causal conv over time that starts from a row's carried
    tail.  xbc [B, T, C]; tail [B, K-1, C] (the K-1 inputs before this
    call); w [K, C] (tap j weighs the input K-1-j positions back); valid
    [B, T].  Padding is zeroed and left out of the new tail, which holds the
    K-1 inputs before the position just past the row's last valid one.
    Returns (silu(conv + bias) [B, T, C] float32, new tail)."""
    k = w.shape[0]
    t = xbc.shape[1]
    xm = jnp.where(valid[..., None], xbc, 0).astype(tail.dtype)
    cat = jnp.concatenate([tail, xm], axis=1)            # [B, K-1+T, C]
    catf = cat.astype(jnp.float32)
    conv = sum(catf[:, j: j + t] * w[j].astype(jnp.float32)
               for j in range(k))
    end = jnp.max(jnp.where(valid, jnp.arange(1, t + 1), 0), axis=1)   # [B]
    idx = end[:, None] + jnp.arange(k - 1)[None, :]
    new_tail = jnp.take_along_axis(cat, idx[..., None], axis=1)
    return jax.nn.silu(conv + bias.astype(jnp.float32)), new_tail


def _scan(cfg, stack_src, stack_dst, layer, rows, fresh, x, dt, a, b, c,
          lens):
    """Run the recurrence for this layer's rows and put the new state into
    ``stack_dst`` [L, R, H, P, N] at ``layer``; the state before comes from
    ``stack_src`` (the same array, or the tick's read-only source where
    this is the tick's first touch).  ``rows`` None: batch entry i IS state
    row i, every row is rewritten.  ``rows`` [B]: a prefill block's map to
    state rows (R = a pad slot, dropped); ``fresh`` [B] rows start from a
    zero state.  x [B, T, H, P], dt [B, T, H], b/c [B, T, G, N] float32."""
    from ipex_llm_tpu.ops import dispatch

    hpg = cfg.ssm_num_heads // cfg.ssm_n_groups
    use_kernel = False
    if dispatch.use_pallas():
        from ipex_llm_tpu.ops.pallas import ssm_scan

        use_kernel = ssm_scan.supported(cfg)
    if rows is None and use_kernel:
        return ssm_scan.scan_stack(stack_src, stack_dst, layer, x, dt, a, b,
                                   c, lens)
    if rows is None:
        h0 = jax.lax.dynamic_index_in_dim(stack_src, layer, 0, False)
    else:
        r = stack_src.shape[1]
        h0 = stack_src[layer, jnp.clip(rows, 0, r - 1)]
        h0 = jnp.where(fresh[:, None, None, None], 0.0, h0)
    if use_kernel:
        y, h = ssm_scan.scan_rows(h0, x, dt, a, b, c, lens)
    else:
        y, h = ssd_sequential(
            h0, x, dt, a, *(jnp.repeat(v, hpg, axis=2) for v in (b, c)))
    if rows is None:
        return y, jax.lax.dynamic_update_index_in_dim(stack_dst, h, layer, 0)
    return y, stack_dst.at[layer, rows].set(h, mode="drop")


def mup_vector(cfg):
    """Falcon-H1's ``ssm_multipliers`` spread over the columns of
    ``in_proj``'s output: [z | x | B | C | dt], float32."""
    d_in = cfg.ssm_num_heads * cfg.ssm_head_dim
    gn = cfg.ssm_n_groups * cfg.ssm_state_size
    widths = (d_in, d_in, gn, gn, cfg.ssm_num_heads)
    return jnp.concatenate([jnp.full((w,), m, jnp.float32)
                            for w, m in zip(widths, cfg.ssm_multipliers)])


def mamba2_mixer(cfg, lp: dict, u, state: dict, layer, valid, rows=None,
                 fresh=None):
    """The Mamba-2 mixer on the normed input ``u`` [B, T, hidden].

    ``state``: ``{"h", "conv"}`` stacks (and ``"src"``: the same two, read
    only, where the tick reads its state from one buffer and writes it to
    another); ``layer`` this mixer's index among the Mamba blocks; ``valid``
    [B, T] the positions that are tokens.  Returns (out [B, T, hidden], the
    state with this layer's slots rewritten)."""
    heads, hd = cfg.ssm_num_heads, cfg.ssm_head_dim
    groups, n = cfg.ssm_n_groups, cfg.ssm_state_size
    d_in, gn = heads * hd, groups * n
    bsz, t, _ = u.shape
    src = state.get("src", state)
    with jax.named_scope("ssm/proj"):
        zxd = linear_ops.linear(
            linear_ops.times(u, cfg.ssm_in_multiplier), lp["in_proj"])
        if cfg.ssm_multipliers is not None:
            zxd = linear_ops.times(zxd, mup_vector(cfg))
        z = zxd[..., :d_in]
        xbc = zxd[..., d_in: 2 * d_in + 2 * gn]
        dt = zxd[..., 2 * d_in + 2 * gn:]
    with jax.named_scope("ssm/conv"):
        if rows is None:
            tail = jax.lax.dynamic_index_in_dim(src["conv"], layer, 0, False)
        else:
            r = src["conv"].shape[1]
            tail = src["conv"][layer, jnp.clip(rows, 0, r - 1)]
            tail = jnp.where(fresh[:, None, None], 0, tail)
        xbc, new_tail = causal_conv(xbc, tail, lp["conv_w"], lp["conv_b"],
                                    valid)
        if rows is None:
            conv = jax.lax.dynamic_update_index_in_dim(
                state["conv"], new_tail, layer, 0)
        else:
            conv = state["conv"].at[layer, rows].set(new_tail, mode="drop")
        x = xbc[..., :d_in].reshape(bsz, t, heads, hd)
        b = xbc[..., d_in: d_in + gn].reshape(bsz, t, groups, n)
        c = xbc[..., d_in + gn:].reshape(bsz, t, groups, n)
        dt = jax.nn.softplus(dt.astype(jnp.float32)
                             + lp["dt_bias"].astype(jnp.float32))
        dt = jnp.where(valid[..., None], dt, 0.0)
        a = -jnp.exp(lp["A_log"].astype(jnp.float32))
    with jax.named_scope("ssm/step" if t == 1 else "ssm/scan"):
        lens = jnp.sum(valid, axis=1).astype(jnp.int32)
        y, h = _scan(cfg, src["h"], state["h"], layer, rows, fresh, x, dt, a,
                     b, c, lens)
    with jax.named_scope("ssm/out"):
        y = y + lp["D"].astype(jnp.float32)[:, None] * x
        y = y.reshape(bsz, t, d_in) * jax.nn.silu(z.astype(jnp.float32))
        # behind the barrier of ops/norms.py: a group's reduction must not
        # depend on how the batch's leading dimensions factor
        yg = _rows(y.reshape(bsz, t, groups, d_in // groups))
        yg = yg * jax.lax.rsqrt(
            jnp.mean(yg * yg, axis=-1, keepdims=True) + cfg.norm_eps)
        y = yg.reshape(bsz, t, d_in) * lp["gate_norm"].astype(jnp.float32)
        out = linear_ops.times(
            linear_ops.linear(y.astype(u.dtype), lp["out_proj"]),
            cfg.ssm_out_multiplier)
    new_state = dict(state, h=h, conv=conv)
    return out, new_state
