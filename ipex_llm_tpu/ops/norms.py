"""RMSNorm / LayerNorm.

Reference counterparts: ``xe_addons.rms_norm`` / ``xe_addons.layer_norm``
called through models/common.py:184,205.  On TPU these are bandwidth-bound
elementwise+reduce ops that XLA fuses into neighbours, so the jnp form *is*
the fast path; a bespoke Pallas kernel buys nothing here (unlike SYCL where
the reference needed a fused kernel to avoid eager-mode dispatch overhead).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def _rows(x: jnp.ndarray) -> jnp.ndarray:
    """``[..., H]`` as ``[N, H]`` behind an optimization barrier.

    On a TPU, XLA picks the order of a row reduction from how the leading
    dimensions factor: measured on a v5e, the same 128 rows normalized as
    ``[8, 16, H]`` and as ``[1, 128, H]`` differ by one bf16 ulp.  A token's
    norm would then depend on the shape of the batch it rides in, and the
    engine's mixed tick would stop being bit-identical to the sequential
    one.  A plain reshape is not enough (XLA folds it back into the
    reduction); behind the barrier the reduction sees only ``[N, H]``, and
    on the same chip every factorization and every N gave the bits of the
    ``[1, N, H]`` form."""
    return jax.lax.optimization_barrier(x.reshape(-1, x.shape[-1]))


def rms_norm(x: jnp.ndarray, weight: jnp.ndarray, eps: float = 1e-6,
             offset: float = 0.0) -> jnp.ndarray:
    """RMSNorm in fp32 accumulation, cast back to x.dtype.

    ``offset=1.0`` covers Gemma-style (1+w) norms without a weight rewrite.
    """
    xf = _rows(x).astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    y = xf * jax.lax.rsqrt(var + eps)
    y = y * (weight.astype(jnp.float32) + offset)
    return y.astype(x.dtype).reshape(x.shape)


def layer_norm(x: jnp.ndarray, weight: jnp.ndarray | None,
               bias: jnp.ndarray | None, eps: float = 1e-5) -> jnp.ndarray:
    xf = _rows(x).astype(jnp.float32)
    mean = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.var(xf, axis=-1, keepdims=True)
    y = (xf - mean) * jax.lax.rsqrt(var + eps)
    if weight is not None:
        y = y * weight.astype(jnp.float32)
    if bias is not None:
        y = y + bias.astype(jnp.float32)
    return y.astype(x.dtype).reshape(x.shape)
