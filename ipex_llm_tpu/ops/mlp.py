"""Fused gated MLP activations.

Reference counterparts: ``xe_linear.mlp_forward_xpu`` (fused gate/up + act,
models/common.py:146-170) and ``xe_addons.mlp_silu_mul_inplaced`` (§2.3).
On TPU the activation+multiply fuses into the surrounding quantized matmuls
under XLA, so the jnp composition below compiles to the same fused program
the reference hand-wrote in SYCL; merged gate_up weights (one matmul instead
of two) are handled at model-build time like the reference's `_optimize_pre`
qkv/gate-up merges (convert.py:890).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

ACT_FNS = {
    "silu": jax.nn.silu,
    # HF "gelu" is the exact erf form; jax.nn.gelu defaults to tanh-approx
    "gelu": lambda x: jax.nn.gelu(x, approximate=False),
    "gelu_new": lambda x: jax.nn.gelu(x, approximate=True),
    "gelu_pytorch_tanh": lambda x: jax.nn.gelu(x, approximate=True),
    "relu": jax.nn.relu,
    "relu2": lambda x: jnp.square(jax.nn.relu(x)),
    # CLIP/qwen2-vl vision towers
    "quick_gelu": lambda x: x * jax.nn.sigmoid(1.702 * x),
}


def act(x: jnp.ndarray, name: str = "gelu_new") -> jnp.ndarray:
    """Plain activation (non-gated MLPs: phi/gpt-neox/starcoder2)."""
    return ACT_FNS[name](x.astype(jnp.float32)).astype(x.dtype)


def gated_act_mul(gate: jnp.ndarray, up: jnp.ndarray, act: str = "silu",
                  limit: float | None = None) -> jnp.ndarray:
    """act(gate) * up — the SwiGLU/GeGLU core.  ``limit`` (static; a
    config's ``swiglu_limit``): ``act(min(gate, limit)) * clip(up, -limit,
    limit)``."""
    if limit is not None:
        gate = jnp.minimum(gate, jnp.asarray(limit, gate.dtype))
        up = jnp.clip(up, -limit, limit)
    return ACT_FNS[act](gate.astype(jnp.float32)).astype(up.dtype) * up


def split_gate_up(gate_up: jnp.ndarray) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Split a merged gate_up projection output into (gate, up)."""
    d = gate_up.shape[-1] // 2
    return gate_up[..., :d], gate_up[..., d:]
