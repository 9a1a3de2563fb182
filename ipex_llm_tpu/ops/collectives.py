"""Quantized cross-chip collectives (the EQuARX family, arxiv 2506.17615).

Decode under tensor parallelism is ALLREDUCE-BOUND: every layer pays two
AllReduces (the o- and down-projection row-parallel combines), each moving
``hidden * rows`` values over ICI while the MXU sits idle.  EQuARX shows
that quantizing the AllReduce PAYLOAD — narrow codes on the wire, full-
precision accumulation at every hop — recovers most of that bandwidth at a
bounded accuracy cost.  This module is that family for the manual-mesh
programs (parallel/manual.py, parallel/pipeline.py): ONE entry point per
collective with a ``qtype`` axis, so call sites select wire width per op
instead of hard-coding a promotion.

Families (``ALLREDUCE_QTYPES``):

- ``"bf16"`` — the EXACT family and the default: partial sums ride at f32
  and accumulate in f32 (``psum_exact``), so a tp-sharded program is
  bit-stable against its single-chip twin at the bf16 output width — the
  tp2==tp1 bit-identity gate runs on this family.  (The name records the
  TENSOR width being reduced; the wire carries the f32 partials, exactly
  what the pre-family code promoted to.)
- ``"e5m2"`` — fp8(e5m2) codes on the wire (4x narrower than f32), f32
  accumulate: pure-rounding loss, no scale bookkeeping.
- ``"int8"`` — blockwise symmetric int8: per-(row-block) f16 scales ride
  beside the codes (EQuARX's block layout), f32 dequant-accumulate.

CPU note, formerly pipeline.py's blanket workaround: XLA:CPU's
AllReducePromotion pass check-fails cloning a sub-f32 all-reduce inside a
partial-auto shard_map region, so every family keeps its on-wire payload
at a promotion-proof dtype on CPU meshes (quantization still happens — the
values are coded and decoded, so the ERROR model is the real one — only
the emulated wire width is f32).  On TPU backends the payload dtypes are
the real ones.  That platform fork lives HERE, inside the family, not at
call sites.
"""

from __future__ import annotations

import os

import jax
import jax.numpy as jnp

ALLREDUCE_QTYPES = ("bf16", "e5m2", "int8")

# int8 family: contraction-block size for the per-block scales (the EQuARX
# block layout; small enough to track outliers, large enough that scale
# bytes are <2% of payload)
_INT8_BLOCK = 64


def psum_exact(x: jnp.ndarray, axis_name: str) -> jnp.ndarray:
    """AllReduce with f32 accumulation, returned at ``x.dtype``.

    The exact family's primitive, and the one definition of the CPU
    AllReducePromotion workaround (XLA:CPU check-fails cloning a bf16
    all-reduce inside a partial-auto region): sub-f32 payloads promote to
    f32 BEFORE the psum on every backend — on TPU that is also the
    numerically-right call, f32 accumulation is how the MXU reduces.
    """
    dt = x.dtype
    if dt in (jnp.float32, jnp.float64):
        return jax.lax.psum(x, axis_name)
    return jax.lax.psum(x.astype(jnp.float32), axis_name).astype(dt)


# e5m2's largest finite value: casting anything beyond it yields inf,
# which an AllReduce then spreads over the whole hidden state — saturate
# instead (a clipped outlier is bounded error, an inf is not)
_E5M2_MAX = 57344.0


def _e5m2_code(x32: jnp.ndarray) -> jnp.ndarray:
    """Quantize the payload to fp8 e5m2 codes, decode back to f32 (the
    per-hop dequant-accumulate model), saturating at the format's finite
    max.  On CPU the coded values ride an f32 wire (promotion-proof
    emulation, same error); on TPU the psum payload itself can stay
    e5m2-width upstream of accumulation."""
    x32 = jnp.clip(x32, -_E5M2_MAX, _E5M2_MAX)
    return x32.astype(jnp.float8_e5m2).astype(jnp.float32)


def _int8_code(x32: jnp.ndarray) -> jnp.ndarray:
    """Blockwise symmetric int8 code/decode along the last axis: values in
    each ``_INT8_BLOCK``-wide block share one f16 amax scale.  The scale
    saturates at f16's finite max (65504): an amax beyond scale*127 would
    otherwise round the scale to inf and decode the whole block to
    0*inf = NaN — saturation clips the outliers to ±127*65504 instead,
    bounded error rather than poison."""
    shape = x32.shape
    n = shape[-1]
    bs = _INT8_BLOCK if n % _INT8_BLOCK == 0 else n
    blocks = x32.reshape(*shape[:-1], n // bs, bs)
    amax = jnp.max(jnp.abs(blocks), axis=-1, keepdims=True)
    scale = jnp.clip(amax / 127.0, 0.0, 65504.0)
    scale = scale.astype(jnp.float16).astype(jnp.float32)
    scale = jnp.where(scale == 0.0, 1.0, scale)
    codes = jnp.clip(jnp.round(blocks / scale), -127, 127).astype(jnp.int8)
    return (codes.astype(jnp.float32) * scale).reshape(shape)


def all_reduce(x: jnp.ndarray, axis_name: str, qtype: str = "bf16",
               out_dtype=None) -> jnp.ndarray:
    """The per-op AllReduce entry: reduce ``x`` (a per-shard partial sum,
    any float dtype) over ``axis_name`` under the ``qtype`` wire family.

    Accumulation is ALWAYS f32 (every family); ``qtype`` chooses what the
    wire carries.  Returns ``out_dtype`` (default ``x.dtype``).
    """
    out_dtype = out_dtype or x.dtype
    x32 = x.astype(jnp.float32)
    if qtype == "bf16":
        y = jax.lax.psum(x32, axis_name)
    elif qtype == "e5m2":
        y = jax.lax.psum(_e5m2_code(x32), axis_name)
    elif qtype == "int8":
        y = jax.lax.psum(_int8_code(x32), axis_name)
    else:
        raise ValueError(
            f"unknown collective qtype {qtype!r}: valid families are "
            f"{ALLREDUCE_QTYPES}")
    return y.astype(out_dtype)


# Speed may not pick a LOSSY family (quantized wires change numerics), so
# the exact family ("bf16") stands unless an explicit request
# (EngineConfig.collective_qtype or IPEX_LLM_TPU_COLLECTIVE_QTYPE) opts into
# bounded error.
def resolve_qtype(requested: str | None = None) -> str:
    """The family an op should use: explicit request (argument, then the
    IPEX_LLM_TPU_COLLECTIVE_QTYPE env) or the exact default."""
    q = requested or os.environ.get("IPEX_LLM_TPU_COLLECTIVE_QTYPE") or "bf16"
    if q not in ALLREDUCE_QTYPES:
        raise ValueError(
            f"unknown collective qtype {q!r}: valid families are "
            f"{ALLREDUCE_QTYPES}")
    return q
