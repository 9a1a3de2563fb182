"""Quantized linear (dequant-matmul).

TPU-native replacement for ``LowBitLinear.forward`` and its native kernels
``xe_linear.forward_new`` / ``xe_batch.batch_forward`` (reference:
low_bit_linear.py:605-756, §2.3).  Instead of a C++ dispatch per call, the op
is a jittable function over a ``QTensor``; on TPU the packed-int4 path runs a
Pallas kernel that streams packed bytes from HBM and unpacks them in VMEM next
to the MXU (see ops/pallas/qmatmul.py), every other format falls back to an
XLA dequantize→matmul which the compiler fuses.

A layer scan hands its linears not a slice of a stacked weight but the stack
and the layer's index (``quantize/core.py`` ``QLayer``): the kernel reads the
layer's packed codes where they lie, and only the routes made of XLA
operations, into which a slice fuses, slice the layer out (``qmatmul``).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from ipex_llm_tpu.ops import dispatch
from ipex_llm_tpu.quantize import core as qcore
from ipex_llm_tpu.quantize.core import QLayer, QTensor


def qmatmul_reference(x: jnp.ndarray, qt: QTensor, compute_dtype=jnp.bfloat16) -> jnp.ndarray:
    """x [..., in] @ dequant(qt) [in, out] -> [..., out]; XLA fallback/oracle."""
    w = qcore.dequantize(qt, dtype=compute_dtype)
    return jnp.matmul(x.astype(compute_dtype), w, preferred_element_type=jnp.float32).astype(
        x.dtype
    )


def qmatmul(x: jnp.ndarray, qt: QTensor | QLayer,
            compute_dtype=jnp.bfloat16) -> jnp.ndarray:
    """Quantized matmul with backend dispatch: the single entry.

    The Pallas path covers the formats ops/pallas/qmatmul.py lists as
    supported, and the backend comes from ``dispatch.use_pallas()``: the
    compiled kernel on a TPU, the XLA block-dequant path elsewhere.  Under
    an active SPMD mesh, TP-sharded weights
    (``qt.tp_mode`` stamped by parallel/shard.py) whose shape the
    shard_map-wrapped kernel covers run it; everything else takes the XLA
    dequant path, which GSPMD partitions itself.  Every route is decided
    from the weight and the mesh before the call: a kernel that raises is
    an error, never a reason to take another path.

    A ``QLayer`` (a layer of a stack, from a layer scan) reaches the
    single-device kernel as it is, and the kernel reads the layer's codes
    out of the stack if its tiles cover them (``stacked_supported``).  On
    every other route the layer is sliced out here (``take``): the XLA
    dequant path, the shard_map tp wrapper, the manual-tp row-parallel
    branch.  There the slice feeds XLA operations and fuses into them.
    """
    mt = dispatch.manual_tp_state()
    if mt is not None:
        # manual-mesh region (parallel/manual.py): the planes are already
        # per-shard slices and the trace is single-device.  Row-parallel
        # weights are THE cross-chip math — local f32 partial products
        # combined through the quantized-collective family at full
        # accumulator width (casting to x.dtype only AFTER the reduce
        # keeps the exact family bit-stable against the single-chip
        # matmul; the Pallas kernel cannot serve here, it emits at
        # compute_dtype and a narrowed partial would break that
        # guarantee).  Column/replicated weights are pure local compute
        # and fall THROUGH to the ordinary single-device choice below —
        # the per-shard matmul takes the same Pallas-vs-XLA call the
        # single-chip trace takes.
        axis, cq = mt
        if qt.tp_mode == "row":
            w = qcore.dequantize(_whole(qt), dtype=compute_dtype)
            part = jnp.matmul(x.astype(compute_dtype), w,
                              preferred_element_type=jnp.float32)
            from ipex_llm_tpu.ops import collectives

            return collectives.all_reduce(part, axis, qtype=cq,
                                          out_dtype=x.dtype)
    else:
        mesh = dispatch.spmd_mesh()
        if (
            mesh is not None
            and mesh.shape.get("tp", 1) > 1
            and dispatch.use_pallas_sharded()
        ):
            # (kernel modules import pallas, ~1 s: only where a kernel runs)
            from ipex_llm_tpu.ops.pallas import qmatmul as pallas_qmatmul

            if pallas_qmatmul.sharded_supported(qt, mesh.shape["tp"]):
                return pallas_qmatmul.qmatmul_pallas_sharded(
                    x, _whole(qt), mesh, compute_dtype)
    if dispatch.use_pallas():
        from ipex_llm_tpu.ops.pallas import qmatmul as pallas_qmatmul

        if pallas_qmatmul.supported(qt):
            return pallas_qmatmul.qmatmul_pallas(x, qt, compute_dtype)
    return qmatmul_reference(x, _whole(qt), compute_dtype)


def _whole(qt: QTensor | QLayer) -> QTensor:
    return qt.take() if isinstance(qt, QLayer) else qt


def linear(x: jnp.ndarray, w, bias: jnp.ndarray | None = None) -> jnp.ndarray:
    """General linear over a QTensor, a plain array, or a LoraWeight.

    Reference counterpart: models/common.py:309 ``linear_forward`` and, for
    the LoRA path, ``LoraLowBitLinear.forward`` (qlora.py:66): frozen base
    matmul plus ``(x·A)·B · α/r`` with gradients flowing only through A/B.
    """
    base = getattr(w, "base", None)
    if base is not None:  # training.qlora.LoraWeight
        y = linear(x, base)
        lora = (x.astype(w.a.dtype) @ w.a) @ w.b * w.scale
        y = y + lora.astype(y.dtype)
    elif isinstance(w, (QTensor, QLayer)):
        y = qmatmul(x, w)
    else:
        y = jnp.matmul(
            x.astype(w.dtype), w, preferred_element_type=jnp.float32
        ).astype(x.dtype)
    if bias is not None:
        y = y + bias.astype(y.dtype)
    return y


def times(x: jnp.ndarray, m) -> jnp.ndarray:
    """``x`` times a muP multiplier of the configuration (falcon_h1: a
    number, or a vector over the last axis), applied to the ACTIVATION in
    float32 and never folded into a quantized plane; ``x`` itself at 1.0,
    every other family's."""
    if isinstance(m, (int, float)) and m == 1.0:
        return x
    return (x.astype(jnp.float32) * m).astype(x.dtype)
