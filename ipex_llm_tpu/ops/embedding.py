"""Embedding variants: low-bit (quantized) embedding row lookup.

Reference counterparts: ``LowBitEmbedding`` (reference embedding.py:179,
backed by ``xe_linear.dequantize_rows``) plus the CPU/disk offload variants
(embedding.py:29-96).  On TPU the memory lever is HBM, not host RAM, and a
host lookup inside the jitted decode loop would cost a device round-trip
per token — so the TPU-native variant quantizes the table in HBM and
dequantizes only the gathered rows in-jit.  ``cpu_embedding`` /
``disk_embedding`` flags map onto this (documented deviation).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from ipex_llm_tpu.quantize import numerics
from ipex_llm_tpu.quantize.core import SCALE_DTYPE, QTensor

EMBED_QTYPES = ("sym_int8", "sym_int4", "nf4", "fp4")


def embed_lookup(table, ids: jnp.ndarray, dtype=jnp.bfloat16) -> jnp.ndarray:
    """rows = table[ids]; table is a dense array or a QTensor laid out
    ``[vocab, hidden]`` (vocab = contraction/block axis).

    The gather touches only ``len(ids)`` rows — the xe_linear
    ``dequantize_rows`` equivalent, fused into the forward by XLA.
    """
    if not isinstance(table, QTensor):
        return jnp.take(table, ids, axis=0).astype(dtype)

    bs = table.block_size
    qtype = table.qtype
    block = ids // bs                     # [...,]
    offset = ids % bs
    # the rows' bit patterns, then their numbers: never the whole table's
    scales = jax.lax.bitcast_convert_type(
        jnp.take(table.scale_bits, block, axis=0), SCALE_DTYPE
    ).astype(jnp.float32)

    if qtype == "sym_int8":
        codes = jnp.take(table.data, ids, axis=0).astype(jnp.int32)
        rows = (codes - 128).astype(jnp.float32) * scales
    else:  # packed 4-bit: block-local halves pairing (core._pack_nibbles)
        half = bs // 2
        in_low = offset < half
        packed_row = jnp.where(
            in_low, block * half + offset, block * half + offset - half
        )
        bytes_ = jnp.take(table.data, packed_row, axis=0).astype(jnp.int32)
        codes = jnp.where(in_low[..., None], bytes_ & 0x0F, bytes_ >> 4)
        if qtype == "sym_int4":
            rows = (codes - 8).astype(jnp.float32) * scales
        else:
            import numpy as np

            tab = jnp.asarray(
                numerics.NF4_TABLE if qtype == "nf4" else numerics.FP4_TABLE,
                jnp.float32,
            )
            rows = jnp.take(tab, codes, axis=0) * scales
    if table.zeros is not None:
        rows = rows + jnp.take(table.zeros, block, axis=0).astype(jnp.float32)
    return rows.astype(dtype)
