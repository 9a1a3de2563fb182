"""Random model initialization through the real build path.

Used by tests, the benchmark driver, and the multichip dry-run to fabricate a
model of any size without a checkpoint on disk: random tensors are generated
under the llama weight-naming scheme and fed through ``build_params`` exactly
like a real safetensors read, so quantization/merging behave identically.
Reference counterpart: the reference benchmarks on real checkpoints only
(all-in-one/run.py); a synthetic path keeps CI hermetic.
"""

from __future__ import annotations

from typing import Iterator

import numpy as np

from ipex_llm_tpu.hostutil import (PARALLEL_MIN_ELEMS, ROW_BLOCKS,
                                   for_row_blocks)
from ipex_llm_tpu.models.build import build_params
from ipex_llm_tpu.models.config import ModelConfig
from ipex_llm_tpu.models.families import FAMILIES
from ipex_llm_tpu.ops.rope import RopeScaling


def llama_config(
    hidden_size: int = 64,
    intermediate_size: int = 256,
    num_layers: int = 2,
    num_heads: int = 8,
    num_kv_heads: int = 8,
    head_dim: int | None = None,
    vocab_size: int = 128,
    max_position_embeddings: int = 2048,
    **over,
) -> ModelConfig:
    hd = head_dim or hidden_size // num_heads
    d = dict(
        model_type="llama",
        vocab_size=vocab_size,
        hidden_size=hidden_size,
        intermediate_size=intermediate_size,
        num_layers=num_layers,
        num_heads=num_heads,
        num_kv_heads=num_kv_heads,
        head_dim=hd,
        max_position_embeddings=max_position_embeddings,
        rope=RopeScaling(head_dim=hd),
    )
    d.update(over)
    return ModelConfig(**d)


def _llama_tensor_shapes(cfg: ModelConfig) -> dict[str, tuple[int, ...]]:
    h, ffn, v = cfg.hidden_size, cfg.intermediate_size, cfg.vocab_size
    qd, kvd = cfg.q_dim, cfg.kv_dim
    shapes: dict[str, tuple[int, ...]] = {}
    for i in range(cfg.num_layers):
        p = f"model.layers.{i}."
        shapes[p + "input_layernorm.weight"] = (h,)
        shapes[p + "post_attention_layernorm.weight"] = (h,)
        shapes[p + "self_attn.q_proj.weight"] = (qd, h)
        shapes[p + "self_attn.k_proj.weight"] = (kvd, h)
        shapes[p + "self_attn.v_proj.weight"] = (kvd, h)
        shapes[p + "self_attn.o_proj.weight"] = (h, qd)
        shapes[p + "mlp.gate_proj.weight"] = (ffn, h)
        shapes[p + "mlp.up_proj.weight"] = (ffn, h)
        shapes[p + "mlp.down_proj.weight"] = (h, ffn)
    shapes["model.embed_tokens.weight"] = (v, h)
    shapes["model.norm.weight"] = (h,)
    if not cfg.tie_word_embeddings:
        shapes["lm_head.weight"] = (v, h)
    return shapes


def _moe_tensor_shapes(cfg: ModelConfig) -> dict[str, tuple[int, ...]]:
    """Extra tensors for a mixtral-scheme MoE model."""
    h, fm, e = cfg.hidden_size, cfg.moe_intermediate_size, cfg.num_experts
    shapes: dict[str, tuple[int, ...]] = {}
    for i in range(cfg.num_layers):
        p = f"model.layers.{i}.block_sparse_moe."
        shapes[p + "gate.weight"] = (e, h)
        for j in range(e):
            shapes[p + f"experts.{j}.w1.weight"] = (fm, h)
            shapes[p + f"experts.{j}.w3.weight"] = (fm, h)
            shapes[p + f"experts.{j}.w2.weight"] = (h, fm)
    return shapes


def _standard_normal(rng: np.random.Generator, shape) -> np.ndarray:
    """float32 normals.  One core's ziggurat is most of the wall time of a
    7B build, so large tensors are filled in fixed row blocks by a thread
    pool, each block from its own child stream (the values do not depend on
    the core count); smaller tensors keep the parent's stream."""
    if int(np.prod(shape)) < PARALLEL_MIN_ELEMS:
        return rng.standard_normal(shape, dtype=np.float32)
    out = np.empty(shape, np.float32)
    children = rng.spawn(ROW_BLOCKS)
    for_row_blocks(shape[0], lambda i, lo, hi: children[i].standard_normal(
        dtype=np.float32, out=out[lo:hi]))
    return out


def random_params(cfg: ModelConfig, qtype: str = "sym_int4", seed: int = 0) -> dict:
    """Random params built through ``build_params`` (streamed: each tensor is
    generated on demand, never the whole checkpoint at once).  MoE configs
    (num_experts > 0) use the mixtral weight scheme."""
    shapes = _llama_tensor_shapes(cfg)
    moe = cfg.num_experts > 0
    if moe:
        for i in range(cfg.num_layers):
            p = f"model.layers.{i}.mlp."
            for stem in ("gate_proj", "up_proj", "down_proj"):
                del shapes[p + stem + ".weight"]
        shapes.update(_moe_tensor_shapes(cfg))
    rng = np.random.default_rng(seed)

    def gen(name: str) -> np.ndarray:
        s = shapes[name]
        if name.endswith("layernorm.weight") or name == "model.norm.weight":
            return np.ones(s, np.float32) + 0.05 * rng.standard_normal(s).astype(
                np.float32
            )
        w = _standard_normal(rng, s)
        w *= np.float32(0.3 / np.sqrt(max(s[-1], 1)) * 4)
        return w

    fam = FAMILIES["mixtral" if moe else "llama"]
    return build_params(cfg, fam.scheme, gen, lambda n: n in shapes,
                        qtype=qtype, moe_scheme=fam.moe)
