"""The model of a run: random weights from ``--seed``, made ON THE DEVICE in
one jitted call, already in the type they are served in.

The program's own ``random_params`` draws float32 normals on the host and
quantizes them through the native library: 68 s for Mistral-7B (my chip run,
PR 21), paid by every run of every cell.  A checkpoint's values do not
change what a tick costs, so the benchmark draws the packed int4 planes and
their fp16 scales directly: uniform 4-bit codes, and scales of random sign
sized so that a dequantized weight has the standard deviation the program's
generator gives it (1.2 / sqrt(in_features)).  The tree is the one
``models/build.py`` builds for a Llama-scheme dense model: stacked ``qkv``,
``o``, ``gate_up`` and ``down`` planes, float32 norms, a bf16 embedding and a
packed head.  The plain reference dequantizes the same planes by its own
code, so a wrong plane layout here shows as ``correct: false``.
"""

from __future__ import annotations

import math

# std of a uniform 4-bit code minus 8: sqrt((16^2 - 1) / 12)
CODE_STD = math.sqrt(255.0 / 12.0)
WEIGHT_GAIN = 1.2           # random_init.py: 0.3 / sqrt(in) * 4


def param_tree(cfg, qtype: str, seed: int):
    """The parameter tree of ``cfg`` (a program ``ModelConfig``), on the
    default device."""
    import jax
    import jax.numpy as jnp

    from ipex_llm_tpu.quantize.core import QTensor

    if qtype != "sym_int4":
        raise ValueError(
            f"perfbench/weights.py draws sym_int4 planes only, not {qtype!r}; "
            "a new weight type is a new generator here")
    bs = 32
    h, ffn, v, n = (cfg.hidden_size, cfg.intermediate_size, cfg.vocab_size,
                    cfg.num_layers)
    qd, kvd = cfg.q_dim, cfg.kv_dim
    planes = {"qkv": (h, qd + 2 * kvd), "o": (qd, h),
              "gate_up": (h, 2 * ffn), "down": (ffn, h)}
    for name, (k, _) in {**planes, "lm_head": (h, v)}.items():
        if k % bs:
            raise ValueError(f"{name}: in_features {k} is not a multiple of "
                             f"the quantization block {bs}")

    def plane(key, k: int, out: int):
        kd, ks, kg = jax.random.split(key, 3)
        data = jax.random.bits(kd, (k // 2, out), jnp.uint8)
        mag = (WEIGHT_GAIN / math.sqrt(k) / CODE_STD) * (
            1.0 + 0.1 * jax.random.uniform(ks, (k // bs, out), jnp.float32,
                                           -1.0, 1.0))
        sign = jnp.where(jax.random.bernoulli(kg, 0.5, (k // bs, out)),
                         1.0, -1.0)
        return data, (mag * sign).astype(jnp.float16)

    def norm(key, *shape):
        return 1.0 + 0.05 * jax.random.normal(key, shape, jnp.float32)

    def make(key):
        k_layers, k_embed, k_head, k_norm = jax.random.split(key, 4)

        def layer(k):
            ks = jax.random.split(k, len(planes) + 2)
            out = {name: plane(ks[i], *shape)
                   for i, (name, shape) in enumerate(planes.items())}
            out["attn_norm"] = norm(ks[-2], h)
            out["mlp_norm"] = norm(ks[-1], h)
            return out

        # one layer at a time, so the generator's temporaries stay one
        # layer's size
        layers = jax.lax.map(layer, jax.random.split(k_layers, n))
        embed = (WEIGHT_GAIN / math.sqrt(h)) * jax.random.normal(
            k_embed, (v, h), jnp.float32)
        return {"layers": layers, "embed": embed.astype(jnp.bfloat16),
                "lm_head": plane(k_head, h, v), "final_norm": norm(k_norm, h)}

    key = jax.random.fold_in(jax.random.key(int(seed) & 0x7FFFFFFF),
                             int(seed) >> 31)
    raw = jax.jit(make)(key)

    def qt(pair, k, out):
        return QTensor(pair[0], pair[1], None, qtype, (k, out), bs)

    layers = {name: qt(raw["layers"][name], *shape)
              for name, shape in planes.items()}
    layers["attn_norm"] = raw["layers"]["attn_norm"]
    layers["mlp_norm"] = raw["layers"]["mlp_norm"]
    params = {
        "embed": raw["embed"], "final_norm": raw["final_norm"],
        "layers": layers, "lm_head": qt(raw["lm_head"], h, v),
        "inv_freq": jnp.asarray(
            cfg.rope.inv_freq(cfg.max_position_embeddings), jnp.float32),
        "rope_mscale": float(cfg.rope.mscale(cfg.max_position_embeddings)),
    }
    return params


def build_model(hf_config: dict, qtype: str, seed: int):
    """The object ``from_pretrained`` returns, around :func:`param_tree`."""
    import jax

    from ipex_llm_tpu.models.families import FAMILIES
    from ipex_llm_tpu.transformers.model import TPUModelForCausalLM

    cfg = FAMILIES[hf_config["model_type"]].to_config(hf_config)
    params = jax.block_until_ready(param_tree(cfg, qtype, seed))
    return TPUModelForCausalLM(cfg, params, hf_config, qtype)


def tree_bytes(params) -> int:
    import jax

    return sum(leaf.nbytes for leaf in jax.tree_util.tree_leaves(params)
               if isinstance(leaf, jax.Array))
