"""The weight generator for ``model_type: mistral``: the tree
``models/build.py`` builds for a Llama-scheme dense model.  Stacked ``qkv``,
``o``, ``gate_up`` and ``down`` int4 planes and two float32 norms a layer, a
bf16 embedding, a packed head, a final norm, and the rotary table; drawn in
ONE jitted call, a layer at a time."""

from __future__ import annotations

import weights


def param_tree(cfg, hf: dict, qtype: str, seed: int):
    """The parameter tree of ``cfg`` (the program's ``ModelConfig`` of
    ``hf``), on the default device."""
    import jax
    import jax.numpy as jnp

    if qtype != "sym_int4":
        raise ValueError(
            f"perfbench/generators/mistral.py draws sym_int4 planes only, "
            f"not {qtype!r}; another weight type is another drawing helper "
            "in weights.py and a branch here")
    h, ffn, v, n = (cfg.hidden_size, cfg.intermediate_size, cfg.vocab_size,
                    cfg.num_layers)
    qd, kvd = cfg.q_dim, cfg.kv_dim
    planes = {"qkv": (h, qd + 2 * kvd), "o": (qd, h),
              "gate_up": (h, 2 * ffn), "down": (ffn, h)}
    for name, (k, _) in {**planes, "lm_head": (h, v)}.items():
        weights.check_block(name, k)

    def layer(k):
        ks = jax.random.split(k, len(planes) + 2)
        out = {name: weights.int4_plane(ks[i], *shape)
               for i, (name, shape) in enumerate(planes.items())}
        out["attn_norm"] = weights.vector(ks[-2], (h,))
        out["mlp_norm"] = weights.vector(ks[-1], (h,))
        return out

    def make(key):
        k_layers, k_embed, k_head, k_norm = jax.random.split(key, 4)
        return {"layers": weights.stack(layer, k_layers, n),
                "embed": weights.dense(k_embed, (v, h), h, jnp.bfloat16),
                "lm_head": weights.int4_plane(k_head, h, v),
                "final_norm": weights.vector(k_norm, (h,))}

    raw = jax.jit(make)(weights.seed_key(seed))
    layers = {name: weights.qtensor(raw["layers"][name], *shape)
              for name, shape in planes.items()}
    layers["attn_norm"] = raw["layers"]["attn_norm"]
    layers["mlp_norm"] = raw["layers"]["mlp_norm"]
    return {
        "embed": raw["embed"], "final_norm": raw["final_norm"],
        "layers": layers, "lm_head": weights.qtensor(raw["lm_head"], h, v),
        "inv_freq": jnp.asarray(
            cfg.rope.inv_freq(cfg.max_position_embeddings), jnp.float32),
        "rope_mscale": float(cfg.rope.mscale(cfg.max_position_embeddings)),
    }


def tiny(hf: dict) -> dict:
    """The same family at a size a CPU holds: the rehearsal takes a tick's
    row state from an engine around it (that state depends on the engine's
    sizes only)."""
    out = dict(hf, hidden_size=128, intermediate_size=256,
               num_hidden_layers=1, num_attention_heads=2,
               num_key_value_heads=1, vocab_size=256)
    out.pop("head_dim", None)
    return out
