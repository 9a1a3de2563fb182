"""A throw-away second family for tests/test_perfbench.py, added to a COPY of
the benchmark by files alone: ``model_type: mixtral``, whose tree the dense
generator cannot express.  Beside the attention planes a layer holds a
float32 router and two EXPERT stacks, so the whole tree's stacks are
``[layers, experts, K/2, N]``, drawn with ``weights.stack`` one expert at a
time.  It is no benchmark configuration: it lives under the tests, on a path
the test adds."""

from __future__ import annotations

import weights


def param_tree(cfg, hf: dict, qtype: str, seed: int):
    import jax
    import jax.numpy as jnp

    if qtype != "sym_int4":
        raise ValueError(f"the test's mixtral generator draws sym_int4 "
                         f"planes only, not {qtype!r}")
    h, ffn, v = cfg.hidden_size, cfg.moe_intermediate_size, cfg.vocab_size
    n, e = cfg.num_layers, cfg.num_experts
    qd, kvd = cfg.q_dim, cfg.kv_dim
    planes = {"qkv": (h, qd + 2 * kvd), "o": (qd, h)}
    experts = {"moe_gate_up": (h, 2 * ffn), "moe_down": (ffn, h)}
    for name, (k, _) in {**planes, **experts, "lm_head": (h, v)}.items():
        weights.check_block(name, k)

    def layer(k):
        ks = iter(jax.random.split(k, len(planes) + len(experts) + 3))
        out = {name: weights.int4_plane(next(ks), *shape)
               for name, shape in planes.items()}
        for name, shape in experts.items():
            out[name] = weights.stack(
                lambda kk, shape=shape: weights.int4_plane(kk, *shape),
                next(ks), e)
        out["router"] = weights.dense(next(ks), (h, e), h, jnp.float32)
        out["attn_norm"] = weights.vector(next(ks), (h,))
        out["mlp_norm"] = weights.vector(next(ks), (h,))
        return out

    def make(key):
        k_layers, k_embed, k_head, k_norm = jax.random.split(key, 4)
        return {"layers": weights.stack(layer, k_layers, n),
                "embed": weights.dense(k_embed, (v, h), h, jnp.bfloat16),
                "lm_head": weights.int4_plane(k_head, h, v),
                "final_norm": weights.vector(k_norm, (h,))}

    raw = jax.jit(make)(weights.seed_key(seed))
    layers = dict(raw["layers"])
    for name, shape in {**planes, **experts}.items():
        layers[name] = weights.qtensor(layers[name], *shape)
    return {
        "embed": raw["embed"], "final_norm": raw["final_norm"],
        "layers": layers, "lm_head": weights.qtensor(raw["lm_head"], h, v),
        "inv_freq": jnp.asarray(
            cfg.rope.inv_freq(cfg.max_position_embeddings), jnp.float32),
        "rope_mscale": float(cfg.rope.mscale(cfg.max_position_embeddings)),
    }


def tiny(hf: dict) -> dict:
    out = dict(hf, hidden_size=128, intermediate_size=256,
               num_hidden_layers=1, num_attention_heads=2,
               num_key_value_heads=1, vocab_size=256, num_local_experts=4)
    out.pop("head_dim", None)
    return out
