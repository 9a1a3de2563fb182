"""The weight generator for ``model_type: glm_moe_dsa`` (GLM-5.x): the tree
``models/build.py`` builds for a DeepSeek-scheme model with a dense prefix AND
an indexer.  Two stacks: ``layers_dense`` (``first_k_dense_replace`` layers:
the MLA planes and a gated MLP) and ``layers`` (the MLA planes, a float32
router over the router's full width with its ``e_score_correction_bias``, the
HELD experts' two stacks ``[layers, experts held, ...]`` and the shared
expert).  Each stack carries an ``indexer`` sub-tree, an entry a layer of the
stack whose ``indexer_types`` entry is "full" and none for a "shared" one:
``wq_b`` (low-rank query -> index heads) and ``wk`` (hidden -> index key) as
packed planes, the key's LayerNorm weight and bias, and the float32 head
weights' projection.  A bf16 embedding, a packed head, the final norm and the
rotary table.  Drawn in ONE jitted call, a layer (and inside an expert layer
an expert) at a time.

Planes are drawn padded once to the dequant-matmul kernel's tiles, the
padding's scales zero.  Magnitudes as for the other families (a comparison
between two precisions means nothing on a stream the blocks dwarf): every
projection that writes to the residual stream has the gain 1 / sqrt(2 *
num_hidden_layers) where the other planes have ``weights.py``'s 1.2; the
embedding is drawn at unit scale; the router's bias is a few hundredths, the
size of the gaps between neighbouring sigmoid scores."""

from __future__ import annotations

import math

import weights


def _padded(k: int, n: int) -> tuple[int, int]:
    from ipex_llm_tpu.quantize.core import kernel_padded_shape

    return kernel_padded_shape(k, n, weights.BLOCK)


def _plane(key, k: int, n: int, gain: float = weights.WEIGHT_GAIN):
    """One sym_int4 weight ``[k, n]`` at its padded size: the logical part
    has the standard deviation ``gain`` / sqrt(k), the padding is inert."""
    import jax.numpy as jnp

    kp, np_ = _padded(k, n)
    data, scales = weights.int4_plane(key, kp, np_)
    scales = scales * jnp.asarray(
        gain / weights.WEIGHT_GAIN * math.sqrt(kp / k), scales.dtype)
    live = ((jnp.arange(kp // weights.BLOCK) < k // weights.BLOCK)[:, None]
            & (jnp.arange(np_) < n)[None, :])
    return data, jnp.where(live, scales, 0).astype(scales.dtype)


def param_tree(cfg, hf: dict, qtype: str, seed: int):
    """The parameter tree of ``cfg`` (the program's ``ModelConfig`` of
    ``hf``), on the default device."""
    import jax
    import jax.numpy as jnp

    if qtype != "sym_int4":
        raise ValueError(
            f"perfbench/generators/glm_moe_dsa.py draws sym_int4 planes "
            f"only, not {qtype!r}")
    h, v, heads = cfg.hidden_size, cfg.vocab_size, cfg.num_heads
    rank, q_rank = cfg.kv_lora_rank, cfg.q_lora_rank
    ffn, dense_ffn = cfg.moe_intermediate_size, cfg.intermediate_size
    shared = ffn * max(cfg.num_shared_experts, 1)
    held, width = cfg.moe_experts_held, cfg.num_experts
    n_dense = cfg.moe_layer_start
    n_moe = cfg.num_layers - n_dense
    i_heads, i_dim = cfg.index_n_heads, cfg.index_head_dim
    attn = {"q_a": (h, q_rank), "q_b": (q_rank, heads * cfg.head_dim),
            "kv_a": (h, rank + cfg.qk_rope_head_dim),
            "kv_b": (rank, heads * (cfg.qk_nope_head_dim + cfg.v_dim)),
            "o": (heads * cfg.v_dim, h)}
    planes = {
        "dense": {**attn, "gate_up": (h, 2 * dense_ffn),
                  "down": (dense_ffn, h)},
        "moe": {**attn, "shared_gate_up": (h, 2 * shared),
                "shared_down": (shared, h)},
        "experts": {"moe_gate_up": (h, 2 * ffn), "moe_down": (ffn, h)},
        "indexer": {"wq_b": (q_rank, i_heads * i_dim), "wk": (h, i_dim)},
    }
    for group in (*planes.values(), {"lm_head": (h, v)}):
        for name, (k, _) in group.items():
            weights.check_block(name, k)
    to_stream = 1.0 / math.sqrt(2.0 * cfg.num_layers)
    gains = {"o": to_stream, "down": to_stream, "moe_down": to_stream,
             "shared_down": to_stream}
    norm_shapes = {"attn_norm": (h,), "mlp_norm": (h,), "q_a_norm": (q_rank,),
                   "kv_a_norm": (rank,)}
    full = [t == "full" for t in cfg.indexer_types]
    n_full = {"dense": sum(full[:n_dense]), "moe": sum(full[n_dense:])}

    def draw(ks, group):
        return {name: _plane(next(ks), *shape,
                             gain=gains.get(name, weights.WEIGHT_GAIN))
                for name, shape in planes[group].items()}

    def layer(group):
        def one(k):
            ks = iter(jax.random.split(k, 16))
            out = draw(ks, group)
            for name, shape in norm_shapes.items():
                out[name] = weights.vector(next(ks), shape)
            if group == "moe":
                for name, shape in planes["experts"].items():
                    out[name] = weights.stack(
                        lambda kk, shape=shape, name=name: _plane(
                            kk, *shape,
                            gain=gains.get(name, weights.WEIGHT_GAIN)),
                        next(ks), held)
                out["router"] = weights.dense(next(ks), (h, width), h,
                                              jnp.float32)
                out["router_bias"] = weights.vector(next(ks), (width,), 0.0,
                                                    0.02)
            return out
        return one

    def indexer(k):
        ks = iter(jax.random.split(k, 8))
        out = draw(ks, "indexer")
        out["k_norm"] = weights.vector(next(ks), (i_dim,))
        out["k_norm_bias"] = weights.vector(next(ks), (i_dim,), 0.0)
        out["w_proj"] = weights.dense(next(ks), (h, i_heads), h, jnp.float32)
        return out

    def make(key):
        kd, km, kid, kim, k_embed, k_head, k_norm = jax.random.split(key, 7)
        out = {"layers_dense": weights.stack(layer("dense"), kd, n_dense),
               "layers": weights.stack(layer("moe"), km, n_moe),
               # unit scale: fan_in 1 in weights.dense's 1.2 / sqrt(fan_in)
               "embed": weights.dense(k_embed, (v, h), 1, jnp.bfloat16),
               "lm_head": _plane(k_head, h, v),
               "final_norm": weights.vector(k_norm, (h,))}
        for group, k in (("dense", kid), ("moe", kim)):
            if n_full[group]:
                out["indexer_" + group] = weights.stack(indexer, k,
                                                        n_full[group])
        return out

    raw = jax.jit(make)(weights.seed_key(seed))
    out = {"embed": raw["embed"], "final_norm": raw["final_norm"],
           "lm_head": weights.qtensor(raw["lm_head"], h, v),
           "inv_freq": jnp.asarray(
               cfg.rope.inv_freq(cfg.max_position_embeddings), jnp.float32),
           "rope_mscale": float(
               cfg.rope.mscale(cfg.max_position_embeddings))}
    for stack, group in (("layers_dense", "dense"), ("layers", "moe")):
        tree = dict(raw[stack])
        named = dict(planes[group], **(planes["experts"] if group == "moe"
                                       else {}))
        for name, shape in named.items():
            tree[name] = weights.qtensor(tree[name], *shape)
        if n_full[group]:
            ix = dict(raw["indexer_" + group])
            for name, shape in planes["indexer"].items():
                ix[name] = weights.qtensor(ix[name], *shape)
            tree["indexer"] = ix
        out[stack] = tree
    return out


def tiny(hf: dict) -> dict:
    """The same family at a size a CPU holds: one dense layer with a full
    indexer and five expert layers (shared, shared, full, shared, shared), 16
    experts of which the first 4 are held, 16 of the cached tokens chosen."""
    return dict(
        hf, hidden_size=128, intermediate_size=256, num_hidden_layers=6,
        first_k_dense_replace=1, num_attention_heads=4,
        num_key_value_heads=4, q_lora_rank=64, kv_lora_rank=64,
        qk_nope_head_dim=32, qk_rope_head_dim=16, v_head_dim=32,
        qk_head_dim=48, head_dim=32,
        moe_intermediate_size=64, n_routed_experts=4, n_router_experts=16,
        first_routed_expert=0, n_group=1, topk_group=1,
        num_experts_per_tok=4, vocab_size=256,
        index_topk=16, index_n_heads=4, index_head_dim=32,
        indexer_types=["full", "shared", "shared", "full", "shared",
                       "shared"],
        mlp_layer_types=["dense"] + ["sparse"] * 5)


def cache_shapes(cfg, engine_config):
    """The cache a tick of this family holds: the SPARSE latent pool, its two
    kinds of page (a latent row a token a layer; an index key a token a full
    layer) under one block table, and the device counters."""
    from ipex_llm_tpu.kv import PagedKVCache, with_recurrent_state

    ec = engine_config
    return with_recurrent_state(PagedKVCache.for_model(
        cfg, ec.n_pages, ec.max_rows, ec.max_pages, ec.page_size,
        storage=ec.kv_storage), cfg, ec.max_rows)
