"""The weight generator for ``model_type: glm5_next_text`` (GLM-5.3): the tree
``models/build.py`` builds for a DeepSeek-scheme model with a dense prefix
whose MIXER KIND varies by layer, on hyper-connected residual streams.  Two
stacks, ``layers_dense`` (``first_k_dense_replace`` layers: a gated MLP) and
``layers`` (a float32 router over the router's full width with its
``e_score_correction_bias``, the HELD experts' two stacks ``[layers, experts
held, ...]`` and the shared expert).  Every layer of either stack holds its
two norms and, for each of its two sublayers, the hyper-connection's float32
tensors as ``ipex_llm_tpu/ops/hyper.py`` reads them (``hc_<sub>_proj``,
``_scale``, ``_bias``; generators/xing4_0.py has their drawing).  The mixers'
tensors are stacked per KIND, an entry a layer of that kind of the stack:

- ``kda`` (a ``linear_attention`` layer; ``ipex_llm_tpu/ops/kda.py``):
  ``in_proj`` ``[hidden, 3 H D + D + D + H]`` (q, k, v, the decay gate's and
  the output gate's down projections, the write strength), ``conv_w`` ``[K, 3
  H D]``, ``f_b`` / ``g_b`` ``[D, H D]``, ``A_log`` ``[H]``, ``dt_bias`` ``[H
  D]``, ``o_norm`` ``[D]``, ``o``;
- ``mla`` (a ``deepseek_sparse_attention`` layer): the MLA planes ``q_a``,
  ``q_a_norm``, ``q_b``, ``kv_a`` (``kv_lora_rank`` columns: no rotated
  slice), ``kv_a_norm``, ``kv_b``, ``o``; and beside it ``indexer``, an entry
  a layer of that kind as well: ``wq_b``, ``wk``, the key's LayerNorm weight
  and bias, the float32 head weights' projection.

A bf16 embedding, a packed head, the final norm; no rotary table.  Drawn in
ONE jitted call, a layer (and inside an expert layer an expert) at a time.

Magnitudes as for the other families: every projection that writes to the
residual streams has the gain 1 / sqrt(2 * num_hidden_layers) where the other
planes have ``weights.py``'s 1.2; the embedding is drawn at unit scale; the
router's bias is a few hundredths.  The KDA gate is drawn so that the decays
SPREAD: ``exp(A_log)`` around 1 and ``dt_bias`` around -4 with a spread of
1.5 put ``g = gate_lower_bound * sigmoid(.)`` between about -0.01 (a channel
that remembers hundreds of tokens) and -2 (one that forgets within a few),
so both the state hand-over between blocks and the strongly decayed pairs
inside a block carry weight in the comparison."""

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


def _hyper_connection(key, n: int, c: int, sub: str) -> dict:
    """One sublayer's hyper-connection (generators/xing4_0.py: the dynamic
    term does work, the residual mix starts near the identity)."""
    import jax
    import jax.numpy as jnp

    kp, ks, kb = jax.random.split(key, 3)
    rows = n + n + n * n
    diag = jnp.concatenate([jnp.zeros((2 * n,), jnp.float32),
                            2.0 * jnp.eye(n, dtype=jnp.float32).reshape(-1)])
    return {
        f"hc_{sub}_proj": jax.random.normal(kp, (rows, n * c), jnp.float32)
        / math.sqrt(n * c),
        f"hc_{sub}_scale": weights.vector(ks, (3,), 1.0, 0.1),
        f"hc_{sub}_bias": diag + weights.vector(kb, (rows,), 0.0, 0.3),
    }


def plane_shapes(cfg) -> dict:
    """{group: {plane: logical [in, out]}} of every packed plane."""
    h, heads = cfg.hidden_size, cfg.num_heads
    rank, q_rank = cfg.kv_lora_rank, cfg.q_lora_rank
    ffn = cfg.moe_intermediate_size
    shared = ffn * max(cfg.num_shared_experts, 1)
    kh, kd = cfg.kda_num_heads, cfg.kda_head_dim
    return {
        "dense": {"gate_up": (h, 2 * cfg.intermediate_size),
                  "down": (cfg.intermediate_size, h)},
        "moe": {"shared_gate_up": (h, 2 * shared),
                "shared_down": (shared, h)},
        "experts": {"moe_gate_up": (h, 2 * ffn), "moe_down": (ffn, h)},
        "kda": {"in_proj": (h, 3 * kh * kd + 2 * kd + kh),
                "f_b": (kd, kh * kd), "g_b": (kd, kh * kd),
                "o": (kh * kd, h)},
        "mla": {"q_a": (h, q_rank), "q_b": (q_rank, heads * cfg.head_dim),
                "kv_a": (h, rank + cfg.qk_rope_head_dim),
                "kv_b": (rank, heads * (cfg.qk_nope_head_dim + cfg.v_dim)),
                "o": (heads * cfg.v_dim, h)},
        "indexer": {"wq_b": (q_rank, cfg.index_n_heads * cfg.index_head_dim),
                    "wk": (h, cfg.index_head_dim)},
    }


def param_tree(cfg, hf: dict, qtype: str, seed: int):
    """The parameter tree of ``cfg`` (the program's ``ModelConfig`` of
    ``hf``), on the default device."""
    import jax
    import jax.numpy as jnp

    if qtype != "sym_int4":
        raise ValueError(
            f"perfbench/generators/glm5_next_text.py draws sym_int4 planes "
            f"only, not {qtype!r}")
    h, v = cfg.hidden_size, cfg.vocab_size
    held, width = cfg.moe_experts_held, cfg.num_experts
    n_dense = cfg.moe_layer_start
    kh, kd = cfg.kda_num_heads, cfg.kda_head_dim
    planes = plane_shapes(cfg)
    for group in (*planes.values(), {"lm_head": (h, v)}):
        for name, (k, _) in group.items():
            weights.check_block(name, k)
    to_stream = 1.0 / math.sqrt(2.0 * cfg.num_layers)
    gains = {"o": to_stream, "down": to_stream, "moe_down": to_stream,
             "shared_down": to_stream}
    ranges = {"dense": (0, n_dense), "moe": (n_dense, cfg.num_layers)}
    count = {g: {k: sum(m == k for m in cfg.mixer_types[lo:hi])
                 for k in ("kda", "mla")} for g, (lo, hi) in ranges.items()}

    def draw(ks, group):
        return {name: _plane(next(ks), *shape,
                             gain=gains.get(name, weights.WEIGHT_GAIN))
                for name, shape in planes[group].items()}

    def layer(group):
        def one(k):
            ks = iter(jax.random.split(k, 16))
            out = draw(ks, group)
            for name in ("attn_norm", "mlp_norm"):
                out[name] = weights.vector(next(ks), (h,))
            for sub in ("attn", "mlp"):
                out.update(_hyper_connection(next(ks), cfg.hc_mult, h, sub))
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

    def kda(k):
        ks = iter(jax.random.split(k, 12))
        out = draw(ks, "kda")
        out["conv_w"] = weights.vector(
            next(ks), (cfg.kda_conv_kernel, 3 * kh * kd), 0.0, 0.5)
        out["A_log"] = weights.vector(next(ks), (kh,), 0.0, 0.3)
        out["dt_bias"] = weights.vector(next(ks), (kh * kd,), -4.0, 1.5)
        out["o_norm"] = weights.vector(next(ks), (kd,))
        return out

    def mla(k):
        ks = iter(jax.random.split(k, 8))
        out = draw(ks, "mla")
        out["q_a_norm"] = weights.vector(next(ks), (cfg.q_lora_rank,))
        out["kv_a_norm"] = weights.vector(next(ks), (cfg.kv_lora_rank,))
        return out

    def indexer(k):
        ks = iter(jax.random.split(k, 8))
        out = draw(ks, "indexer")
        out["k_norm"] = weights.vector(next(ks), (cfg.index_head_dim,))
        out["k_norm_bias"] = weights.vector(next(ks), (cfg.index_head_dim,),
                                            0.0)
        out["w_proj"] = weights.dense(next(ks), (h, cfg.index_n_heads), h,
                                      jnp.float32)
        return out

    subs = {"kda": (kda, "kda"), "mla": (mla, "mla"),
            "indexer": (indexer, "mla")}

    def make(key):
        kd_, km, k_embed, k_head, k_norm, k_sub = jax.random.split(key, 6)
        out = {"layers_dense": weights.stack(layer("dense"), kd_, n_dense),
               "layers": weights.stack(layer("moe"), km,
                                       cfg.num_layers - n_dense),
               # unit scale: fan_in 1 in weights.dense's 1.2 / sqrt(fan_in)
               "embed": weights.dense(k_embed, (v, h), 1, jnp.bfloat16),
               "lm_head": _plane(k_head, h, v),
               "final_norm": weights.vector(k_norm, (h,))}
        keys = iter(jax.random.split(k_sub, 2 * len(subs)))
        for group in ranges:
            for name, (fn, kind) in subs.items():
                k = next(keys)
                if count[group][kind]:
                    out[f"{name}_{group}"] = weights.stack(
                        fn, k, count[group][kind])
        return out

    raw = jax.jit(make)(weights.seed_key(seed))
    out = {"embed": raw["embed"], "final_norm": raw["final_norm"],
           "lm_head": weights.qtensor(raw["lm_head"], h, v)}
    for stack, group in (("layers_dense", "dense"), ("layers", "moe")):
        tree = dict(raw[stack])
        named = dict(planes[group], **(planes["experts"] if group == "moe"
                                       else {}))
        for name, shape in named.items():
            tree[name] = weights.qtensor(tree[name], *shape)
        for name in subs:
            if f"{name}_{group}" in raw:
                sub = dict(raw[f"{name}_{group}"])
                for plane, shape in planes[name].items():
                    sub[plane] = weights.qtensor(sub[plane], *shape)
                tree[name] = sub
        out[stack] = tree
    return out


def tiny(hf: dict) -> dict:
    """The same family at a size a CPU holds: a KDA layer with a dense MLP,
    then a sparse-attention layer and a KDA layer with expert layers (16
    experts, the first 4 held), four streams, 16 cached tokens = 4 pooled
    blocks chosen.  The KDA head keeps its 128 dims (the chunk kernel's lane
    tile), two heads of them."""
    return dict(
        hf, hidden_size=128, intermediate_size=256, num_hidden_layers=3,
        first_k_dense_replace=1, num_attention_heads=4,
        num_key_value_heads=4, q_lora_rank=64, kv_lora_rank=128,
        qk_nope_head_dim=32, qk_head_dim=32, v_head_dim=32,
        moe_intermediate_size=64, n_routed_experts=4, n_router_experts=16,
        first_routed_expert=0, num_experts_per_tok=4, vocab_size=256,
        index_topk=16, index_n_heads=4, index_head_dim=32,
        layer_types=["linear_attention", "deepseek_sparse_attention",
                     "linear_attention"],
        mlp_layer_types=["dense", "sparse", "sparse"],
        indexer_types=["full"] * 3,
        linear_attn_config=dict(hf["linear_attn_config"], num_heads=2,
                                kda_layers=[0, 2], full_attn_layers=[1]))


def cache_shapes(cfg, engine_config):
    """The cache a tick of this family holds: the SPARSE latent pool of its
    sparse-attention layers (a latent row a token; a pooled index key a
    block of ``index_kpool`` tokens) under one block table, and the per-row
    state of its KDA layers, the indexer's key tails and the counters."""
    from ipex_llm_tpu.kv import PagedKVCache, with_recurrent_state

    ec = engine_config
    return with_recurrent_state(PagedKVCache.for_model(
        cfg, ec.n_pages, ec.max_rows, ec.max_pages, ec.page_size,
        storage=ec.kv_storage), cfg, ec.max_rows)
