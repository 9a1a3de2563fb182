"""The weight generator for ``model_type: xing4_0`` (Xing4.0): the tree
``models/build.py`` builds for a DeepSeek-scheme model with a dense prefix on
HYPER-CONNECTED residual streams.  Two stacks: ``layers_dense``
(``first_k_dense_replace`` layers: the MLA planes and a gated MLP) and
``layers`` (the MLA planes, a float32 router over the router's full width with
its ``e_score_correction_bias``, the HELD experts' two stacks ``[layers,
experts held, ...]`` and the shared expert).  Every layer of either stack
carries, for each of its two sublayers ``<sub>`` in (``attn``, ``mlp``), the
hyper-connection's tensors as ``ipex_llm_tpu/ops/hyper.py`` reads them, all
float32 and never quantized: ``hc_<sub>_proj`` ``[n + n + n*n, n*C]`` (rows:
``P_pre``'s n columns, ``P_post``'s n, ``P_res``'s n*n row-major, each as a
row; columns stream-major as ``vec(X)``), ``hc_<sub>_scale`` ``[3]`` (``a_pre,
a_post, a_res``) and ``hc_<sub>_bias`` ``[n + n + n*n]``.  A bf16 embedding, a
packed head, the final norm and the rotary table.  Drawn in ONE jitted call, a
layer (and inside an expert layer an expert) at a time.

Planes are drawn padded once to the dequant-matmul kernel's tiles, the
padding's scales zero (``kv_a``'s 576 columns become 640).  Magnitudes as for
the other families (a comparison between two precisions means nothing on a
stream the blocks dwarf): every projection that writes to the residual streams
has the gain 1 / sqrt(2 * num_hidden_layers) where the other planes have
``weights.py``'s 1.2; the embedding is drawn at unit scale; the router's bias
is a few hundredths, the size of the gaps between neighbouring sigmoid scores.

But for the ROUTED experts' down projections, drawn at ROUTED_GAIN = a quarter
of that gain.  A random router scores its top experts alike (each of the 4
chosen weighs ``routed_scaling_factor / 4`` = 0.5; a trained one's fourth
choice weighs little) and the gap between the fourth and the fifth score is a
few hundredths, so rounding flips a decision somewhere in 38 expert layers for
nearly every token.  At the other blocks' gain one flip moves the streams by
~4 %, the later routers' inputs by more than their gaps: flips breed flips, and
ANY rounding grows over 40 layers to an error set by the weights and not by
the precision (read at the published widths on the chip and on a depth-40 copy
on the CPU, PERF.md section 6: bf16 0.05, fp8 0.2, a probe's 8 positions
overlapping), which no comparison can use.  At a quarter a flip stays under
the gaps, the served path's error is its rounding (0.016 a position on the
chip) and fp8 reads eleven times that (0.174); on the CPU copy a fifth or a
quarter read alike, a tenth hides the experts (held experts read at their
neighbours' index no longer fail).  A checkpoint's values do not change what
a tick costs.

The hyper-connection is drawn so that its TOKEN-DEPENDENT term does work: ``v``
has unit mean square over its n*C values, so a row of ``proj`` drawn at
standard deviation 1 / sqrt(n*C) gives ``v P`` a standard deviation of 1 across
tokens, and the scalars ``a_*`` lie around 1 (0.8-1.2): each coefficient's
pre-activation moves by order 1 from token to token, and a program that
dropped the dynamic term (coefficients from the biases alone) fails the
comparison (tests/test_xing4.py plants exactly that).  The biases: ``b_pre``
around 0 (a sublayer reads about half of every stream), ``b_post`` around 0
(it writes back at about 1), ``b_res`` 2 on the diagonal and 0 off it, so the
residual mix starts near the identity and the streams stay distinct."""

from __future__ import annotations

import math

import weights


ROUTED_GAIN = 0.25          # module docstring: one flipped decision stays small


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
    """One sublayer's hyper-connection (module docstring)."""
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


def param_tree(cfg, hf: dict, qtype: str, seed: int):
    """The parameter tree of ``cfg`` (the program's ``ModelConfig`` of
    ``hf``), on the default device."""
    import jax
    import jax.numpy as jnp

    if qtype != "sym_int4":
        raise ValueError(
            f"perfbench/generators/xing4_0.py draws sym_int4 planes only, "
            f"not {qtype!r}")
    h, v, heads = cfg.hidden_size, cfg.vocab_size, cfg.num_heads
    rank, q_rank = cfg.kv_lora_rank, cfg.q_lora_rank
    ffn, dense_ffn = cfg.moe_intermediate_size, cfg.intermediate_size
    shared = ffn * max(cfg.num_shared_experts, 1)
    held, width = cfg.moe_experts_held, cfg.num_experts
    n_dense = cfg.moe_layer_start
    n_moe = cfg.num_layers - n_dense
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
    }
    for group in (*planes.values(), {"lm_head": (h, v)}):
        for name, (k, _) in group.items():
            weights.check_block(name, k)
    to_stream = 1.0 / math.sqrt(2.0 * cfg.num_layers)
    gains = {"o": to_stream, "down": to_stream, "shared_down": to_stream,
             "moe_down": to_stream * ROUTED_GAIN}
    norm_shapes = {"attn_norm": (h,), "mlp_norm": (h,), "q_a_norm": (q_rank,),
                   "kv_a_norm": (rank,)}

    def layer(group):
        def one(k):
            ks = iter(jax.random.split(k, 24))
            out = {name: _plane(next(ks), *shape,
                                gain=gains.get(name, weights.WEIGHT_GAIN))
                   for name, shape in planes[group].items()}
            for name, shape in norm_shapes.items():
                out[name] = weights.vector(next(ks), shape)
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

    def make(key):
        kd, km, k_embed, k_head, k_norm = jax.random.split(key, 5)
        return {"layers_dense": weights.stack(layer("dense"), kd, n_dense),
                "layers": weights.stack(layer("moe"), km, n_moe),
                # unit scale: fan_in 1 in weights.dense's 1.2 / sqrt(fan_in)
                "embed": weights.dense(k_embed, (v, h), 1, jnp.bfloat16),
                "lm_head": _plane(k_head, h, v),
                "final_norm": weights.vector(k_norm, (h,))}

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
        out[stack] = tree
    return out


def tiny(hf: dict) -> dict:
    """The same family at a size a CPU holds: two dense layers and three
    expert layers on four streams, 16 experts of which the first 8 are held
    (one chip's share of two)."""
    return dict(
        hf, hidden_size=128, intermediate_size=256, num_hidden_layers=5,
        first_k_dense_replace=2, num_attention_heads=4,
        num_key_value_heads=4, q_lora_rank=64, kv_lora_rank=64,
        qk_nope_head_dim=32, qk_rope_head_dim=32, v_head_dim=32,
        moe_intermediate_size=64, n_routed_experts=8, n_router_experts=16,
        first_routed_expert=0, n_group=1, topk_group=1,
        num_experts_per_tok=4, vocab_size=256)


def cache_shapes(cfg, engine_config):
    """The cache a tick of this family holds: the LATENT pool (one row a
    token a layer, shared by every head) and the held expert layers'
    counters.  The residual streams are activations and hold no cache."""
    from ipex_llm_tpu.kv import PagedKVCache, with_recurrent_state

    ec = engine_config
    return with_recurrent_state(PagedKVCache.for_model(
        cfg, ec.n_pages, ec.max_rows, ec.max_pages, ec.page_size,
        storage=ec.kv_storage), cfg, ec.max_rows)
