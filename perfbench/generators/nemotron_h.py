"""The weight generator for ``model_type: nemotron_h``: the tree
``models/build.py`` ``build_hybrid_params`` builds, one stack a block KIND
under ``params["hybrid"]``: ``mamba`` ``[M blocks, ...]``, ``moe`` ``[E
blocks, experts held, ...]``, ``attn`` ``[* blocks, ...]``; a bf16 embedding,
a packed head, the final norm.  Drawn in ONE jitted call, a block (and inside
an expert block an expert) at a time.

The planes are drawn as ``build.py`` leaves them: padded once to the
dequant-matmul kernel's tiles (``quantize/core.py`` ``kernel_padded_shape``),
the padding's scales zero, so that no call pads a weight.

What is not a matrix is drawn as the family initialises it, so that random
weights neither blow the recurrent state up nor erase it: ``dt_bias`` is the
inverse softplus of a step drawn log-uniformly in ``[time_step_min,
time_step_max]`` (floored at ``time_step_floor``), ``A_log = log(A)`` with A
uniform in [1, 16], ``D`` is 1; the conv taps are uniform within
1/sqrt(kernel).  ``e_score_correction_bias`` is small and non-zero.

Two magnitudes are the family's too, and decide whether the comparison with
the reference means anything.  Every projection that writes to the residual
stream (``out_proj``, ``o``, the experts' and the shared expert's ``down``)
is drawn as the family initialises it under ``rescale_prenorm_residual``:
uniform within 1/sqrt(in), divided by sqrt(num_hidden_layers), a gain of
1 / sqrt(3 * 52) = 0.080 where the other planes have ``weights.py``'s 1.2;
and the embedding is drawn at unit scale, so that the stream the blocks add
to is not dwarfed by what they add, as in a trained checkpoint.  With every
plane at 1.2 and the embedding at 1.2 / sqrt(hidden) the 52 blocks (relu2
experts, 2,900 routing decisions a probe) amplify rounding until it
saturates: the float32 reference against ITSELF with bf16 activations
differed by a median 0.36 in log-probability and through fp8 by 1.65, both
near what unrelated models give (my CPU runs at the published widths, PR
28); drawn this way bf16 moves it by a few thousandths and fp8 thirty times
more, which is what a limit between the two needs."""

from __future__ import annotations

import math

import weights


def _padded(k: int, n: int) -> tuple[int, int]:
    from ipex_llm_tpu.quantize.core import kernel_padded_shape

    return kernel_padded_shape(k, n, weights.BLOCK)


def _plane(key, k: int, n: int, gain: float = weights.WEIGHT_GAIN):
    """One sym_int4 weight ``[k, n]`` at its padded size: the logical part
    as ``weights.int4_plane`` draws it (standard deviation ``gain`` /
    sqrt(k), the logical k), the padding inert (zero scales)."""
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
            f"perfbench/generators/nemotron_h.py draws sym_int4 planes "
            f"only, not {qtype!r}")
    h, v = cfg.hidden_size, cfg.vocab_size
    d_in = cfg.ssm_num_heads * cfg.ssm_head_dim
    conv_dim, heads, taps = cfg.ssm_conv_dim, cfg.ssm_num_heads, cfg.ssm_conv_kernel
    ffn, shared = cfg.moe_intermediate_size, cfg.moe_shared_intermediate_size
    held, width = cfg.moe_experts_held, cfg.num_experts
    n_m, n_e, n_a = (cfg.hybrid_pattern.count(c) for c in "ME*")
    planes = {
        "mamba": {"in_proj": (h, d_in + conv_dim + heads),
                  "out_proj": (d_in, h)},
        "moe": {"shared_gate_up": (h, shared), "shared_down": (shared, h)},
        "experts": {"moe_gate_up": (h, ffn), "moe_down": (ffn, h)},
        "attn": {"qkv": (h, cfg.q_dim + 2 * cfg.kv_dim), "o": (cfg.q_dim, h)},
    }
    for group in (*planes.values(), {"lm_head": (h, v)}):
        for name, (k, _) in group.items():
            weights.check_block(name, k)
    dt_min, dt_max = float(hf["time_step_min"]), float(hf["time_step_max"])
    dt_floor = float(hf["time_step_floor"])
    # the planes that write to the residual stream, at the family's scale
    to_stream = 1.0 / math.sqrt(3.0 * cfg.num_layers)
    gains = {"out_proj": to_stream, "o": to_stream, "moe_down": to_stream,
             "shared_down": to_stream}

    def draw(ks, group):
        return {name: _plane(next(ks), *shape,
                             gain=gains.get(name, weights.WEIGHT_GAIN))
                for name, shape in planes[group].items()}

    def mamba(k):
        ks = iter(jax.random.split(k, 9))
        out = draw(ks, "mamba")
        out["norm"] = weights.vector(next(ks), (h,))
        bound = 1.0 / math.sqrt(taps)
        out["conv_w"] = jax.random.uniform(next(ks), (taps, conv_dim),
                                           jnp.float32, -bound, bound)
        out["conv_b"] = jax.random.uniform(next(ks), (conv_dim,), jnp.float32,
                                           -bound, bound)
        dt = jnp.exp(jax.random.uniform(
            next(ks), (heads,), jnp.float32, math.log(dt_min),
            math.log(dt_max)))
        dt = jnp.maximum(dt, dt_floor)
        out["dt_bias"] = dt + jnp.log(-jnp.expm1(-dt))
        out["A_log"] = jnp.log(jax.random.uniform(
            next(ks), (heads,), jnp.float32, 1.0, 16.0))
        out["D"] = jnp.ones((heads,), jnp.float32)
        out["gate_norm"] = weights.vector(next(ks), (d_in,))
        return out

    def moe(k):
        ks = iter(jax.random.split(k, 7))
        out = draw(ks, "moe")
        for name, shape in planes["experts"].items():
            out[name] = weights.stack(
                lambda kk, shape=shape, name=name: _plane(
                    kk, *shape, gain=gains.get(name, weights.WEIGHT_GAIN)),
                next(ks), held)
        out["mlp_norm"] = weights.vector(next(ks), (h,))
        out["router"] = weights.dense(next(ks), (h, width), h, jnp.float32)
        out["router_bias"] = weights.vector(next(ks), (width,), 0.0, 0.01)
        return out

    def attn(k):
        ks = iter(jax.random.split(k, 3))
        out = draw(ks, "attn")
        out["attn_norm"] = weights.vector(next(ks), (h,))
        return out

    def make(key):
        km, ke, ka, k_embed, k_head, k_norm = jax.random.split(key, 6)
        return {"mamba": weights.stack(mamba, km, n_m),
                "moe": weights.stack(moe, ke, n_e),
                "attn": weights.stack(attn, ka, n_a),
                # unit scale: fan_in 1 in weights.dense's 1.2 / sqrt(fan_in)
                "embed": weights.dense(k_embed, (v, h), 1, jnp.bfloat16),
                "lm_head": _plane(k_head, h, v),
                "final_norm": weights.vector(k_norm, (h,))}

    raw = jax.jit(make)(weights.seed_key(seed))
    hybrid = {}
    for kind in ("mamba", "moe", "attn"):
        tree = dict(raw[kind])
        named = dict(planes[kind], **(planes["experts"] if kind == "moe"
                                      else {}))
        for name, shape in named.items():
            tree[name] = weights.qtensor(tree[name], *shape)
        hybrid[kind] = tree
    return {"embed": raw["embed"], "final_norm": raw["final_norm"],
            "hybrid": hybrid,
            "lm_head": weights.qtensor(raw["lm_head"], h, v)}


def tiny(hf: dict) -> dict:
    """The same family at a size a CPU holds (two units ``M E`` and ``M * E``
    of the pattern, half the experts of a narrow router held)."""
    return dict(
        hf, hidden_size=128, num_hidden_layers=5,
        hybrid_override_pattern="MEM*E", num_attention_heads=2,
        num_key_value_heads=1, head_dim=64, mamba_num_heads=4,
        mamba_head_dim=16, n_groups=2, ssm_state_size=16,
        intermediate_size=64, moe_intermediate_size=64,
        moe_shared_expert_intermediate_size=128, n_routed_experts=4,
        n_router_experts=8, first_routed_expert=0, num_experts_per_tok=2,
        vocab_size=256)


def cache_shapes(cfg, engine_config):
    """The cache a tick of this family holds: the paged KV pool over the
    attention blocks and, in the same object, the recurrent state."""
    from ipex_llm_tpu.kv import PagedKVCache, with_recurrent_state

    ec = engine_config
    return with_recurrent_state(PagedKVCache.init(
        cfg.kv_layers, ec.n_pages, ec.max_rows, ec.max_pages,
        cfg.num_kv_heads, ec.page_size, cfg.head_dim), cfg, ec.max_rows)
