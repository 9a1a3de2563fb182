"""The weight generator for ``model_type: falcon_h1``: the tree
``models/build.py`` ``build_hybrid_params`` builds for ``P`` blocks, ONE stack
``params["hybrid"]["parallel"]`` of ``[blocks, ...]`` leaves holding a block's
attention (``attn_norm``, ``qkv``, ``o``), its Mamba-2 mixer (``in_proj``,
``conv_w``, ``conv_b``, ``dt_bias``, ``A_log``, ``D``, ``gate_norm``,
``out_proj``) and its gated MLP (``mlp_norm``, ``gate_up``, ``down``); a bf16
embedding, a packed head, the final norm and the rotary table.  Drawn in ONE
jitted call, a block at a time, the embedding 1024 rows at a time (261120 x
5120 in float32 would be 5.3 GB of temporaries).

The planes are drawn as ``build.py`` leaves them: padded once to the
dequant-matmul kernel's tiles (``quantize/core.py`` ``kernel_padded_shape``:
``in_proj`` N 9248 -> 9344), the padding's scales zero.  ``dt_bias``,
``A_log``, ``D`` and the conv taps are drawn as ``generators/nemotron_h.py``
draws them (the config publishes no ``time_step_*``: the Mamba-2 defaults
0.001 .. 0.1, floor 1e-4).

THE GAINS.  The published multipliers are small because a trained muP
checkpoint's planes are LARGE: with ``weights.py``'s unit gain (1.2 /
sqrt(in)) the key is 0.011 of a unit vector (every softmax flat), ``x`` / ``B``
/ ``dt`` entering the recurrence are 0.04-0.09 (the conv bias drowns them), the
branches' outputs are 0.04, 0.1 and 0.005 of the stream and the logits spread
by 0.01 over 261120 rows: a flat distribution that no precision moves.  So each
plane (a COLUMN RANGE of a merged plane) is drawn at the gain that undoes the
multiplier it meets; the multipliers themselves stay the published ones and
stay on the activations.  With ``u`` a normed input of unit RMS:

- ``qkv``: q and k columns at 1 / sqrt(key_multiplier) each (9.5), so that a
  score ``q . k / sqrt(d)`` has unit deviation (softmax neither flat nor
  one-hot); v columns at 1.
- ``o``: TO_STREAM / attention_out_multiplier (x ATTN_FLAT: a softmax over
  a few hundred keys averages the values down by about that).
- ``in_proj``: the z, x, B, C, dt columns at 1 / (ssm_in_multiplier x their
  entry of ssm_multipliers) (11.3, 16, 22.6, 8, 11.3): unit gate, inputs and
  a ``dt`` pre-activation of unit deviation around ``dt_bias``, as in
  nemotron_h's draw.
- ``out_proj``: TO_STREAM / ssm_out_multiplier (its input is group-normed:
  unit RMS whatever the state's size).
- ``gate_up``: gate columns at 1 / mlp_multipliers[0] (a unit argument of
  silu), up columns at 1; ``down``: TO_STREAM / (mlp_multipliers[1] x
  GATED_RMS), GATED_RMS = 0.6 the RMS of ``up * silu(gate)`` at unit inputs.
- ``lm_head``: LOGIT_STD / lm_head_multiplier (256): logits of deviation 2
  over 261120 rows: the greedy token near -3 nats, the distribution's
  entropy several nats under uniform.
- the embedding at 1 / embedding_multiplier: a stream of unit RMS.

TO_STREAM = 0.2: each branch adds a fifth of a unit stream a block, so after
24 blocks the three sums (each ~ sqrt(24) x 0.2 = 1) are of the stream's own
order, as in a trained checkpoint, and none is a rounding error beside it.
What this buys, read on a depth-24 CPU copy of the tiny model
(tests/test_perfbench_falcon_h1.py keeps the reading; PERF.md section 6, PR
46): bf16 activations move the reference's log-probabilities by 0.013-0.017
in the mean, fp8 activations by 0.23-0.27 (an order more), an fp8 state by
0.07-0.09; a bfloat16 state by nothing that shows (references/falcon_h1.py
says where its precision is held instead).  At the published widths on the
chip the same three read 0.012-0.028, 0.12-0.51 and 0.08-0.39."""

from __future__ import annotations

import math

import weights

TO_STREAM = 0.2       # a branch's output RMS a block, of a unit stream
ATTN_FLAT = 4.0       # what averaging over the attended keys costs the values
GATED_RMS = 0.6       # RMS of up * silu(gate) at unit-deviation inputs
LOGIT_STD = 2.0
DT_MIN, DT_MAX, DT_FLOOR = 1e-3, 1e-1, 1e-4      # Mamba-2's defaults


def _padded(k: int, n: int) -> tuple[int, int]:
    from ipex_llm_tpu.quantize.core import kernel_padded_shape

    return kernel_padded_shape(k, n, weights.BLOCK)


def _plane(key, k: int, n: int, gains):
    """One sym_int4 weight ``[k, n]`` at its padded size.  ``gains``: [(width,
    gain), ...] over the columns: a range's dequantized weights have the
    standard deviation gain / sqrt(k); the padding is inert (zero scales)."""
    import jax.numpy as jnp

    kp, np_ = _padded(k, n)
    data, scales = weights.int4_plane(key, kp, np_)
    col = jnp.concatenate(
        [jnp.full((w,), g / weights.WEIGHT_GAIN * math.sqrt(kp / k),
                  jnp.float32) for w, g in gains]
        + [jnp.zeros((np_ - n,), jnp.float32)])
    live = (jnp.arange(kp // weights.BLOCK) < k // weights.BLOCK)[:, None]
    return data, jnp.where(live, scales.astype(jnp.float32) * col[None, :],
                           0).astype(scales.dtype)


def plane_gains(cfg, hf: dict) -> dict:
    """{plane: ((k, n), [(columns, gain), ...])}: the module docstring's
    table, from the multipliers the configuration publishes."""
    h, ffn, v = cfg.hidden_size, cfg.intermediate_size, cfg.vocab_size
    d_in = cfg.ssm_num_heads * cfg.ssm_head_dim
    gn = cfg.ssm_n_groups * cfg.ssm_state_size
    qk = 1.0 / math.sqrt(cfg.key_multiplier * cfg.attention_in_multiplier ** 2)
    mup = [cfg.ssm_in_multiplier * m for m in cfg.ssm_multipliers]
    gate_m, down_m = cfg.mlp_multipliers
    return {
        "qkv": ((h, cfg.q_dim + 2 * cfg.kv_dim),
                [(cfg.q_dim, qk), (cfg.kv_dim, qk),
                 (cfg.kv_dim, 1.0 / cfg.attention_in_multiplier)]),
        "o": ((cfg.q_dim, h),
              [(h, TO_STREAM * ATTN_FLAT / cfg.attention_out_multiplier)]),
        "in_proj": ((h, 2 * d_in + 2 * gn + cfg.ssm_num_heads),
                    [(w, 1.0 / m) for w, m in zip(
                        (d_in, d_in, gn, gn, cfg.ssm_num_heads), mup)]),
        "out_proj": ((d_in, h), [(h, TO_STREAM / cfg.ssm_out_multiplier)]),
        "gate_up": ((h, 2 * ffn), [(ffn, 1.0 / gate_m), (ffn, 1.0)]),
        "down": ((ffn, h), [(h, TO_STREAM / (down_m * GATED_RMS))]),
        "lm_head": ((h, v), [(v, LOGIT_STD / cfg.logit_scale)]),
    }


def param_tree(cfg, hf: dict, qtype: str, seed: int):
    """The parameter tree of ``cfg`` (the program's ``ModelConfig`` of
    ``hf``), on the default device."""
    import jax
    import jax.numpy as jnp

    if qtype != "sym_int4":
        raise ValueError(
            f"perfbench/generators/falcon_h1.py draws sym_int4 planes only, "
            f"not {qtype!r}")
    h, v, n = cfg.hidden_size, cfg.vocab_size, cfg.num_layers
    d_in, heads = cfg.ssm_num_heads * cfg.ssm_head_dim, cfg.ssm_num_heads
    conv_dim, taps = cfg.ssm_conv_dim, cfg.ssm_conv_kernel
    planes = plane_gains(cfg, hf)
    for name, ((k, _), _) in planes.items():
        weights.check_block(name, k)
    head = planes.pop("lm_head")
    embed_rows = math.gcd(v, 1024)

    def block(k):
        ks = iter(jax.random.split(k, len(planes) + 8))
        out = {name: _plane(next(ks), *shape, gains)
               for name, (shape, gains) in planes.items()}
        out["attn_norm"] = weights.vector(next(ks), (h,))
        out["mlp_norm"] = weights.vector(next(ks), (h,))
        out["gate_norm"] = weights.vector(next(ks), (d_in,))
        bound = 1.0 / math.sqrt(taps)
        out["conv_w"] = jax.random.uniform(next(ks), (taps, conv_dim),
                                           jnp.float32, -bound, bound)
        out["conv_b"] = jax.random.uniform(next(ks), (conv_dim,), jnp.float32,
                                           -bound, bound)
        dt = jnp.exp(jax.random.uniform(
            next(ks), (heads,), jnp.float32, math.log(DT_MIN),
            math.log(DT_MAX)))
        dt = jnp.maximum(dt, DT_FLOOR)
        out["dt_bias"] = dt + jnp.log(-jnp.expm1(-dt))
        out["A_log"] = jnp.log(jax.random.uniform(
            next(ks), (heads,), jnp.float32, 1.0, 16.0))
        out["D"] = jnp.ones((heads,), jnp.float32)
        return out

    def embed_rows_of(k):
        return (jax.random.normal(k, (embed_rows, h), jnp.float32)
                / cfg.embedding_multiplier).astype(jnp.bfloat16)

    def make(key):
        k_blocks, k_embed, k_head, k_norm = jax.random.split(key, 4)
        return {"parallel": weights.stack(block, k_blocks, n),
                "embed": weights.stack(embed_rows_of, k_embed,
                                       v // embed_rows).reshape(v, h),
                "lm_head": _plane(k_head, *head[0], head[1]),
                "final_norm": weights.vector(k_norm, (h,))}

    raw = jax.jit(make)(weights.seed_key(seed))
    tree = dict(raw["parallel"])
    for name, (shape, _) in planes.items():
        tree[name] = weights.qtensor(tree[name], *shape)
    return {
        "embed": raw["embed"], "final_norm": raw["final_norm"],
        "hybrid": {"parallel": tree},
        "lm_head": weights.qtensor(raw["lm_head"], *head[0]),
        "inv_freq": jnp.asarray(
            cfg.rope.inv_freq(cfg.max_position_embeddings), jnp.float32),
        "rope_mscale": float(cfg.rope.mscale(cfg.max_position_embeddings)),
    }


def tiny(hf: dict) -> dict:
    """The same family at a size a CPU holds: two blocks, a GQA group of
    FIVE as published (5 query heads on 1 kv head), two state groups, and the
    state as WIDE as published (256: ``C . h`` sums over it, and with a
    narrow state the skip term ``D x`` drowns it and the state's precision
    moves nothing)."""
    return dict(
        hf, hidden_size=128, num_hidden_layers=2, num_attention_heads=5,
        num_key_value_heads=1, head_dim=64, intermediate_size=256,
        mamba_n_heads=4, mamba_d_head=16, mamba_d_ssm=64, mamba_n_groups=2,
        vocab_size=256)


def cache_shapes(cfg, engine_config):
    """The cache a tick of this family holds: the paged KV pool, a layer a
    block, and in the same object the recurrent state, a slot a block (as
    the engine holds it ONCE: ``rehearse_memory.py`` hands the tick the
    second buffer as ``state_src``)."""
    from ipex_llm_tpu.kv import PagedKVCache, with_recurrent_state

    ec = engine_config
    return with_recurrent_state(PagedKVCache.init(
        cfg.kv_layers, ec.n_pages, ec.max_rows, ec.max_pages,
        cfg.num_kv_heads, ec.page_size, cfg.head_dim), cfg, ec.max_rows)
