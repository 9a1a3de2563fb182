"""Pipeline-parallel microbatch scheduler (shard_map + ppermute).

Reference counterpart: ``PPModelWorker`` (reference
pipeline_parallel.py:482-928), which overlaps microbatches across pipeline
stages with torch.distributed send/recv between ranks.  The r2 repo only
stage-sharded the layer stack under GSPMD, which executes stages
sequentially — (pp-1)/pp of the chips idle at any instant (VERDICT r2
missing #5).

TPU-native redesign: a software pipeline inside ONE jitted program.

- the stacked layer tree shards its layer axis over the ``pp`` mesh axis
  (the sharding parallel/shard.py already applies); under
  ``shard_map(manual={'pp'})`` each stage holds ``L/pp`` layers;
- the batch splits into M microbatches; a ``lax.scan`` over
  ``M + pp - 1`` ticks runs every stage on its current microbatch and
  rotates activations stage→stage+1 with ``lax.ppermute`` — after the
  pp-1-tick fill, ALL stages compute every tick (the GPipe schedule);
- stage 0 injects microbatch t at tick t; the last stage's outputs are
  psum-broadcast back (only it contributes non-zero rows).

Each stage's layer chunk runs through the SAME compiled layer body as
everything else (models/decoder.run_layers), so MoE / ALiBi / qk-norm
families pipeline unchanged.  Works for cacheless full-sequence forwards:
training steps and prefill-for-logits.  ``jax.grad`` through the pipeline
is valid (ppermute is differentiable), giving pipelined training for free.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ipex_llm_tpu.models.config import ModelConfig
from ipex_llm_tpu.ops import collectives


def _stage_specs(tree) -> object:
    """P('pp', ...) on the stacked layer axis of every leaf."""
    return jax.tree_util.tree_map(
        lambda leaf: P(*(("pp",) + (None,) * (leaf.ndim - 1))), tree
    )


@partial(jax.jit, static_argnames=("cfg", "n_micro", "mesh"))
def pipeline_forward(
    cfg: ModelConfig,
    params: dict,
    tokens: jnp.ndarray,          # [B, T] (B divisible by n_micro)
    mesh,
    n_micro: int,
) -> jnp.ndarray:
    """Microbatch-pipelined full-sequence logits [B, T, V].

    Embedding / final norm / lm head run replicated outside the pipeline
    (they are a sliver of the FLOPs); the layer stack runs the GPipe
    schedule across the ``pp`` axis.
    """
    from ipex_llm_tpu.kv import KVCache
    from ipex_llm_tpu.models.decoder import (
        alibi_bias_for,
        embed_prelude,
        local_rope_tables,
        logits_tail,
        run_layers,
    )

    if "layers_dense" in params:
        raise NotImplementedError(
            "dense-prefix MoE models don't pipeline yet (two stacks)"
        )
    pp = mesh.shape["pp"]
    b, t = tokens.shape
    if b % n_micro:
        raise ValueError(f"batch {b} not divisible by n_micro {n_micro}")
    bm = b // n_micro

    # the SAME prelude/tail decoder_forward uses (embed multiplier, learned
    # positions, embed norm, rope/M-ROPE) — pipelining must never have its
    # own partial copy of family semantics
    pos = jnp.broadcast_to(jnp.arange(t)[None, :], (b, t))
    x, cos, sin = embed_prelude(cfg, params, tokens, pos)
    cos_l, sin_l = local_rope_tables(cfg, params, pos)
    mbs = x.reshape(n_micro, bm, t, x.shape[-1])
    # rows are position-identical: slice per-microbatch cos/sin views
    cos = None if cos is None else cos[:bm]
    sin = None if sin is None else sin[:bm]
    cos_l = None if cos_l is None else cos_l[:bm]
    sin_l = None if sin_l is None else sin_l[:bm]

    q_slots = jnp.broadcast_to(jnp.arange(t)[None, :], (bm, t))
    kv_len = jnp.full((bm,), t, jnp.int32)
    alibi_bias = alibi_bias_for(cfg, q_slots, t) if cfg.alibi else None
    sliding_flags = jnp.array(
        [cfg.layer_is_sliding(l) for l in range(cfg.num_layers)], dtype=bool
    )

    def stages(layer_tree, flags, mb_all):
        """Runs on every pp stage with its local L/pp layer chunk."""
        stage = jax.lax.axis_index("pp")
        n_local = cfg.num_layers // pp
        # scratch cache for the local chunk (cacheless full-seq attention)
        cache = KVCache.init(n_local, bm, t, cfg.num_kv_heads, cfg.head_dim,
                             v_head_dim=cfg.v_dim)

        def run_chunk(xa):
            y, _, _, _ = run_layers(
                cfg, layer_tree, cache.k, cache.v, flags, xa, cos, sin,
                jnp.asarray(0, jnp.int32), q_slots, kv_len, None, cache,
                alibi_bias=alibi_bias, cos_local=cos_l, sin_local=sin_l,
            )
            return y

        def tick(carry, ti):
            state, outs = carry
            inject = jax.lax.dynamic_index_in_dim(
                mb_all, jnp.clip(ti, 0, n_micro - 1), keepdims=False
            )
            xin = jnp.where(stage == 0, inject, state)
            xout = run_chunk(xin)
            # the last stage finished microbatch ti - (pp-1)
            done_idx = jnp.clip(ti - (pp - 1), 0, n_micro - 1)
            contrib = jnp.where(
                (stage == pp - 1) & (ti >= pp - 1), xout,
                jnp.zeros_like(xout),
            )
            outs = jax.lax.dynamic_update_index_in_dim(
                outs,
                jax.lax.dynamic_index_in_dim(outs, done_idx, keepdims=False)
                + contrib,
                done_idx, 0,
            )
            # rotate stage s -> s+1 for the next tick
            state = jax.lax.ppermute(
                xout, "pp", [(i, (i + 1) % pp) for i in range(pp)]
            )
            return (state, outs), None

        outs0 = jnp.zeros_like(mb_all)
        state0 = jnp.zeros_like(mb_all[0])
        (_, outs), _ = jax.lax.scan(
            tick, (state0, outs0), jnp.arange(n_micro + pp - 1)
        )
        # only the last stage holds real (non-zero) outputs: the psum is a
        # broadcast of its rows to every stage.  The collective family
        # (ops/collectives.py) owns the payload story — f32 accumulation,
        # and the XLA:CPU AllReducePromotion crash handled inside the
        # family instead of a blanket promotion at every call site.
        return collectives.psum_exact(outs, "pp")

    out = jax.shard_map(
        stages,
        mesh=mesh,
        in_specs=(_stage_specs(params["layers"]), P("pp"), P()),
        out_specs=P(),
        check_vma=False,
        # only pp is manual: on a composed mesh the other axes stay
        # under GSPMD inside the region
        axis_names={"pp"},
    )(params["layers"], sliding_flags, mbs)

    return logits_tail(cfg, params, out.reshape(b, t, -1))


@partial(jax.jit, static_argnames=("cfg", "mesh", "n_micro"),
         donate_argnums=(2,))
def pp_decode_step(
    cfg: ModelConfig,
    params: dict,
    cache,                       # PagedKVCache, pool layer axis pp-sharded
    toks: jnp.ndarray,           # [R] current token — or [R, T] wide step
    row_lens: jnp.ndarray,       # [R] slots already in cache
    mesh,
    n_micro: int,
):
    """Pipelined SERVING decode step (the PPModelWorker peer, reference
    pipeline_parallel.py:482-928): the engine's row pool splits into
    ``n_micro`` request groups that flow through the pp stages in the GPipe
    schedule, each stage holding L/pp layers AND the matching L/pp slice of
    the paged KV pool.  After the pp-1-tick fill every stage decodes a
    different request group each tick — the stage-sequential GSPMD decode
    keeps (pp-1)/pp chips idle instead.

    ``toks`` may be [R] (plain decode) or [R, T] (the speculative verify
    step's [cur_tok; drafts] window): each group's T tokens ride one
    microbatch, so speculative serving pipelines exactly like plain decode.

    Fused-horizon contract: the engine's `_horizon_step` entry routes pp
    meshes here with H pinned to 1 — scanning a decode horizon over this
    schedule would nest a full GPipe fill/drain (pp-1 bubble ticks) inside
    every horizon step, and the stage-sharded pool would have to ride the
    scan carry.  Pipelining the horizon (fill the schedule with H
    successive tokens of the same groups) is the designed follow-up; until
    then pp decode re-uploads per step like the historical path.

    Writes go through each group's block tables; drain/fill ticks run with
    all-(-1) tables so their garbage lands on the scratch page (kv.py
    update_layer contract).  Returns (logits [R, V] for 1-D input,
    [R, T, V] for 2-D, and the updated cache).
    """
    from dataclasses import replace as _dc_replace

    from ipex_llm_tpu.models.decoder import (
        alibi_bias_for,
        embed_prelude,
        local_rope_tables,
        logits_tail,
        run_layers,
    )

    if "layers_dense" in params:
        raise NotImplementedError("dense-prefix MoE models don't pipeline yet")
    pp = mesh.shape["pp"]
    wide = toks.ndim == 2
    tokens = toks if wide else toks[:, None]     # [R, T]
    r, t_w = tokens.shape
    if r % n_micro:
        raise ValueError(f"rows {r} not divisible by n_micro {n_micro}")
    rm = r // n_micro

    pos = row_lens[:, None] + jnp.arange(t_w)[None, :]   # [R, T]
    x, cos, sin = embed_prelude(cfg, params, tokens, pos)
    cos_l, sin_l = local_rope_tables(cfg, params, pos)

    def grp(a):
        return None if a is None else a.reshape(n_micro, rm, *a.shape[1:])

    # everything the stage body reads must enter through shard_map args —
    # closing over auto-context arrays inside the manual region is invalid
    aux = {"x": x.reshape(n_micro, rm, t_w, x.shape[-1]),
           "tables": cache.tables.reshape(n_micro, rm, -1),
           "lens": row_lens.reshape(n_micro, rm)}
    for name, a in (("cos", grp(cos)), ("sin", grp(sin)),
                    ("cos_l", grp(cos_l)), ("sin_l", grp(sin_l))):
        if a is not None:
            aux[name] = a
    sliding_flags = jnp.array(
        [cfg.layer_is_sliding(l) for l in range(cfg.num_layers)], dtype=bool
    )

    def stages(layer_tree, flags, k_loc, v_loc, aux):
        stage = jax.lax.axis_index("pp")

        def pick(name, mi):
            a = aux.get(name)
            return None if a is None else jax.lax.dynamic_index_in_dim(
                a, mi, keepdims=False)

        def tick(carry, ti):
            state, k_loc, v_loc, outs = carry
            mi = ti - stage                       # this stage's group id
            valid = (mi >= 0) & (mi < n_micro)
            mic = jnp.clip(mi, 0, n_micro - 1)
            xin = jnp.where(stage == 0, pick("x", mic), state)
            # fill/drain ticks write to the scratch page, never live pages
            tabs = jnp.where(valid, pick("tables", mic), -1)
            lens = pick("lens", mic)
            q_slots = lens[:, None] + jnp.arange(t_w)[None, :]
            group_cache = _dc_replace(cache, k=k_loc, v=v_loc, tables=tabs)
            bias = (alibi_bias_for(cfg, q_slots, cache.max_len)
                    if cfg.alibi else None)
            y, k_loc, v_loc, _ = run_layers(
                cfg, layer_tree, k_loc, v_loc, flags, xin,
                pick("cos", mic), pick("sin", mic), lens, q_slots,
                lens + t_w, None, group_cache, alibi_bias=bias,
                cos_local=pick("cos_l", mic), sin_local=pick("sin_l", mic),
            )
            contrib = jnp.where((stage == pp - 1) & valid, y,
                                jnp.zeros_like(y))
            outs = jax.lax.dynamic_update_index_in_dim(
                outs,
                jax.lax.dynamic_index_in_dim(outs, mic, keepdims=False)
                + contrib,
                mic, 0,
            )
            state = jax.lax.ppermute(
                y, "pp", [(i, (i + 1) % pp) for i in range(pp)]
            )
            return (state, k_loc, v_loc, outs), None

        outs0 = jnp.zeros_like(aux["x"])
        (_, k_loc, v_loc, outs), _ = jax.lax.scan(
            tick, (jnp.zeros_like(aux["x"][0]), k_loc, v_loc, outs0),
            jnp.arange(n_micro + pp - 1),
        )
        # exact-family psum: see pipeline_forward (the collective family
        # owns the CPU AllReducePromotion workaround)
        return collectives.psum_exact(outs, "pp"), k_loc, v_loc

    pool_spec = P("pp", None, None, None, None)
    aux_specs = jax.tree_util.tree_map(lambda _: P(), aux)
    out, k_new, v_new = jax.shard_map(
        stages,
        mesh=mesh,
        in_specs=(_stage_specs(params["layers"]), P("pp"), pool_spec,
                  pool_spec, aux_specs),
        out_specs=(P(), pool_spec, pool_spec),
        check_vma=False,
        axis_names={"pp"},   # only pp is manual (see pipeline_forward)
    )(params["layers"], sliding_flags, cache.k, cache.v, aux)

    logits = logits_tail(cfg, params, out.reshape(r, t_w, -1))
    if not wide:
        logits = logits[:, 0]
    return logits, _dc_replace(cache, k=k_new, v=v_new)
