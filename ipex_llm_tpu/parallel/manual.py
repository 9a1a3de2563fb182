"""Manual-mesh tensor parallelism for the fused serving tick.

Even where GSPMD partitions this engine correctly it is free to insert
reshards between ops, and what it inserts changes with the compiler.  This
module takes the compiler out of the loop for the serving hot path: the
ENTIRE fused tick — ragged prefill chunk, on-device first-token merge, the
speculative and plain decode-horizon loops — executes inside ONE
``shard_map`` region with every mesh axis manual, per-shard paged-KV
pools, and EXPLICIT collectives
(ops/collectives.py) at exactly the two row-parallel combine points per
layer plus one lm-head all-gather per sampled position.  Per-shard compute
is the UNMODIFIED single-chip decoder over a shard-local ``ModelConfig``
(heads divided by tp), so the tick's program structure — and JP106's ==1
dispatch — is identical at every tp degree.

The Megatron dataflow (arxiv 2112.09017's layout discipline):

- qkv / gate_up: column-parallel.  The packed projections are RE-LAID-OUT
  at placement time (:func:`relayout_packed`): out-columns permute from
  ``[q | k | v]`` to ``[q_0 k_0 v_0 | q_1 k_1 v_1 | ...]`` so a contiguous
  column shard holds shard s's heads of ALL THREE sections and the
  in-region ``qkv[..., :q_dim_local]`` split is correct per shard.  A pure
  permutation: every column's dot product is untouched, so the global math
  is bit-identical to the unpermuted single-chip weight.
- o / down: row-parallel — the ONLY cross-chip math.  The per-shard f32
  partial products combine through ``collectives.all_reduce`` under the
  engine's wire family (exact "bf16" by default; EQuARX-style "e5m2" /
  "int8" opt-in).
- attention: head-local per shard over the shard's slice of the paged
  pool (``shard_paged_cache``'s head split) — zero collectives.
- embed / norms / rope tables: replicated (the embed gather is a sliver;
  replication keeps it exact and keeps token ids out of collectives).
- lm_head: column-parallel when vocab divides; the [R, V/tp] logits
  all-gather back to full width inside ``logits_tail`` right before
  sampling (sampling then runs replicated — every shard draws the same
  token from the same key, so engine state stays replicated for free).

Everything else in the tick body — the first-token merge scatters, the
n-gram proposer, acceptance walks, PRNG splits — computes on replicated
operands and is therefore shard-invariant by construction.
"""

from __future__ import annotations

from dataclasses import replace as _dc_replace

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from ipex_llm_tpu.models.config import ModelConfig
from ipex_llm_tpu.quantize.core import QTensor

# column-parallel packed projections and their section widths (cfg-derived)
_COL_BIAS = ("qkv_bias", "gate_up_bias")


# --------------------------------------------------------------------------
# eligibility
# --------------------------------------------------------------------------

def ineligible_reason(cfg: ModelConfig, params: dict,
                      mesh) -> str | None:
    """Why the manual tick CANNOT serve this (cfg, params, mesh) — None
    when it can.  The engine falls back to the GSPMD tick on any reason,
    so this is a routing decision, never an error."""
    axes = dict(mesh.shape)
    tp = axes.get("tp", 1)
    if tp <= 1:
        return "no tp axis"
    others = {a: n for a, n in axes.items() if a != "tp" and n > 1}
    if others:
        return f"composed mesh (non-tp axes {others})"
    if cfg.recurrent_layers:
        return (f"model_type {cfg.model_type!r} carries recurrent state "
                f"({cfg.recurrent_layers} layers): neither the state nor the "
                "mixer's heads are manual-sharded")
    if cfg.num_heads % tp or cfg.num_kv_heads % tp:
        return (f"heads do not divide tp ({cfg.num_heads}q/"
                f"{cfg.num_kv_heads}kv over tp={tp})")
    if cfg.hc_mult > 1:
        return (f"model_type {cfg.model_type!r}: {cfg.hc_mult} "
                "hyper-connected residual streams are not manual-sharded "
                "(their coefficients reduce over the full hidden width)")
    if cfg.is_mla:
        return "MLA attention (low-rank q/kv) not manual-sharded yet"
    if cfg.alibi:
        return "alibi slopes are global-head-indexed"
    if cfg.rope_2d:
        return "2D-rope models are generate()-only anyway"
    layers = params.get("layers", {})
    if "layers_dense" in params or "moe_gate_up" in layers:
        return "MoE stacks not manual-sharded yet"
    if "qkv" not in layers:
        return "split q/k/v projections (GGUF import) not relaid-out yet"
    if not cfg.mlp_gated or "gate_up" not in layers:
        return "ungated / split MLP needs a sliced row input"
    if cfg.qk_norm and "q_norm" in layers:
        qn = layers["q_norm"]
        width = (qn.shape[-1] if not isinstance(qn, QTensor)
                 else qn.out_features)
        if width == cfg.q_dim:
            return "flat qk-norm reduces over the full q_dim"
    for key, kind in (("qkv", "col"), ("gate_up", "col"), ("o", "row"),
                      ("down", "row")):
        qt = layers.get(key)
        if not isinstance(qt, QTensor):
            return f"{key} is not a QTensor"
        from ipex_llm_tpu.parallel.shard import _qtensor_spec

        _, mode = _qtensor_spec(qt, kind, tp, stacked=True)
        if mode != kind:
            return (f"{key} does not {kind}-shard at tp={tp} "
                    f"(shape/blocks do not divide)")
    return None


def local_cfg(cfg: ModelConfig, tp: int) -> ModelConfig:
    """The shard-local model config: heads divided by tp, everything else
    untouched — the per-shard decoder body is the stock single-chip one."""
    if tp <= 1:
        return cfg
    return _dc_replace(cfg, num_heads=cfg.num_heads // tp,
                       num_kv_heads=cfg.num_kv_heads // tp)


# --------------------------------------------------------------------------
# packed-projection re-layout
# --------------------------------------------------------------------------

def _block_perm(sections: tuple[int, ...], tp: int) -> np.ndarray:
    """Out-column permutation ``[a | b | ...]`` -> ``[a_0 b_0 | a_1 b_1 |
    ...]``: shard s's contiguous column block holds its 1/tp slice of
    EVERY section."""
    offs = np.concatenate([[0], np.cumsum(sections)])[:-1]
    idx: list[int] = []
    for s in range(tp):
        for off, w in zip(offs, sections):
            blk = w // tp
            idx.extend(range(off + s * blk, off + (s + 1) * blk))
    return np.asarray(idx, np.int64)


def _permute_out_cols(leaf, idx: np.ndarray):
    if leaf is None:
        return None
    if isinstance(leaf, QTensor):
        return _dc_replace(
            leaf,
            data=jnp.asarray(leaf.data)[..., idx],
            scale_bits=(None if leaf.scale_bits is None
                        else jnp.asarray(leaf.scale_bits)[..., idx]),
            zeros=(None if leaf.zeros is None
                   else jnp.asarray(leaf.zeros)[..., idx]),
        )
    return jnp.asarray(leaf)[..., idx]


def relayout_packed(params: dict, cfg: ModelConfig, tp: int) -> dict:
    """Permute the packed col-parallel projections into the per-shard
    blockwise layout (see module docstring).  Pure column permutation —
    per-column numerics untouched; at tp=1 it is the identity."""
    if tp <= 1:
        return params
    layers = dict(params["layers"])
    sections = {
        "qkv": (cfg.q_dim, cfg.kv_dim, cfg.kv_dim),
    }
    gu = layers.get("gate_up")
    if isinstance(gu, QTensor):
        half = gu.out_features // 2
        sections["gate_up"] = (half, half)
    for key, secs in sections.items():
        if layers.get(key) is None:
            continue
        idx = _block_perm(secs, tp)
        layers[key] = _permute_out_cols(layers[key], idx)
        bias = layers.get(key + "_bias")
        if bias is not None:
            layers[key + "_bias"] = _permute_out_cols(bias, idx)
    out = dict(params)
    out["layers"] = layers
    return out


# --------------------------------------------------------------------------
# placement + specs
# --------------------------------------------------------------------------

def shard_params_manual(params: dict, cfg: ModelConfig, mesh) -> dict:
    """Manual-tick placement: relayout the packed projections, then the
    AutoTP NamedShardings — EXCEPT the embed table, which stays replicated
    (the manual region gathers token rows locally; see module doc)."""
    from ipex_llm_tpu.parallel.shard import param_shardings

    tp = mesh.shape["tp"]
    params = relayout_packed(params, cfg, tp)
    sh = param_shardings(params, mesh)
    rep = NamedSharding(mesh, P())
    emb = params.get("embed")
    if isinstance(emb, QTensor):
        sh["embed"] = _dc_replace(
            sh["embed"], data=rep,
            scale_bits=None if emb.scale_bits is None else rep,
            zeros=None if emb.zeros is None else rep, tp_mode=None)
    elif emb is not None:
        sh["embed"] = rep
    # a col-sharded lm head's bias splits with it: inside the manual
    # region linear() adds the bias BEFORE the logits all-gather, so a
    # replicated [V] bias would broadcast-clash with the [R, V/tp] shard
    if (params.get("lm_head_bias") is not None
            and isinstance(sh.get("lm_head"), QTensor)
            and sh["lm_head"].tp_mode == "col"):
        sh["lm_head_bias"] = NamedSharding(mesh, P("tp"))

    def place(p, s):
        if s is None or isinstance(p, (float, int)):
            return p
        if isinstance(p, QTensor) and isinstance(s, QTensor):
            if p.tp_mode != s.tp_mode:
                p = _dc_replace(p, tp_mode=s.tp_mode)
        return jax.device_put(p, s)

    out = {}
    for key, v in params.items():
        if key == "layers":
            out[key] = {k: place(sub, sh[key][k]) for k, sub in v.items()}
        else:
            out[key] = place(v, sh[key])
    return out


def _qt_spec(qt: QTensor) -> QTensor:
    """The per-plane PartitionSpecs of a placed QTensor, as a QTensor-
    shaped pytree (aligns leaf-for-leaf with the real one)."""
    nd = jnp.ndim(qt.data)
    if qt.tp_mode == "col":
        sp = P(*((None,) * (nd - 1) + ("tp",)))
    elif qt.tp_mode == "row":
        sp = P(*((None,) * (nd - 2) + ("tp", None)))
    else:
        sp = P()
    return _dc_replace(qt, data=sp,
                       scale_bits=None if qt.scale_bits is None else sp,
                       zeros=None if qt.zeros is None else sp)


def _local_qtensors(params: dict, tp: int) -> dict:
    """Inside the manual region a placed QTensor's planes are this shard's
    slice; its static logical shape must say so too, because the Pallas
    kernel sizes its output from it (the XLA path reads the planes)."""
    def local(leaf):
        if isinstance(leaf, QTensor) and leaf.tp_mode in ("col", "row"):
            n_in, n_out = leaf.shape
            return _dc_replace(leaf, shape=(
                (n_in, n_out // tp) if leaf.tp_mode == "col"
                else (n_in // tp, n_out)))
        return leaf

    return jax.tree_util.tree_map(
        local, params, is_leaf=lambda x: isinstance(x, QTensor))


def param_specs(params: dict, tp: int):
    """in_specs pytree for the manual region, mirroring
    :func:`shard_params_manual`'s placement (derived from the stamped
    ``tp_mode`` aux + the col-bias key convention, so it is computable at
    trace time from the abstract tree)."""
    def entry(key: str, v, in_layers: bool):
        if isinstance(v, QTensor):
            # the embed table was placed replicated with tp_mode=None
            # stamped, so the tp_mode-driven spec is right for it too
            return _qt_spec(v)
        if isinstance(v, (float, int)) or v is None:
            return P()
        if (in_layers and key in _COL_BIAS
                and v.shape[-1] % tp == 0):
            return P(*((None,) * (jnp.ndim(v) - 1) + ("tp",)))
        return P()

    out = {}
    for key, v in params.items():
        if key == "layers":
            out[key] = {k: entry(k, sub, True) for k, sub in v.items()}
        else:
            out[key] = entry(key, v, False)
    lm = params.get("lm_head")
    if (params.get("lm_head_bias") is not None
            and isinstance(lm, QTensor) and lm.tp_mode == "col"):
        # mirrors shard_params_manual's bias split (see there)
        out["lm_head_bias"] = P("tp")
    return out


# --------------------------------------------------------------------------
# the manual tick region
# --------------------------------------------------------------------------

def tp_tick(body, cfg: ModelConfig, mesh, collective_qtype: str,
            params: dict, cache, state: tuple, *, prefill, horizon: int,
            with_decode: bool, hist, spec_ks, spec_k: int, spec_ngram: int):
    """Run one fused engine tick (``body`` = engine._tick_body) inside a
    single fully-manual shard_map region over the ``tp`` axis.

    ``state`` is the replicated device row state, in ``body``'s positional
    order after the cache.  Returns exactly what ``body`` returns, with
    the cache re-assembled from its per-shard pool children.
    """
    from ipex_llm_tpu.kv import PagedKVCache
    from ipex_llm_tpu.ops import dispatch

    tp = mesh.shape["tp"]
    lcfg = local_cfg(cfg, tp)
    head_axis = "tp" if cfg.num_kv_heads % tp == 0 else None
    pool = P(None, None, head_axis, None, None)
    rep = P()
    storage = cache.storage

    p_specs = param_specs(params, tp)
    state_specs = tuple(rep for _ in state)
    prefill_specs = None if prefill is None else tuple(rep for _ in prefill)
    hist_spec = None if hist is None else rep
    ks_spec = None if spec_ks is None else rep

    def inner(p, ck, cv, ctab, clen, st, pf, hs, sk):
        p = _local_qtensors(p, tp)
        cache_l = PagedKVCache(ck, cv, ctab, clen, storage=storage)
        with dispatch.manual_tp("tp", collective_qtype):
            out = body(lcfg, p, cache_l, *st, prefill=pf, horizon=horizon,
                       with_decode=with_decode, hist=hs, spec_ks=sk,
                       spec_k=spec_k, spec_ngram=spec_ngram)
        out = list(out)
        c = out[5]
        out[5] = (c.k, c.v, c.tables, c.length)
        return tuple(out)

    n_tail = 4 if spec_k > 0 else 0
    out_specs = (
        (None if prefill is None else rep,      # first_t
         None if prefill is None else rep,      # first_lp
         rep, rep, rep,                         # tok_block, lp_block, n_exec
         (pool, pool, rep, rep),                # cache children
         rep, rep, rep, rep, rep, rep)          # toks..remain, key
        + (rep,) * n_tail)

    fn = jax.shard_map(
        inner, mesh=mesh,
        in_specs=(p_specs, pool, pool, rep, rep, state_specs,
                  prefill_specs, hist_spec, ks_spec),
        out_specs=out_specs,
        axis_names=set(mesh.axis_names),   # fully manual: GSPMD sees nothing
        check_vma=False,
    )
    out = list(fn(params, cache.k, cache.v, cache.tables, cache.length,
                  state, prefill, hist, spec_ks))
    ck, cv, ctab, clen = out[5]
    out[5] = PagedKVCache(ck, cv, ctab, clen, storage=storage)
    return tuple(out)
