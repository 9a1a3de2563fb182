"""Shared transformer decoder core.

This single functional decoder replaces the reference's per-model patched
forwards (transformers/models/llama.py:56-205 and 48 sibling files): merged
QKV / gate-up projections (the `_optimize_pre` merges, convert.py:890) are
done once at weight-load time, and the per-layer loop is a ``lax.scan`` over
stacked layer params so XLA compiles ONE layer body regardless of depth.

Static-shape discipline (SURVEY.md §7 hard part (b)):
- the KV cache is a fixed head-major ``[L, B, H, S_max, D]`` ring (kv.py),
- prompts are left-padded into buckets; RoPE uses logical positions while
  cache slots use physical indices, so decode writes are a single
  ``dynamic_update_slice`` at a uniform offset for the whole batch,
- per-layer sliding-window choice (gemma2-style alternation) enters the scan
  as a traced flag folded into the attention mask, not Python control flow.
"""

from __future__ import annotations

from dataclasses import replace
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from ipex_llm_tpu.kv import MOE_COUNTERS, STATE_COUNTERS, KVCache
from ipex_llm_tpu.models.config import ModelConfig
from ipex_llm_tpu.ops import hyper as hyper_ops
from ipex_llm_tpu.ops import linear as linear_ops
from ipex_llm_tpu.ops import mlp as mlp_ops
from ipex_llm_tpu.ops import rope as rope_ops
from ipex_llm_tpu.ops.attention import (cached_sdpa, index_scores,
                                        latent_attention, paged_kv_write,
                                        paged_pool_write, select_topk,
                                        sparse_latent_attention)
from ipex_llm_tpu.ops.norms import _rows, layer_norm, rms_norm
from ipex_llm_tpu.quantize.core import (QLayer, QTensor, dequantize,
                                        layer_views)

COMPUTE_DTYPE = jnp.bfloat16

# Non-trainable buffer leaves of the param pytree (the reference registers
# inv_freq as a torch buffer).  Single source of truth: decoder_forward
# stop_gradients them (no grad flow) and training/step.py zeroes their
# optimizer updates (no adamw weight-decay drift) from this same list.
FROZEN_BUFFER_KEYS = ("inv_freq", "inv_freq_local", "rope_mscale")


def _norm(x, w, cfg: ModelConfig, bias=None):
    if cfg.norm_kind == "layer":
        return layer_norm(x, w, bias, cfg.norm_eps)
    return rms_norm(x, w, cfg.norm_eps, cfg.norm_offset)


def alibi_slopes(n_heads: int) -> jnp.ndarray:
    """ALiBi per-head slopes (the reference patches bloom/baichuan-13b to
    keep HF's ``build_alibi_tensor`` semantics; same closed form here)."""
    import math

    def pow2_slopes(n):
        start = 2.0 ** (-(2.0 ** -(math.log2(n) - 3)))
        return [start * (start ** i) for i in range(n)]

    n_p2 = 2 ** math.floor(math.log2(n_heads))
    slopes = pow2_slopes(n_p2)
    if n_p2 != n_heads:
        extra = pow2_slopes(2 * n_p2)
        slopes += extra[0::2][: n_heads - n_p2]
    return jnp.asarray(slopes, jnp.float32)


def _in_norm(x, lp, key, cfg):
    return _norm(x, lp[key], cfg, lp.get(key + "_bias"))


def _attention_block(cfg: ModelConfig, lp: dict, x, kl, vl, cos, sin, slot0,
                     q_slots, kv_len, kv_start, sliding, cache: KVCache,
                     collect_obs: int = 0, bias=None, pre_normed=False,
                     chunk_lens=None, layer=None, dsa=None):
    """One layer's attention.  ``kl``/``vl`` are that layer's cache slice;
    with ``layer`` (a paged pool under :func:`run_layers`) they are the
    WHOLE stacks [L, P, Hkv, page, D], written and read at that layer in
    place, and come back whole.  ``dsa`` (a model with an indexer over a
    paged pool; :func:`_sparse_mla`): the layer's indexer call, and the
    return gains ``(selection, counters)``."""
    b, t, _ = x.shape

    def write(k, v):
        if layer is None:
            return cache.update_layer(kl, vl, k, v, slot0)
        return paged_kv_write(cache, kl, vl, layer, k, v, slot0)

    # olmo2-style reordered norm: attention sees the raw residual stream
    # and attn_norm applies to the block OUTPUT instead; pre_normed: the
    # caller already normed x (glm_alpha residual needs the normed input)
    with jax.named_scope("layer/qkv"):
        h = (x if cfg.norm_after or pre_normed
             else _in_norm(x, lp, "attn_norm", cfg))
    if cfg.is_mla:
        return _mla_block(cfg, lp, x, h, kl, vl, cos, sin, q_slots, kv_len,
                          kv_start, sliding, cache, collect_obs, chunk_lens,
                          layer, write, dsa)
    with jax.named_scope("layer/qkv"):
        q, k, v, obs_q = _qkv_rope(cfg, lp, x, h, cos, sin, collect_obs)
    kl, vl = write(k, v)

    # the cache layer stays in storage dtype: decode steps read it directly
    # through the specialized kernel (fp8 dequant in-kernel); other shapes
    # cast once inside cached_sdpa
    with jax.named_scope("layer/attn"):
        attn = cached_sdpa(
            q,
            kl,
            vl,
            cache,
            compute_dtype=COMPUTE_DTYPE,
            causal=True,
            q_positions=q_slots,
            kv_len=kv_len,
            kv_start=kv_start,
            window=cfg.sliding_window,
            window_on=sliding,
            softcap=cfg.attn_softcap,
            scale=cfg.attn_scale,
            bias=bias,
            chunk_lens=chunk_lens,
            layer=layer,
        )
    return _o_proj(cfg, lp, attn), kl, vl, obs_q


@jax.named_scope("layer/o_proj")
def _o_proj(cfg: ModelConfig, lp: dict, attn):
    """The ``layer/o_proj`` stage of plain attention: the heads' outputs
    ``[B, T, Hq, D]`` through the output projection and its norms."""
    attn = attn.reshape(*attn.shape[:2], cfg.num_heads * cfg.head_dim)
    out = linear_ops.times(linear_ops.linear(attn, lp["o"], lp.get("o_bias")),
                 cfg.attention_out_multiplier)
    if cfg.norm_after:
        out = _norm(out, lp["attn_norm"], cfg, lp.get("attn_norm_bias"))
    if cfg.post_attn_norm:
        out = _norm(out, lp["post_attn_norm"], cfg)
    return out


def _dense_weight(w, dtype):
    """A linear's weight ``[in, out]`` at full width: what an einsum that
    contracts another axis than ``in`` needs (MLA's absorbed products)."""
    if isinstance(w, QLayer):
        w = w.take()
    if isinstance(w, QTensor):
        return dequantize(w, dtype=dtype)
    return w.astype(dtype)


def _mla_block(cfg: ModelConfig, lp: dict, x, h, kl, vl, cos, sin, q_slots,
               kv_len, kv_start, sliding, cache, collect_obs, chunk_lens,
               layer, write, dsa=None):
    """One layer's latent attention (DeepSeek MLA, reference
    deepseek.py:274-343): a low-rank q, a compressed KV latent ``c`` and ONE
    rotated key slice ``k_r`` shared by every head; ``kv_b`` holds every
    head's ``W_UK`` (c -> k_nope) and ``W_UV`` (c -> v) side by side.

    Over a PAGED pool the cache holds the latent and nothing per head (kv.py
    ``pool_geometry``: ``c`` in the ``k`` pool, ``k_r`` in the ``v`` pool),
    and attention runs on it (ops/attention.py ``latent_attention``) in the
    ABSORBED form for every call's shape: ``W_UK`` folded into the query and
    ``W_UV`` applied to the latent-space output, both read from ``kv_b``
    without a second copy kept.  Over a dense cache (``generate()``) K and V
    are EXPANDED per head before the write, as before: K at the qk dim, V at
    v_head_dim.

    With ``dsa`` (a model with an indexer, over its sparse latent pool) the
    walk is over a SELECTION (:func:`_sparse_mla`), and the return gains the
    selection and the layer's counters."""
    b, t, _ = x.shape
    nope, rd_pe, rank = (cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
                         cfg.kv_lora_rank)
    latent = hasattr(cache, "tables")
    with jax.named_scope("layer/qkv"):
        with jax.named_scope("mla/q"):
            c_q = None
            if "q_a" in lp:
                qa = linear_ops.linear(h, lp["q_a"], lp.get("q_a_bias"))
                c_q = rms_norm(qa, lp["q_a_norm"], cfg.norm_eps)
                q = linear_ops.linear(c_q, lp["q_b"])
            else:  # V2-Lite: full-rank q_proj
                q = linear_ops.linear(h, lp["q"], lp.get("q_bias"))
            q = q.reshape(b, t, cfg.num_heads, cfg.head_dim)
            q_nope = q[..., :nope]
            # (``qk_rope_head_dim`` 0: a position-free layer, whose rotated
            # slices are empty and rotate by nothing)
            q_pe = (rope_ops.apply_rope(q[..., nope:], cos, sin, "two")
                    if rd_pe else q[..., nope:])
        with jax.named_scope("mla/latent"):
            ckv = linear_ops.linear(h, lp["kv_a"], lp.get("kv_a_bias"))
            c = rms_norm(ckv[..., :rank], lp["kv_a_norm"], cfg.norm_eps)
            k_pe = ckv[..., rank:][:, :, None, :]               # [B,T,1,rd]
            if rd_pe:
                k_pe = rope_ops.apply_rope(k_pe, cos, sin, "two")
            if dsa is not None:
                # the pool's row is the whole key a token: [c ; k_r ; 0]
                row = jnp.concatenate([c[:, :, None, :], k_pe], axis=-1)
                row = jnp.pad(row, ((0, 0),) * 3
                              + ((0, kl.shape[-1] - row.shape[-1]),))
                kl = paged_pool_write(cache, kl, layer, row, q_slots[:, 0])
            elif latent:
                kl, vl = write(c[:, :, None, :], k_pe)

    if dsa is not None:
        if collect_obs:
            raise NotImplementedError(
                "SnapKV observation windows over a sparse latent pool")
        attn, vl, sel, d_stats, *rec = _sparse_mla(
            cfg, lp, dsa, h, c_q, q_nope, q_pe, kl, vl, cos, sin, q_slots,
            kv_len, chunk_lens, cache, layer)
        attn = attn.astype(x.dtype)
        obs_q = jnp.zeros((0,), x.dtype)
    elif latent:
        if collect_obs:
            raise NotImplementedError(
                "SnapKV observation windows over a latent (MLA) paged pool")
        with jax.named_scope("layer/attn"):
            # the absorbed products see the call's tokens as ONE axis: a
            # prefill block is [p_b, width] with p_b x width fixed by the
            # budget, so every block shape is the same product
            with jax.named_scope("mla/absorb"):
                q_lat, w_kvb = _absorb_q(cfg, lp, q_nope)
            o_lat = latent_attention(
                q_lat, q_pe, kl, vl, cache, kv_len, chunk_lens,
                scale=(cfg.attn_scale if cfg.attn_scale is not None
                       else cfg.head_dim ** -0.5), layer=layer)
            with jax.named_scope("mla/absorb"):
                attn = jnp.einsum(
                    "nhc,chd->nhd", o_lat.reshape(b * t, -1, rank),
                    w_kvb[..., nope:], preferred_element_type=jnp.float32
                ).astype(x.dtype)
        obs_q = jnp.zeros((0,), x.dtype)
    else:
        with jax.named_scope("mla/kv_up"):
            kv = linear_ops.linear(c, lp["kv_b"]).reshape(
                b, t, cfg.num_heads, nope + cfg.v_dim)
            k = jnp.concatenate(
                [kv[..., :nope],
                 jnp.broadcast_to(k_pe, (b, t, cfg.num_heads, rd_pe))],
                axis=-1)
            v = kv[..., nope:]
        q = jnp.concatenate([q_nope, q_pe], axis=-1)
        obs_q = (q[:, -collect_obs:] if collect_obs
                 else jnp.zeros((0,), x.dtype))
        kl, vl = write(k, v)
        attn = cached_sdpa(
            q, kl, vl, cache,
            compute_dtype=COMPUTE_DTYPE, causal=True, q_positions=q_slots,
            kv_len=kv_len, kv_start=kv_start, window=None, window_on=sliding,
            softcap=cfg.attn_softcap, scale=cfg.attn_scale,
            chunk_lens=chunk_lens, layer=layer,
        )
    with jax.named_scope("layer/o_proj"):
        attn = attn.reshape(b, t, cfg.num_heads * cfg.v_dim)
        out = linear_ops.linear(attn, lp["o"], lp.get("o_bias"))
        if cfg.post_attn_norm:
            out = _norm(out, lp["post_attn_norm"], cfg)
    if dsa is not None:
        return out, kl, vl, obs_q, sel, d_stats, *rec
    return out, kl, vl, obs_q


def _absorb_q(cfg: ModelConfig, lp: dict, q_nope):
    """``W_UK`` folded into the position-free query: (q_lat [B, T, H, rank],
    ``kv_b`` dense as [rank, H, nope + v])."""
    b, t = q_nope.shape[:2]
    nope, rank = cfg.qk_nope_head_dim, cfg.kv_lora_rank
    w_kvb = _dense_weight(lp["kv_b"], COMPUTE_DTYPE).reshape(
        rank, cfg.num_heads, nope + cfg.v_dim)
    q_lat = jnp.einsum(
        "nhd,chd->nhc", q_nope.astype(COMPUTE_DTYPE).reshape(b * t, -1, nope),
        w_kvb[..., :nope], preferred_element_type=jnp.float32
    ).astype(COMPUTE_DTYPE).reshape(b, t, -1, rank)
    return q_lat, w_kvb


# the indexer's LayerNorm (DeepSeek-V3.2's published indexer, whose keys a
# glm_moe_dsa config names): its own epsilon, not the model's RMSNorm one
_INDEX_NORM_EPS = 1e-6


def _sparse_mla(cfg: ModelConfig, lp: dict, dsa, h, c_q, q_nope, q_pe, kl, vl,
                cos, sin, q_slots, kv_len, chunk_lens, cache, layer):
    """The attention of a layer WITH AN INDEXER over its sparse latent pool
    (kv.py ``pool_geometry``: ``kl`` the latent rows, already holding this
    call's tokens; ``vl`` the index keys of the full layers).  ``dsa`` is
    ``(is_full, entry of the indexer stack, the full layer's index in the
    index-key pool, the selection carried from below)``, and for an indexer
    over POOLED keys (``cfg.index_kpool`` > 1) a fifth entry, the recurrent
    state's call (:func:`_pool_index_keys`).

    A FULL layer runs its indexer: 32 small heads off the low-rank query
    ``c_q`` it shares with the main heads, a LayerNormed index key a token
    (written to the pool), head weights off the hidden state; scores,
    selection (ops/attention.py).  A SHARED layer has no indexer weights and
    no index keys and takes the selection of the nearest full layer below.
    Either way every head attends the selected slots only, absorbed.  The
    published code rotates the indexer's q and k by a Hadamard matrix before
    an fp8 product: an orthogonal map that leaves every dot product as it
    is, left out here (bf16 products).

    POOLED keys: the pool holds one key a complete block of ``index_kpool``
    positions, the mean of its keys; a query scores the complete blocks
    that end before it, keeps the ``index_topk / index_kpool`` best and
    attends their positions AND every position after its last complete
    block, its own among them, unscored.

    Returns (attn [B*T, H, v], the index-key pool, the selection, int32 [5]
    counters: keys (or blocks) scored, positions visible, positions
    selected, full calls, shared calls[, the recurrent state's leaves])."""
    b, t = h.shape[:2]
    is_full, indexer, fi, sel, *rec = dsa
    rd = cfg.qk_rope_head_dim
    kp = cfg.index_kpool
    if chunk_lens is None:
        chunk_lens = jnp.full((b,), t, jnp.int32)
    asks = jnp.arange(t)[None, :] < chunk_lens[:, None]   # attending queries
    visible = jnp.sum(jnp.where(asks, q_slots + 1, 0)).astype(jnp.int32)

    def rope_head(z):
        if not rd:
            return z
        return jnp.concatenate(
            [rope_ops.apply_rope(z[..., :rd], cos, sin, "two"), z[..., rd:]],
            axis=-1)

    def index_call():
        """(query heads, index key a token, head weights) of this call."""
        ix = indexer()
        q_i = rope_head(linear_ops.linear(c_q, ix["wq_b"]).reshape(
            b, t, cfg.index_n_heads, cfg.index_head_dim))
        k_i = rope_head(layer_norm(
            linear_ops.linear(h, ix["wk"]), ix["k_norm"],
            ix["k_norm_bias"], _INDEX_NORM_EPS)[:, :, None, :])
        # (the tokens as one axis, as the router's product is)
        w_i = jnp.matmul(
            _rows(h).astype(jnp.float32), ix["w_proj"],
            precision=jax.lax.Precision.HIGHEST
        ).reshape(b, t, -1) * (
            cfg.index_n_heads ** -0.5 * cfg.index_head_dim ** -0.5)
        return q_i, k_i, w_i

    def full(vl, sel):
        with jax.named_scope("dsa/index"):
            q_i, k_i, w_i = index_call()
            vl = paged_pool_write(cache, vl, fi, k_i, q_slots[:, 0])
            # (``dsa/score`` nests here: the indexer's whole cost is one
            # scope, its scoring a part of it)
            scores = index_scores(q_i, w_i, vl, cache, q_slots, kv_len,
                                  layer=fi)
        chosen = select_topk(scores, cfg.index_topk)
        if t == 1:
            n_sel = jnp.sum(chosen[1], axis=-1, dtype=jnp.int32)[:, None]
            return vl, (*chosen, n_sel), visible
        return vl, (chosen, jnp.sum(chosen, axis=-1, dtype=jnp.int32)), visible

    def pooled(vl, src, rows, fresh, state):
        with jax.named_scope("dsa/index"):
            q_i, k_i, w_i = index_call()
            with jax.named_scope("dsa/pool"):
                vl, state = _pool_index_keys(
                    cfg, cache, vl, fi, k_i[:, :, 0], q_slots, chunk_lens,
                    src, rows, fresh, state)
            scores = index_scores(q_i, w_i, vl, cache, q_slots, kv_len,
                                  layer=fi, kpool=kp)
        chosen = select_topk(scores, cfg.index_topk // kp)
        # the chosen blocks' positions, and the tail's: every position from
        # the query's last incomplete block's first on, up to its own
        first = (q_slots // kp) * kp                               # [B, T]
        if t == 1:
            blk, ok = chosen
            idx = (blk[..., None] * kp + jnp.arange(kp)).reshape(b, -1)
            tail = first + jnp.arange(kp)[None, :]
            idx = jnp.concatenate([idx, tail], axis=-1).astype(jnp.int32)
            ok = jnp.concatenate(
                [jnp.repeat(ok, kp, axis=-1), tail <= q_slots], axis=-1)
            picked = (idx, ok, jnp.sum(ok, axis=-1, dtype=jnp.int32)[:, None])
        else:
            s_len = kl.shape[3] * cache.tables.shape[1]
            pos = jnp.arange(s_len)[None, None, :]
            mask = (jnp.repeat(chosen, kp, axis=-1)
                    | ((pos >= first[..., None])
                       & (pos <= q_slots[..., None])))
            picked = (mask, jnp.sum(mask, axis=-1, dtype=jnp.int32))
        scored = jnp.sum(jnp.where(asks, q_slots // kp, 0)).astype(jnp.int32)
        return vl, picked, scored, state

    def shared(vl, sel):
        return vl, sel, jnp.zeros((), jnp.int32)

    with jax.named_scope("layer/attn"):
        # (a tree of shared layers alone holds no indexer stack)
        if kp > 1:
            vl, sel, scored, state = pooled(vl, *rec[0])
            rec = [state]
        else:
            vl, sel, scored = (shared(vl, sel) if indexer is None else
                               jax.lax.cond(is_full, full, shared, vl, sel))
        with jax.named_scope("mla/absorb"):
            q_lat, w_kvb = _absorb_q(cfg, lp, q_nope)
            q_row = jnp.concatenate(
                [q_lat, q_pe.astype(COMPUTE_DTYPE)], axis=-1)
            q_row = jnp.pad(q_row, ((0, 0),) * 3
                            + ((0, kl.shape[-1] - q_row.shape[-1]),))
        o_lat = sparse_latent_attention(
            q_row, kl, cache, sel[:-1] if t == 1 else sel[0], chunk_lens,
            kv_len, rank=cfg.kv_lora_rank,
            scale=(cfg.attn_scale if cfg.attn_scale is not None
                   else cfg.head_dim ** -0.5), layer=layer)
        with jax.named_scope("mla/absorb"):
            attn = jnp.einsum(
                "nhc,chd->nhd", o_lat.reshape(b * t, -1, cfg.kv_lora_rank),
                w_kvb[..., cfg.qk_nope_head_dim:],
                preferred_element_type=jnp.float32)
    full_i = is_full.astype(jnp.int32)
    stats = jnp.stack([
        scored, visible, jnp.sum(jnp.where(asks, sel[-1], 0)).astype(
            jnp.int32), full_i, 1 - full_i])
    return (attn, vl, sel, stats, *rec)


def _pool_index_keys(cfg: ModelConfig, cache, vl, fi, k_i, q_slots,
                     chunk_lens, src, rows, fresh, state):
    """The POOLED index cache of a full layer advanced by this call's index
    keys ``k_i`` [B, T, D]: every block of ``cfg.index_kpool`` positions that
    this call completes gets its key, the mean of its keys (float32, term by
    term, stored in the pool's type), written to the index-key pool ``vl``
    at ``(fi, page, block in page)``; the keys of a row's incomplete block
    wait in ``state["ix_tail"]`` [full layers, R, kpool-1, D] (read from
    ``src``, the tick's source buffer).  How many wait follows from the
    row's length, so a tail is never cleared.  A decode row (T == 1) adds
    one key to its tail or closes its block with it; a chunk starts on a
    block's edge (the prompt grid, ``cfg.chunk_grid``), pools its whole
    blocks and leaves its last keys in the tail.  ``rows``/``fresh``: the
    prefill block's map to state rows (models/decoder.py ``run_layers``).
    Returns (the pool, the state)."""
    kp = cfg.index_kpool
    b, t, d = k_i.shape
    ps = vl.shape[3] * kp                    # tokens a page
    n_pages = vl.shape[1]
    f32 = jnp.float32
    base = q_slots[:, 0]
    tails = (src or state)["ix_tail"]
    if rows is None:
        tail = jax.lax.dynamic_index_in_dim(tails, fi, 0, False)
    else:
        tail = tails[fi, jnp.clip(rows, 0, tails.shape[1] - 1)]
        tail = jnp.where(fresh[:, None, None], 0, tail)

    def mean(parts):
        total = parts[0].astype(f32)
        for part in parts[1:]:
            total = total + part.astype(f32)
        return (total / kp).astype(vl.dtype)

    if t == 1 and rows is None:
        alive = chunk_lens > 0
        m = base % kp
        key = k_i[:, 0]
        closes = alive & (m == kp - 1)
        pooled = mean([tail[:, j] for j in range(kp - 1)] + [key])[:, None]
        blocks = base[:, None]
        done = closes[:, None]
        waits = (alive & (m < kp - 1))[:, None, None] & (
            jnp.arange(kp - 1)[None, :, None] == m[:, None, None])
        new_tail = jnp.where(waits, key[:, None, :].astype(tail.dtype), tail)
    else:
        pad = -t % kp
        kb = jnp.pad(k_i, ((0, 0), (0, pad), (0, 0))).reshape(b, -1, kp, d)
        pooled = mean([kb[:, :, j] for j in range(kp)])       # [B, T/kp, D]
        first = jnp.arange(kb.shape[1])[None, :] * kp
        blocks = base[:, None] + first
        done = first + kp <= chunk_lens[:, None]
        whole = (chunk_lens // kp) * kp
        at = jnp.clip(whole[:, None] + jnp.arange(kp - 1)[None, :], 0, t - 1)
        new_tail = jnp.take_along_axis(
            k_i, at[..., None], axis=1).astype(tail.dtype)
    pages = jnp.where(done, cache.route_pages(blocks // ps), n_pages)
    vl = vl.at[fi, pages, 0, (blocks % ps) // kp].set(pooled, mode="drop")
    if rows is None:
        tails = jax.lax.dynamic_update_index_in_dim(
            state["ix_tail"], new_tail, fi, 0)
    else:
        tails = state["ix_tail"].at[fi, rows].set(new_tail, mode="drop")
    return vl, dict(state, ix_tail=tails)


def no_selection(cfg: ModelConfig, b: int, t: int, s: int):
    """The empty selection a model with an indexer starts its layers from
    (its first layer's indexer is full and replaces it): a decode call's
    ``(slots [B, k], which are real, how many a query chose [B, T])``, a
    chunk's ``(mask [B, T, S], how many)``."""
    n_sel = jnp.zeros((b, t), jnp.int32)
    if t == 1:
        k = min(cfg.index_topk, s)
        if cfg.index_kpool > 1:
            # the chosen blocks' positions and the always-attended tail's
            kp = cfg.index_kpool
            k = min(cfg.index_topk // kp, s // kp) * kp + kp
        return (jnp.zeros((b, k), jnp.int32), jnp.zeros((b, k), bool), n_sel)
    return jnp.zeros((b, t, s), bool), n_sel


def _qkv_rope(cfg: ModelConfig, lp: dict, x, h, cos, sin, collect_obs):
    """The ``layer/qkv`` stage of :func:`_attention_block` (non-MLA):
    projections from the normed input ``h``, q/k norms, rope.  Returns
    (q [B,T,Hq,D], k, v [B,T,Hkv,D], obs_q)."""
    b, t, _ = x.shape
    q_dim, kv_dim = cfg.q_dim, cfg.kv_dim
    h = linear_ops.times(h, cfg.attention_in_multiplier)
    if "qkv" in lp:
        qkv = linear_ops.linear(h, lp["qkv"], lp.get("qkv_bias"))
        q = qkv[..., :q_dim]
        k = linear_ops.times(qkv[..., q_dim : q_dim + kv_dim], cfg.key_multiplier)
        v = qkv[..., q_dim + kv_dim :]
    else:
        # split projections (GGUF import keeps q/k/v in their native — and
        # possibly different — block formats, e.g. q4_k q/k with q6_k v)
        q = linear_ops.linear(h, lp["q"], lp.get("q_bias"))
        k = linear_ops.times(linear_ops.linear(h, lp["k"], lp.get("k_bias")),
                   cfg.key_multiplier)
        v = linear_ops.linear(h, lp["v"], lp.get("v_bias"))
    if cfg.qk_norm and lp["q_norm"].shape[-1] == q_dim:
        # olmo2-style flat q/k rmsnorm over the whole projection
        q = rms_norm(q, lp["q_norm"], cfg.norm_eps, cfg.norm_offset)
        k = rms_norm(k, lp["k_norm"], cfg.norm_eps, cfg.norm_offset)
    q = q.reshape(b, t, cfg.num_heads, cfg.head_dim)
    k = k.reshape(b, t, cfg.num_kv_heads, cfg.head_dim)
    v = v.reshape(b, t, cfg.num_kv_heads, cfg.head_dim)

    if cfg.qk_norm and lp["q_norm"].shape[-1] == cfg.head_dim:
        q = rms_norm(q, lp["q_norm"], cfg.norm_eps, cfg.norm_offset)
        k = rms_norm(k, lp["k_norm"], cfg.norm_eps, cfg.norm_offset)

    rd = cfg.rope.rotary_dim if cfg.rope is not None else cfg.head_dim
    if cfg.rope is not None and cfg.rope_2d:
        # chatglm v1: each head_dim half rotates with its own channel table
        # (cos/sin arrive concatenated from embed_prelude)
        d2 = cfg.head_dim // 2
        f = cos.shape[-1] // 2
        def rot2(x):
            return jnp.concatenate([
                rope_ops.apply_rope(x[..., :d2], cos[..., :f], sin[..., :f],
                                    "half"),
                rope_ops.apply_rope(x[..., d2:], cos[..., f:], sin[..., f:],
                                    "half"),
            ], axis=-1)
        q, k = rot2(q), rot2(k)
    elif cfg.rope is not None:
        if rd == cfg.head_dim:
            q = rope_ops.apply_rope(q, cos, sin, cfg.rope_layout)
            k = rope_ops.apply_rope(k, cos, sin, cfg.rope_layout)
        else:  # partial rotary (phi / gptneox style)
            q = jnp.concatenate(
                [rope_ops.apply_rope(q[..., :rd], cos, sin, cfg.rope_layout), q[..., rd:]],
                axis=-1,
            )
            k = jnp.concatenate(
                [rope_ops.apply_rope(k[..., :rd], cos, sin, cfg.rope_layout), k[..., rd:]],
                axis=-1,
            )

    obs_q = q[:, -collect_obs:] if collect_obs else jnp.zeros((0,), x.dtype)
    return q, k, v, obs_q


def _sum_chosen(w):
    """The sum over the k chosen experts (the last axis), kept: term by
    term in index order.  As a reduction XLA orders it by the shape of the
    block: measured on a v5e, a token's six weights normalised as part of a
    [1, 128, 6] block and of a [4, 32, 6] one differ by one float32 ulp in
    a fifth of the cases, which is enough to give a served token other bits
    when its prompt shares a prefill block (PERF.md section 6, PR 28)."""
    total = w[..., 0]
    for j in range(1, w.shape[-1]):
        total = total + w[..., j]
    return total[..., None]


def _route(cfg: ModelConfig, lp: dict, router_logits):
    """Router logits [B, T, E] -> (weights, expert ids), both [B, T, k]."""
    k = cfg.num_experts_per_tok
    if cfg.moe_softmax_before_topk:
        if cfg.moe_score_func == "sigmoid":  # deepseek-v3 noaux_tc
            scores = jax.nn.sigmoid(router_logits)
        else:
            scores = jax.nn.softmax(router_logits, axis=-1)
        sel = scores
        if "router_bias" in lp:  # v3 e_score_correction_bias: selection
            sel = sel + lp["router_bias"]  # only; weights use raw scores
        if cfg.moe_n_group > 1:
            # group-limited routing (deepseek group_limited_greedy /
            # noaux_tc): only experts in the top ``topk_group`` groups are
            # eligible; group score is the max (v2) or top-2 sum (v3) of
            # its members
            g = sel.reshape(*sel.shape[:-1], cfg.moe_n_group, -1)
            if cfg.moe_group_score == "top2sum":
                # term by term, like ``_sum_chosen``: as a reduction XLA
                # orders it by the block's shape
                top2 = jax.lax.top_k(g, 2)[0]
                gs = top2[..., 0] + top2[..., 1]
            else:
                gs = g.max(-1)
            _, gidx = jax.lax.top_k(gs, cfg.moe_topk_group)
            gmask = jax.nn.one_hot(gidx, cfg.moe_n_group, dtype=sel.dtype
                                   ).sum(-2)
            sel = jnp.where(gmask[..., None] > 0, g, 0.0).reshape(sel.shape)
        _, idx = jax.lax.top_k(sel, k)
        w = jnp.take_along_axis(scores, idx, axis=-1)
        if cfg.moe_norm_topk_prob:
            w = w / (_sum_chosen(w) + 1e-20)
    else:  # mixtral: top-k logits, softmax over the k
        lg, idx = jax.lax.top_k(router_logits, k)
        w = jnp.exp(lg - lg[..., :1])       # top_k sorts: the first is the max
        w = w / _sum_chosen(w)
    if cfg.moe_router_scale != 1.0:
        w = w * cfg.moe_router_scale

    return w, idx


@jax.named_scope("layer/mlp")
def _moe_block(cfg: ModelConfig, lp: dict, x):
    """Sparse-MoE FFN (mixtral/qwen-moe), reference deepseek.py:274-343 +
    common.py:342-375 ``moe_group_topk``/``moe_forward_vec``.

    Router in fp32, then sparse dispatch (ops/moe.py): decode-shaped
    batches gather only the top-k experts' packed weights from HBM; larger
    batches run capacity-bucketed dispatch with one vmapped expert matmul
    (ep-shardable).  IPEX_LLM_TPU_DENSE_MOE=1 selects the dense
    all-experts scan (the oracle used by the sparse-vs-dense tests).
    """
    h = _norm(x, lp["mlp_norm"], cfg)
    router_logits = jnp.matmul(
        h.astype(jnp.float32), lp["router"]
    )  # [B,T,E]
    n_e = cfg.num_experts
    w, idx = _route(cfg, lp, router_logits)

    from ipex_llm_tpu.ops import moe as moe_ops

    if moe_ops.use_sparse():
        out = moe_ops.moe_ffn(
            h, w, idx, lp["moe_gate_up"], lp["moe_down"], cfg.act, n_e,
            gated=cfg.mlp_gated, up_bias=lp.get("moe_up_bias"),
            down_bias=lp.get("moe_down_bias"),
        ).astype(x.dtype)
    else:
        # dense gate map [B,T,E]: zeros except the top-k columns
        gates = (w[..., None] * jax.nn.one_hot(idx, n_e, dtype=w.dtype)).sum(-2)

        def expert_step(acc, xs):
            e_i = xs["i"]
            inner = linear_ops.linear(h, xs["gu"])
            if "ub" in xs:
                inner = inner + xs["ub"].astype(inner.dtype)
            if cfg.mlp_gated:
                gate, up = mlp_ops.split_gate_up(inner)
                yi = mlp_ops.gated_act_mul(gate, up, cfg.act)
            else:
                yi = mlp_ops.act(inner, cfg.act)
            y = linear_ops.linear(yi, xs["dn"])
            if "db" in xs:
                y = y + xs["db"].astype(y.dtype)
            return acc + y * gates[..., e_i, None].astype(y.dtype), None

        xs = {"i": jnp.arange(n_e), "gu": lp["moe_gate_up"],
              "dn": lp["moe_down"]}
        if "moe_up_bias" in lp:
            xs["ub"] = lp["moe_up_bias"]
        if "moe_down_bias" in lp:
            xs["db"] = lp["moe_down_bias"]
        out, _ = jax.lax.scan(expert_step, jnp.zeros_like(x), xs)

    if "shared_gate_up" in lp:  # qwen2-moe shared expert
        gate, up = mlp_ops.split_gate_up(
            linear_ops.linear(h, lp["shared_gate_up"])
        )
        ys = linear_ops.linear(mlp_ops.gated_act_mul(gate, up, cfg.act),
                               lp["shared_down"])
        if "shared_router" in lp:
            g = jax.nn.sigmoid(
                jnp.matmul(h.astype(jnp.float32), lp["shared_router"])
            )
            ys = ys * g.astype(ys.dtype)
        out = out + ys
    return out


@jax.named_scope("layer/mlp")
def _mlp_block(cfg: ModelConfig, lp: dict, x, pre_normed=False):
    h = (x if cfg.norm_after or pre_normed
         else _in_norm(x, lp, "mlp_norm", cfg))
    gate_m, down_m = cfg.mlp_multipliers or (1.0, 1.0)
    if not cfg.mlp_gated:
        # fc1 -> act -> fc2 (phi/gptneox/starcoder2-style MLP)
        inner = mlp_ops.act(
            linear_ops.linear(h, lp["up"], lp.get("up_bias")), cfg.act
        )
    else:
        if "gate_up" in lp:
            gate_up = linear_ops.linear(h, lp["gate_up"], lp.get("gate_up_bias"))
            gate, up = mlp_ops.split_gate_up(gate_up)
        else:
            gate = linear_ops.linear(h, lp["gate"], lp.get("gate_bias"))
            up = linear_ops.linear(h, lp["up"], lp.get("up_bias"))
        inner = mlp_ops.gated_act_mul(linear_ops.times(gate, gate_m), up,
                                      cfg.act, cfg.mlp_act_limit)
    out = linear_ops.times(linear_ops.linear(inner, lp["down"], lp.get("down_bias")),
                 down_m)
    if cfg.norm_after:
        out = _norm(out, lp["mlp_norm"], cfg, lp.get("mlp_norm_bias"))
    if cfg.post_mlp_norm:
        out = _norm(out, lp["post_mlp_norm"], cfg)
    return out


@jax.named_scope("layer/mlp")
def _held_moe_block(cfg: ModelConfig, lp: dict, x, valid, shared: bool = True,
                    layer=None):
    """An expert layer that is told WHICH experts it holds (a hybrid
    model's ``E`` block; ``cfg.moe_first_expert``, and as many as its stacks
    hold): it routes over all ``cfg.num_experts`` in float32, computes its
    own experts' part for the pairs that land on them (ops/moe.py
    ``moe_held``: nothing dropped, nothing dequantized at full width) and
    leaves the absent experts' part out: no stand-in for the chip that
    holds them.  The shared expert, ungated like the routed ones, runs for
    every token.  ``valid`` [B, T] keeps padding from routing anywhere.
    With ``layer`` the two expert stacks are the whole model's ``[E blocks,
    held, ...]`` and that block's experts are read in place.

    Returns (out, [pairs routed, pairs local, experts hit, 1, rows the
    expert kernel multiplied]): ``kv.MOE_COUNTERS``."""
    from ipex_llm_tpu.ops import moe as moe_ops

    b, t, hidden = x.shape
    h = _norm(x, lp["mlp_norm"], cfg)
    with jax.named_scope("moe/router"):
        # the tokens as ONE axis behind the barrier of ops/norms.py: on the
        # chip XLA plans this float32 product by how the leading dimensions
        # factor ([8, 64] tokens gave other bits than [1, 512]: PERF.md
        # section 6, PR 50), and a token's routing weights must not depend
        # on the block it rides in
        logits = jnp.matmul(_rows(h).astype(jnp.float32), lp["router"],
                            precision=jax.lax.Precision.HIGHEST
                            ).reshape(b, t, -1)
        w, idx = _route(cfg, lp, logits)                     # [B, T, k]
        held = jax.tree_util.tree_leaves(lp["moe_gate_up"])[0].shape[
            0 if layer is None else 1]
        local = idx - cfg.moe_first_expert
        mine = (local >= 0) & (local < held) & valid[..., None]
        gate = jnp.sum(
            jnp.where(mine, w, 0.0)[..., None]
            * jax.nn.one_hot(jnp.clip(local, 0, held - 1), held,
                             dtype=jnp.float32), axis=-2)    # [B, T, held]
    with jax.named_scope("moe/experts"):
        out, n_hit, rows = moe_ops.moe_held(
            h.reshape(b * t, hidden), gate.reshape(b * t, held),
            lp["moe_gate_up"], lp["moe_down"], cfg.act, gated=cfg.mlp_gated,
            layer=layer, top_k=cfg.num_experts_per_tok,
            n_routed=cfg.num_experts, limit=cfg.mlp_act_limit)
        out = out.reshape(b, t, hidden)
    if shared and "shared_gate_up" in lp:
        with jax.named_scope("moe/shared"):
            inner = linear_ops.linear(h, lp["shared_gate_up"])
            if cfg.mlp_gated:
                inner = mlp_ops.gated_act_mul(
                    *mlp_ops.split_gate_up(inner), cfg.act,
                    cfg.mlp_act_limit)
            else:
                inner = mlp_ops.act(inner, cfg.act)
            out = out + linear_ops.linear(
                inner, lp["shared_down"]).astype(jnp.float32)
    stats = jnp.stack([
        jnp.sum(valid).astype(jnp.int32) * cfg.num_experts_per_tok,
        jnp.sum(mine).astype(jnp.int32), n_hit,
        jnp.asarray(1, jnp.int32), rows])
    return out.astype(x.dtype), stats


def hybrid_schedule(pattern: str):
    """How a hybrid pattern is walked.  Read as units ``M [*] E`` (every
    Nemotron-3-Nano block sequence is 23 of them, six holding an attention
    block) it compiles as ONE scanned body: ``("units", [has attention, a
    unit])``.  A pattern of ``P`` blocks alone (Falcon-H1: attention and a
    Mamba-2 mixer side by side, then a gated MLP) is one scanned body too:
    ``("parallel", number of blocks)``.  A pattern of neither form is walked
    block by block: ``("blocks", [(kind, index among its kind)])``."""
    import re

    if re.fullmatch(r"P+", pattern):
        return "parallel", len(pattern)
    if re.fullmatch(r"(M\*?E)+", pattern):
        return "units", ["*" in u for u in re.findall(r"M\*?E", pattern)]
    seen: dict[str, int] = {}
    blocks = []
    for kind in pattern:
        blocks.append((kind, seen.get(kind, 0)))
        seen[kind] = seen.get(kind, 0) + 1
    return "blocks", blocks


def run_hybrid(cfg: ModelConfig, tree: dict, cache, x, slot0, q_slots,
               kv_len, kv_start, valid, chunk_lens=None, state_rows=None,
               cos=None, sin=None):
    """Walk a hybrid model's blocks: ``x <- x + Mixer(norm(x))``, one mixer
    a block, over the per-KIND stacks of ``tree`` (``mamba``, ``moe``,
    ``attn``).  Attention block ``a`` addresses layer ``a`` of the KV cache
    (a paged pool in place by ``(layer, page)``, a dense cache by its layer
    slice); Mamba block ``m`` rewrites slot ``m`` of ``cache.state``.
    ``state_rows`` [B]: the batch is a prefill block whose entry i is state
    row ``state_rows[i]`` (a row starting at slot 0 starts from a zero
    state); None: entry i is row i.  Returns (x, k, v, state).

    A PARALLEL block (``P``; the stack ``parallel``) is ``x <- x + Attn(u) +
    Mixer(u)`` with ONE ``u = norm(x)``, then ``x <- x + MLP(norm'(x))``:
    block ``i`` addresses layer ``i`` of the KV cache AND slot ``i`` of the
    state.  ``cos``/``sin``: the rotary tables of a family that rotates
    (None: no rotary embedding, nemotron_h)."""
    from ipex_llm_tpu.ops import ssm as ssm_ops

    paged = hasattr(cache, "tables")
    fresh = None if state_rows is None else slot0 == 0
    state = dict(cache.state)
    # the tick's read-only source state (engine._tick_body): handed to the
    # Mamba mixer beside the state it writes, never carried or returned
    src = state.pop("src", None)
    if fresh is not None:
        resets = jnp.sum(fresh & (jnp.sum(valid, axis=1) > 0))
        state["stats"] = state["stats"].at[
            STATE_COUNTERS.index("ssm_state_resets")].add(
                resets.astype(jnp.int32))
    no_window = jnp.asarray(False)

    def mixer(u, lp, state, m):
        out, state = ssm_ops.mamba2_mixer(
            cfg, lp, u, state if src is None else dict(state, src=src), m,
            valid, rows=state_rows, fresh=fresh)
        state.pop("src", None)
        return out, state

    def mamba(x, lp, state, m):
        with jax.named_scope("layer/ssm"):
            out, state = mixer(_norm(x, lp["norm"], cfg), lp, state, m)
            return x + out, state

    def attention(x, k, v, lp, a, pre_normed=False):
        # (``pre_normed``: ``x`` is the normed input and the branch's output
        # is returned alone, for the caller's sum)
        if paged:
            out, k, v, _ = _attention_block(
                cfg, lp, x, k, v, cos, sin, slot0, q_slots, kv_len,
                kv_start, no_window, cache, chunk_lens=chunk_lens, layer=a,
                pre_normed=pre_normed)
            return (out if pre_normed else x + out), k, v
        kl = jax.lax.dynamic_index_in_dim(k, a, 0, False)
        vl = jax.lax.dynamic_index_in_dim(v, a, 0, False)
        out, kl, vl, _ = _attention_block(
            cfg, lp, x, kl, vl, cos, sin, slot0, q_slots, kv_len, kv_start,
            no_window, cache, chunk_lens=chunk_lens, pre_normed=pre_normed)
        return ((out if pre_normed else x + out),
                jax.lax.dynamic_update_index_in_dim(k, kl, a, 0),
                jax.lax.dynamic_update_index_in_dim(v, vl, a, 0))

    def experts(x, lp, state, layer=None):
        out, stats = _held_moe_block(cfg, lp, x, valid, layer=layer)
        return x + out, dict(
            state, stats=state["stats"].at[:stats.shape[0]].add(stats))

    def entry(stack, i):
        # the quantized weights as views of their stacks, the rest sliced
        xs, view = layer_views(stack)
        return view(jax.tree_util.tree_map(
            lambda a: jax.lax.dynamic_index_in_dim(a, i, 0, False), xs), i)

    kind, plan = hybrid_schedule(cfg.hybrid_pattern)
    k, v = cache.k, cache.v
    if kind == "parallel":
        block_xs, block_view = layer_views(tree["parallel"])

        def parallel(carry, xs):
            x, k, v, state = carry
            lp, i = xs
            lp = block_view(lp, i)
            with jax.named_scope("layer/qkv"):
                u = _in_norm(x, lp, "attn_norm", cfg)
            a_out, k, v = attention(u, k, v, lp, i, pre_normed=True)
            with jax.named_scope("layer/ssm"):
                s_out, state = mixer(u, lp, state, i)
                x = x + a_out + s_out
            with jax.named_scope("layer/mlp"):
                x = x + _mlp_block(cfg, lp, x)
            return (x, k, v, state), None

        (x, k, v, state), _ = jax.lax.scan(
            parallel, (x, k, v, state),
            (block_xs, jnp.arange(plan, dtype=jnp.int32)))
        return x, k, v, state
    if kind == "blocks":
        for block, i in plan:
            if block == "M":
                x, state = mamba(x, entry(tree["mamba"], i), state, i)
            elif block == "E":
                x, state = experts(x, entry(tree["moe"], i), state)
            else:
                x, k, v = attention(x, k, v, entry(tree["attn"], i), i)
        return x, k, v, state

    has_attn = jnp.asarray(plan)
    attn_idx = jnp.maximum(jnp.cumsum(has_attn) - 1, 0).astype(jnp.int32)
    # the expert stacks stay whole and are addressed by (block, expert): as
    # the scan's ``xs`` a block's stack would be sliced out, every held
    # expert's planes copied a block a tick
    stacks = ("moe_gate_up", "moe_down")
    moe_whole = {k_: tree["moe"][k_] for k_ in stacks}
    # likewise every quantized linear: whole, and handed on as a view of its
    # stack at the unit's index (quantize/core.py ``layer_views``)
    mamba_xs, mamba_view = layer_views(tree["mamba"])
    moe_xs, moe_view = layer_views(
        {k_: v_ for k_, v_ in tree["moe"].items() if k_ not in stacks})

    def unit(carry, xs):
        x, k, v, state = carry
        m_lp, e_lp, m, attn_here, a = xs
        m_lp, e_lp = mamba_view(m_lp, m), moe_view(e_lp, m)
        x, state = mamba(x, m_lp, state, m)
        if "attn" in tree:
            x, k, v = jax.lax.cond(
                attn_here,
                lambda x, k, v: attention(x, k, v, entry(tree["attn"], a), a),
                lambda x, k, v: (x, k, v), x, k, v)
        x, state = experts(x, dict(e_lp, **moe_whole), state, layer=m)
        return (x, k, v, state), None

    n = len(plan)
    (x, k, v, state), _ = jax.lax.scan(
        unit, (x, k, v, state),
        (mamba_xs, moe_xs, jnp.arange(n, dtype=jnp.int32), has_attn,
         attn_idx))
    return x, k, v, state


def embed_prelude(cfg: ModelConfig, params, tokens, rope_positions,
                  input_embeds=None):
    """Embedding + positional prelude shared by decoder_forward and the
    pipeline microbatch scheduler (parallel/pipeline.py): token (or spliced
    multimodal) embeddings, embedding multiplier/norm, learned positions,
    and the rope/M-ROPE cos-sin tables.  Returns (x, cos, sin)."""
    from ipex_llm_tpu.ops.embedding import embed_lookup

    b = tokens.shape[0]
    if input_embeds is not None:
        # multimodal path: image features already spliced into the stream
        x = input_embeds.astype(COMPUTE_DTYPE)
    else:
        x = embed_lookup(params["embed"], tokens, COMPUTE_DTYPE)
    if cfg.embedding_multiplier != 1.0:
        x = x * jnp.asarray(cfg.embedding_multiplier, COMPUTE_DTYPE)
    if cfg.learned_pos:
        # gpt2/opt absolute positions: logical (left-pad-aware) indices
        pos_clip = jnp.clip(rope_positions, 0, cfg.learned_pos - 1)
        x = x + params["pos_embed"][pos_clip].astype(COMPUTE_DTYPE)
    if cfg.embed_norm:  # bloom word_embeddings_layernorm
        x = _norm(x, params["embed_norm"], cfg, params.get("embed_norm_bias"))

    cos, sin = (None, None)
    if cfg.rope is not None:
        # FROZEN_BUFFER_KEYS are non-trainable: without stop_gradient, full
        # fine-tuning / LISA would drift the RoPE tables every step.
        def frozen(key, default=None):
            v = params.get(key, default)
            return v if isinstance(v, (float, int, type(None))) else (
                jax.lax.stop_gradient(v)
            )

        if cfg.mrope_section is not None:
            # qwen2-vl M-ROPE: [B,3,T] t/h/w channels ([B,T] text-only input
            # broadcasts to equal channels, reducing to plain rope)
            mpos = rope_positions
            if mpos.ndim == 2:
                mpos = jnp.broadcast_to(mpos[:, None, :],
                                        (b, 3, mpos.shape[1]))
            cos, sin = rope_ops.cos_sin_mrope(
                mpos, frozen("inv_freq"), cfg.mrope_section
            )
        elif cfg.rope_2d:
            # chatglm v1 2D rotary (reference chatglm.py:35-40
            # apply_rotary_pos_emb_index over 2-channel position ids):
            # positions [B,2,T] = (sequence, block) channels; a [B,T] input
            # means "all context" (block channel 0).  The two per-channel
            # tables ride concatenated; _attention_block splits head_dim in
            # half and rotates each half with its own table.
            p2 = rope_positions
            if p2.ndim == 2:
                p2 = jnp.stack([p2, jnp.zeros_like(p2)], axis=1)
            c1, s1 = rope_ops.cos_sin(p2[:, 0], frozen("inv_freq"))
            c2, s2 = rope_ops.cos_sin(p2[:, 1], frozen("inv_freq"))
            cos = jnp.concatenate([c1, c2], axis=-1)
            sin = jnp.concatenate([s1, s2], axis=-1)
        else:
            cos, sin = rope_ops.cos_sin(
                rope_positions, frozen("inv_freq"), frozen("rope_mscale", 1.0)
            )
    return x, cos, sin


def local_rope_tables(cfg: ModelConfig, params, rope_positions):
    """gemma3: sliding layers rope with a separate local-frequency table
    (cfg.rope_local -> params["inv_freq_local"]); None for other models."""
    if "inv_freq_local" not in params or cfg.rope is None \
            or cfg.mrope_section is not None:
        return None, None
    inv = params["inv_freq_local"]
    if not isinstance(inv, (float, int)):
        inv = jax.lax.stop_gradient(inv)
    return rope_ops.cos_sin(rope_positions, inv, 1.0)


def alibi_bias_for(cfg: ModelConfig, q_slots, s: int):
    """ALiBi bias [B, H, T, S] (bloom/mpt/baichuan-13b): slope *
    (k_pos - q_pos), identical for every layer — built ONCE per forward
    (like cos/sin), never inside the scan body.  Slot arithmetic cancels
    kv_start, so left-padding is transparent."""
    slopes = alibi_slopes(cfg.num_heads)
    kv_pos = jnp.arange(s, dtype=jnp.float32)
    dist = kv_pos[None, None, None, :] - q_slots.astype(jnp.float32)[
        :, None, :, None]                           # [B,1,T,S] (<=0 causal)
    return slopes[None, :, None, None] * dist


@jax.named_scope("head")
def logits_tail(cfg: ModelConfig, params, x):
    """Final norm + lm head + logit scale/softcap — the post-stack tail
    shared by decoder_forward and the pipeline scheduler."""
    x = _norm(x, params["final_norm"], cfg, params.get("final_norm_bias"))
    lm_head = params.get("lm_head")
    if lm_head is None:  # tied embeddings
        logits = jnp.matmul(
            x.astype(COMPUTE_DTYPE), params["embed"].T.astype(COMPUTE_DTYPE),
            preferred_element_type=jnp.float32,
        )
    else:
        logits = linear_ops.linear(
            x, lm_head, params.get("lm_head_bias")
        ).astype(jnp.float32)
        from ipex_llm_tpu.ops import dispatch as _dispatch

        mt = _dispatch.manual_tp_state()
        if mt is not None and getattr(lm_head, "tp_mode", None) == "col":
            # manual-mesh region with a column-parallel lm head: each
            # shard holds its contiguous vocab slice of the logits —
            # gather to full width so sampling runs replicated (every
            # shard draws the same token from the same key).  Exact: an
            # all-gather moves bits, col-parallel splits no reduction.
            logits = jax.lax.all_gather(logits, mt[0], axis=-1, tiled=True)
    if cfg.logit_scale != 1.0:  # cohere
        logits = logits * cfg.logit_scale
    if cfg.logit_softcap is not None:
        logits = jnp.tanh(logits / cfg.logit_softcap) * cfg.logit_softcap
    return logits.astype(jnp.float32)


def run_layers(cfg: ModelConfig, tree, k_stack, v_stack, sliding_flags,
               x, cos, sin, slot0, q_slots, kv_len, kv_start, cache,
               collect_obs: int = 0, alibi_bias=None,
               cos_local=None, sin_local=None, chunk_lens=None,
               layer_offset: int = 0, valid=None, selection=None,
               recurrent=None):
    """Scan one stacked layer tree over its layers of the cache.

    The single compiled layer body shared by decoder_forward and the
    pipeline-parallel microbatch scheduler (parallel/pipeline.py), which
    runs each stage's LOCAL chunk of layers through this same function
    inside shard_map.  ``k_stack``/``v_stack`` are the cache's stacks, of
    which the tree's ``n`` layers start at ``layer_offset`` (a two-stack
    model's second tree starts past the first).  Returns (x, k_new, v_new,
    obs_q, moe_stats) with the stacks whole again; ``moe_stats`` int32
    (``kv.MOE_COUNTERS``) is what the tree's HELD expert layers counted,
    zeros where it has none.

    How the cache rides the scan follows its class.  A dense cache
    [L, B, H, S, D] is per-row, so the scan runs over layer slices: the
    cache's layers are the scan's ``xs`` and come back as its ``ys``.  A
    paged pool [L, P, H, page, D] (``cache.tables``) is addressed through
    the block table, so nothing needs slicing: the stacks ride the scan's
    carry beside ``x`` and are written in place.

    Of the tree, the ``xs`` hold what XLA operations read and a slice
    fuses into: norm weights, biases, native-width and expert stacks,
    beside the sliding flag and each layer's index.  A HELD expert layer's
    two stacks (``cfg.moe_experts_held``: the layer is told which experts it
    holds; ``valid`` [B, T] then keeps padding from routing anywhere) do not
    ride either: whole, read in place by ``(layer, expert)``.  The QUANTIZED linears
    do not ride: their stacks stay whole, closed over by the body, and
    ``lp`` holds a view "this stack at this layer" of each (``QLayer``),
    which the dequant-matmul kernel reads in place.  As ``xs`` every packed
    plane would be copied out of its stack before the kernel call that
    reads it, every layer, every pass (a quarter of the device's time, PR
    30's ledger).

    A model with an INDEXER over a paged pool (``selection`` given:
    :func:`no_selection`, or what the tree below returned) carries a
    selection beside the stacks: a layer whose ``cfg.indexer_types`` entry is
    "full" replaces it and the "shared" layers after it attend over it
    (:func:`_sparse_mla`).  The indexer's weights are the tree's
    ``indexer`` stack, an entry a FULL layer of the tree only, read at the
    full layer's index; its index keys are ``v_stack``, a layer a full layer
    of the MODEL.  The return gains ``(selection, int32 [5] counters)``.

    A model of HYPER-CONNECTED residual streams (``cfg.hc_mult`` > 1) carries
    ``x`` as ``[n, B, T, C]``, the streams leading (ops/hyper.py): each
    sublayer reads the mix ``h`` [B, T, C] its coefficients give and the
    streams are re-mixed around its output, where another model adds.

    A model whose MIXER KIND varies by layer (``cfg.mixer_types``; over its
    sparse latent pool, ``selection`` given) is walked by the same scan: a
    "kda" layer runs Kimi Delta Attention (ops/kda.py) where an "mla" layer
    attends, under one ``lax.cond`` a layer.  The weights are stacked per
    KIND: the tree's ``kda`` and ``mla`` sub-trees hold an entry a layer of
    that kind and are read at the layer's index among its kind, as the
    ``indexer`` stack is; what every layer has (norms, the hyper-
    connections' planes, the MLP or expert layer) stays in the tree itself.
    The pool holds the "mla" layers alone, so such a layer addresses pool
    layer ``index among the model's mla layers``.  ``recurrent`` = ``(src,
    rows, fresh, state)`` is the per-row state's call: ``state`` the leaves
    that are written (``kv.recurrent_state`` without its counters), carried
    beside the selection; ``src`` the tick's read-only source buffer (None:
    read what is written); ``rows``/``fresh`` a prefill block's map to state
    rows and which of them start from zero (None: entry i is row i).  The
    return gains ``(state, int32 [2] kv.KDA_COUNTERS)``.
    """
    n = sliding_flags.shape[0]
    dsa = selection is not None
    kinds = (cfg.mixer_types[layer_offset:layer_offset + n]
             if cfg.mixer_types else None)
    if kinds is not None and (not dsa or recurrent is None):
        raise ValueError(
            f"{cfg.model_type} mixes layer kinds over per-row recurrent "
            "state and a sparse latent pool, which only the serving path "
            "carries (ServingEngine / build_server)")
    ix_tree = tree.get("indexer")
    if ix_tree is not None:
        # (a dense cache attends every token and never reads it)
        tree = {k_: v_ for k_, v_ in tree.items() if k_ != "indexer"}
    if kinds is not None:
        from ipex_llm_tpu.ops import kda as kda_ops

        src, state_rows, fresh, _ = recurrent
        is_kda = jnp.asarray([k_ == "kda" for k_ in kinds])
        # a layer's entry of this tree's stack of its kind, and its slot in
        # the model's state (the layers of its kind below this tree first)
        kind_local = jnp.where(
            is_kda, jnp.cumsum(is_kda) - 1, jnp.cumsum(~is_kda) - 1
        ).astype(jnp.int32)
        kda_below = sum(k_ == "kda" for k_ in cfg.mixer_types[:layer_offset])
        kind_xs = {k_: layer_views(tree[k_]) for k_ in ("kda", "mla")
                   if k_ in tree}
        tree = {k_: v_ for k_, v_ in tree.items() if k_ not in kind_xs}

        def of_kind(kind, j):
            xs, view_ = kind_xs[kind]
            return view_(jax.tree_util.tree_map(
                lambda a: jax.lax.dynamic_index_in_dim(a, j, 0, False), xs),
                j)
    if dsa:
        types = cfg.indexer_types[layer_offset:layer_offset + n]
        is_full = jnp.asarray([t_ == "full" for t_ in types])
        # a layer's entry of this tree's indexer stack, and of the model's
        # index-key pool (the full layers below this tree come first)
        fi_local = jnp.maximum(jnp.cumsum(is_full) - 1, 0).astype(jnp.int32)
        fi_below = sum(t_ == "full" for t_ in
                       cfg.indexer_types[:layer_offset])
        if ix_tree is not None:
            ix_xs, ix_view = layer_views(ix_tree)
    held = cfg.moe_experts_held > 0 and "moe_gate_up" in tree
    moe_whole = {}
    if held:
        if valid is None:
            valid = jnp.ones(x.shape[-3:-1], bool)
        moe_whole = {k_: tree[k_] for k_ in ("moe_gate_up", "moe_down")}
        tree = {k_: v_ for k_, v_ in tree.items() if k_ not in moe_whole}
    tree_xs, view = layer_views(tree)
    local = jnp.arange(n, dtype=jnp.int32)    # a layer's index in ``tree``
    no_stats = jnp.zeros((len(MOE_COUNTERS),), jnp.int32)

    def ffn(lp, x, i):
        if held:
            return _held_moe_block(cfg, dict(lp, **moe_whole), x, valid,
                                   layer=i)
        block = _moe_block if "moe_gate_up" in lp else _mlp_block
        return block(cfg, lp, x), no_stats

    def mixer(lp, attn_in, kl, vl, sliding, dsa_call, kind_call):
        """The mixer of a layer whose kind varies: (out, k, v, selection,
        the indexer's counters, the state, the KDA counters)."""
        kda_here, j, st = kind_call
        full, indexer, fi, sel = dsa_call
        no_dsa = jnp.zeros((5,), jnp.int32)
        no_kda = jnp.zeros((2,), jnp.int32)

        def attend(kl, vl, sel, st):
            out, kl, vl, _, sel, d_i, st = _attention_block(
                cfg, dict(lp, **of_kind("mla", j)), attn_in, kl, vl, cos,
                sin, slot0, q_slots, kv_len, kv_start, sliding, cache,
                chunk_lens=chunk_lens, layer=fi,
                dsa=(full, indexer, fi, sel, (src, state_rows, fresh, st)))
            return out, kl, vl, sel, d_i, st, no_kda

        def delta(kl, vl, sel, st):
            with jax.named_scope("layer/kda"):
                out, counts, st = kda_ops.kda_mixer(
                    cfg, of_kind("kda", j),
                    _in_norm(attn_in, lp, "attn_norm", cfg), st,
                    st if src is None else src, kda_below + j, valid,
                    rows=state_rows, fresh=fresh)
            return out, kl, vl, sel, no_dsa, st, jnp.stack(counts)

        if "mla" not in kind_xs:
            return delta(kl, vl, sel, st)
        if "kda" not in kind_xs:
            return attend(kl, vl, sel, st)
        return jax.lax.cond(kda_here, delta, attend, kl, vl, sel, st)

    def layer_step(x, lp, kl, vl, sliding, layer, i, dsa_call=None,
                   kind_call=None):
        if cos_local is not None:
            # gemma3 dual rope: sliding layers use the local table
            c = jnp.where(sliding, cos_local, cos)
            s_ = jnp.where(sliding, sin_local, sin)
        else:
            c, s_ = cos, sin
        if cfg.glm_alpha:
            # chatglm v1 GLM block (reference chatglm.py / THUDM
            # modeling_chatglm GLMBlock): the residual base is the NORMED
            # input scaled by alpha=(2*num_layers)**0.5, for both sublayers
            alpha = jnp.asarray(cfg.glm_alpha, x.dtype)
            a_in = _in_norm(x, lp, "attn_norm", cfg)
            attn_out, kl, vl, obs_q = _attention_block(
                cfg, lp, a_in, kl, vl, c, s_, slot0, q_slots, kv_len,
                kv_start, sliding, cache, collect_obs, bias=alibi_bias,
                pre_normed=True, chunk_lens=chunk_lens, layer=layer,
            )
            x = a_in * alpha + attn_out
            m_in = _in_norm(x, lp, "mlp_norm", cfg)
            x = m_in * alpha + _mlp_block(cfg, lp, m_in, pre_normed=True)
            return x, kl, vl, obs_q, no_stats
        hc = cfg.hc_mult > 1
        attn_in = x
        if hc:
            coef = hyper_ops.coefficients(cfg, lp, "attn", x)
            attn_in = hyper_ops.pre_mix(coef, x)
        if kind_call is not None:
            attn_out, kl, vl, *sparse = mixer(
                lp, attn_in, kl, vl, sliding, dsa_call, kind_call)
            obs_q = jnp.zeros((0,), x.dtype)
        else:
            attn_out, kl, vl, obs_q, *sparse = _attention_block(
                cfg, lp, attn_in, kl, vl, c, s_, slot0, q_slots, kv_len,
                kv_start, sliding, cache, collect_obs, bias=alibi_bias,
                chunk_lens=chunk_lens, layer=layer, dsa=dsa_call,
            )
        # minicpm depth scaling (cfg.residual_multiplier, 1.0 elsewhere)
        rm = (jnp.asarray(cfg.residual_multiplier, COMPUTE_DTYPE)
              if cfg.residual_multiplier != 1.0 else None)

        def add(res, out):
            return res + out if rm is None else res + rm * out

        if hc:
            # every sublayer reads its own mix of the streams and the
            # streams are re-mixed around what it returns (ops/hyper.py)
            x = hyper_ops.post_mix(coef, x, attn_out)
            coef = hyper_ops.coefficients(cfg, lp, "mlp", x)
            out, stats = ffn(lp, hyper_ops.pre_mix(coef, x), i)
            x = hyper_ops.post_mix(coef, x, out)
        elif cfg.parallel_blocks:
            # x + attn(ln(x)) + mlp(ln'(x)) — phi/gpt-neox parallel residual
            out, stats = ffn(lp, x, i)
            x = add(x, attn_out + out)
        else:
            x = add(x, attn_out)
            out, stats = ffn(lp, x, i)
            x = add(x, out)
        if dsa_call is not None:
            return x, kl, vl, obs_q, stats, *sparse
        return x, kl, vl, obs_q, stats

    # a held layer's counters leave the scan as one more ``ys``, where a
    # layer counts: every other model's scan is the program it was
    def ys(obs_q, s_i):
        return (obs_q, s_i) if held else obs_q

    def split(out):
        return (out[0], out[1].sum(0)) if held else (out, no_stats)

    if kinds is not None:
        def mixed_body(carry, xs):
            x, k, v, sel, st = carry
            lp, i, sliding, full, fi, kda_here, j = xs

            def indexer():
                return ix_view(jax.tree_util.tree_map(
                    lambda a: jax.lax.dynamic_index_in_dim(a, fi, 0, False),
                    ix_xs), fi)

            x, k, v, obs_q, s_i, sel, d_i, st, k_i = layer_step(
                x, view(lp, i), k, v, sliding, layer_offset + i, i,
                dsa_call=(full, indexer if ix_tree is not None else None,
                          fi_below + fi, sel), kind_call=(kda_here, j, st))
            return (x, k, v, sel, st), (ys(obs_q, s_i), d_i, k_i)

        (x, k_stack, v_stack, selection, st), (out, d_stats, k_stats) = \
            jax.lax.scan(
                mixed_body, (x, k_stack, v_stack, selection, recurrent[3]),
                (tree_xs, local, sliding_flags, is_full, fi_local, is_kda,
                 kind_local))
        return (x, k_stack, v_stack, *split(out), selection, d_stats.sum(0),
                st, k_stats.sum(0))

    if dsa:
        def sparse_body(carry, xs):
            x, k, v, sel = carry
            lp, i, sliding, full, fi = xs

            def indexer():
                return ix_view(jax.tree_util.tree_map(
                    lambda a: jax.lax.dynamic_index_in_dim(a, fi, 0, False),
                    ix_xs), fi)

            x, k, v, obs_q, s_i, sel, d_i = layer_step(
                x, view(lp, i), k, v, sliding, layer_offset + i, i,
                dsa_call=(full, indexer if ix_tree is not None else None,
                          fi_below + fi, sel))
            return (x, k, v, sel), (ys(obs_q, s_i), d_i)

        (x, k_stack, v_stack, selection), (out, d_stats) = jax.lax.scan(
            sparse_body, (x, k_stack, v_stack, selection),
            (tree_xs, local, sliding_flags, is_full, fi_local))
        return (x, k_stack, v_stack, *split(out), selection, d_stats.sum(0))

    if hasattr(cache, "tables"):
        def paged_body(carry, xs):
            x, k, v = carry
            lp, i, sliding = xs
            x, k, v, obs_q, s_i = layer_step(x, view(lp, i), k, v, sliding,
                                             layer_offset + i, i)
            return (x, k, v), ys(obs_q, s_i)

        (x, k_stack, v_stack), out = jax.lax.scan(
            paged_body, (x, k_stack, v_stack), (tree_xs, local, sliding_flags)
        )
        return (x, k_stack, v_stack, *split(out))

    def body(x, xs):
        lp, i, kl, vl, sliding = xs
        x, kl, vl, obs_q, s_i = layer_step(x, view(lp, i), kl, vl, sliding,
                                           None, i)
        return x, (kl, vl, ys(obs_q, s_i))

    whole = n == k_stack.shape[0]
    ours = slice(layer_offset, layer_offset + n)
    x, (k_new, v_new, out) = jax.lax.scan(
        body, x, (tree_xs, local, k_stack if whole else k_stack[ours],
                  v_stack if whole else v_stack[ours], sliding_flags)
    )
    if not whole:
        k_new = k_stack.at[ours].set(k_new)
        v_new = v_stack.at[ours].set(v_new)
    return (x, k_new, v_new, *split(out))


def decoder_forward(
    cfg: ModelConfig,
    params: dict[str, Any],
    tokens: jnp.ndarray,            # [B, T] int32
    cache: KVCache,
    rope_positions: jnp.ndarray,    # [B, T] logical positions (left-pad aware)
    kv_start: jnp.ndarray | None = None,  # [B] first valid cache slot
    last_token_only: bool = False,
    collect_obs: int = 0,
    slot_offsets: jnp.ndarray | None = None,  # [B] per-row cache write slots
    input_embeds: jnp.ndarray | None = None,  # [B, T, H] bypasses the lookup
    gather_positions: jnp.ndarray | None = None,  # [B] per-row logits index
    chunk_lens: jnp.ndarray | None = None,    # [B] valid tokens this call
    state_rows: jnp.ndarray | None = None,    # [B] recurrent-state row map
):
    """Run the decoder; returns (logits, updated cache).

    logits: [B, V] if last_token_only else [B, T, V].

    ``collect_obs=W`` (static, prefill-only) additionally returns the last-W
    post-RoPE queries of every layer ``[L, B, W, Hq, D]`` — the SnapKV
    observation window used by compresskv.compress (reference kv.py:221).

    ``slot_offsets`` [B] overrides the uniform ``cache.length`` write slot
    with per-row offsets (continuous batching); the returned cache's
    ``length`` is then left untouched — the caller tracks row lengths.

    ``gather_positions`` [B] selects ONE position per row for the logits
    tail (returns [B, V], like ``last_token_only``) — the serving engine's
    mixed prefill+decode step, where a ragged right-padded chunk puts each
    row's last valid token at a different index.  Gathering the hidden
    state BEFORE the lm head keeps the tail matmul at [B, 1, H] — the same
    shape (and therefore the same bitwise result) as the T=1 decode step's
    tail — instead of projecting every pad position.

    ``chunk_lens`` [B] (with ``slot_offsets``) names each row's REAL token
    count this call: the valid-KV bound becomes ``slot_offsets +
    chunk_lens`` instead of the pad-inclusive ``slot_offsets + T``, and
    the per-row raggedness flows into attention (the ragged paged kernel's
    causal mask; a decode row is 1, an idle row 0).  Valid positions
    compute bitwise what the pad-inclusive bound computes — the tighter
    bound only stops pad queries (whose outputs are discarded) from
    touching pad slots, and lets the kernel skip dead pages entirely.
    """
    from ipex_llm_tpu.ops.embedding import embed_lookup

    b, t = tokens.shape
    x, cos, sin = embed_prelude(cfg, params, tokens, rope_positions,
                                input_embeds)
    cos_l, sin_l = local_rope_tables(cfg, params, rope_positions)

    alibi_bias = None

    if slot_offsets is not None:
        slot0 = slot_offsets                       # [B]
        q_slots = slot0[:, None] + jnp.arange(t)[None, :]
        # ragged chunk: the valid-KV bound follows each row's REAL token
        # count, not the right-padded width (pad queries are discarded)
        kv_len = slot0 + (chunk_lens if chunk_lens is not None else t)
    else:
        slot0 = cache.length
        q_slots = jnp.broadcast_to(slot0 + jnp.arange(t)[None, :], (b, t))
        kv_len = jnp.broadcast_to(slot0 + t, (b,))

    if cfg.alibi:
        alibi_bias = alibi_bias_for(cfg, q_slots, cache.max_len)

    if cfg.hybrid_pattern is not None:
        if cache.state is None:
            raise ValueError(
                f"{cfg.model_type} carries recurrent state: build the cache "
                "with kv.recurrent_state(cfg, rows) as its `state`")
        if collect_obs:
            raise NotImplementedError(
                "SnapKV observation windows on a hybrid model")
        valid = _valid_positions(b, t, q_slots, kv_start, chunk_lens)
        x, k_new, v_new, state = run_hybrid(
            cfg, params["hybrid"], cache, x, slot0, q_slots, kv_len,
            kv_start, valid, chunk_lens=chunk_lens, state_rows=state_rows,
            cos=cos, sin=sin)
        logits = _chosen_logits(cfg, params, x, last_token_only,
                                gather_positions)
        new_len = cache.length if slot_offsets is not None else slot0 + t
        return logits, replace(cache, k=k_new, v=v_new, length=new_len,
                               state=state)

    sliding_flags = jnp.array(
        [cfg.layer_is_sliding(l) for l in range(cfg.num_layers)], dtype=bool
    )
    if cfg.hc_mult > 1:
        # hyper-connected residual streams: the embedding goes into every
        # stream, and their sum leaves for the final norm (ops/hyper.py)
        x = hyper_ops.expand(x, cfg.hc_mult)

    # deepseek-style dense-prefix models carry two layer stacks (plain-MLP
    # prefix + MoE rest, models/build.py); each runs its own scan over its
    # layers of the cache (the stacks pass through whole, addressed from
    # layer ``lo`` on) so every stack still compiles one layer body
    if "layers_dense" in params:
        nd = cfg.moe_layer_start
        stacks = [(params["layers_dense"], 0, nd),
                  (params["layers"], nd, cfg.num_layers)]
    else:
        stacks = [(params["layers"], 0, cfg.num_layers)]
    k_new, v_new, obs_parts = cache.k, cache.v, []
    valid = (_valid_positions(b, t, q_slots, kv_start, chunk_lens)
             if cfg.moe_experts_held or cfg.kda_layers else None)
    state = cache.state
    if state is not None:
        # (``src``: the tick's read-only source buffer, engine._tick_body)
        src = state.get("src")
        state = {k_: v_ for k_, v_ in state.items() if k_ != "src"}
    selection = None
    if cfg.index_topk:
        if getattr(cache, "sparse", False):
            selection = no_selection(cfg, b, t, cache.max_len)
        elif cache.max_len > cfg.index_topk or cfg.kda_layers:
            raise ValueError(
                f"{cfg.model_type} attends over the {cfg.index_topk} tokens "
                "its indexer selects, which only the serving path's sparse "
                "latent pool computes (ServingEngine / build_server): this "
                f"cache holds {cache.max_len} tokens a row and would attend "
                "all of them.  Up to index_topk tokens every token is "
                "selected and the dense cache is exact")
    recurrent = None
    if cfg.kda_layers:
        if state is None:
            raise ValueError(
                f"{cfg.model_type} carries recurrent state: build the cache "
                "with kv.recurrent_state(cfg, rows) as its `state`")
        if collect_obs:
            raise NotImplementedError(
                "SnapKV observation windows on a model with recurrent state")
        fresh = None if state_rows is None else slot0 == 0
        if fresh is not None:
            # (rows that start at slot 0 start from a zero state)
            state["stats"] = state["stats"].at[
                STATE_COUNTERS.index("ssm_state_resets")].add(
                    jnp.sum(fresh & (jnp.sum(valid, axis=1) > 0)).astype(
                        jnp.int32))
        recurrent = (src, state_rows, fresh)
    for tree, lo, hi in stacks:
        x, k_new, v_new, op, moe_stats, *sparse = run_layers(
            cfg, tree, k_new, v_new, sliding_flags[lo:hi],
            x, cos, sin, slot0, q_slots, kv_len, kv_start, cache,
            collect_obs, alibi_bias, cos_local=cos_l, sin_local=sin_l,
            chunk_lens=chunk_lens, layer_offset=lo, valid=valid,
            selection=selection,
            # (the leaves the tree below left, without the counters)
            recurrent=recurrent and (*recurrent, {
                k_: v_ for k_, v_ in state.items() if k_ != "stats"}),
        )
        obs_parts.append(op)
        if recurrent is not None:
            # the KDA layers' counters follow the held layers' (kv.py
            # ``KDA_COUNTERS``)
            *sparse, leaves, kda_stats = sparse
            n0 = len(STATE_COUNTERS)
            state = dict(leaves, stats=state["stats"].at[
                n0:n0 + kda_stats.shape[0]].add(kda_stats))
        if state is not None and valid is not None:
            # the held expert layers' device counters (kv.py
            # ``STATE_COUNTERS``)
            state["stats"] = state["stats"].at[:moe_stats.shape[0]].add(
                moe_stats)
        if sparse:
            # the tree above attends over what this one's last full layer
            # chose; the indexer's counters follow the held layers' (kv.py
            # ``DSA_COUNTERS``)
            selection, dsa_stats = sparse
            if state is not None:
                n0 = state["stats"].shape[0] - dsa_stats.shape[0]
                state["stats"] = state["stats"].at[n0:].add(dsa_stats)
    obs_q = (jnp.concatenate(obs_parts) if len(obs_parts) > 1
             else obs_parts[0])
    if cfg.hc_mult > 1:
        x = hyper_ops.collapse(x)

    logits = _chosen_logits(cfg, params, x, last_token_only,
                            gather_positions)

    new_len = cache.length if slot_offsets is not None else slot0 + t
    new_cache = replace(cache, k=k_new, v=v_new, length=new_len, state=state)
    if collect_obs:
        return logits, new_cache, obs_q
    return logits, new_cache


class TokenBlock(NamedTuple):
    """One block of a call of :func:`forward_blocks`: ``tokens`` [B, T] at
    rope ``positions`` [B, T], written from slot ``slot_offsets`` [B] of the
    rows that block table ``tables`` [B, pages] names, ``chunk_lens`` [B] of
    them real; the logits wanted are those at ``gather`` [B] (None: the
    last position)."""

    tokens: jnp.ndarray
    positions: jnp.ndarray
    slot_offsets: jnp.ndarray
    chunk_lens: jnp.ndarray
    tables: jnp.ndarray
    gather: jnp.ndarray | None = None


def fuses_blocks(cfg: ModelConfig) -> bool:
    """Whether :func:`forward_blocks` runs this model, from ``cfg`` alone:
    every layer plain attention (the non-MLA path of
    :func:`_attention_block`) and a dense MLP (:func:`_mlp_block`) on one
    residual stream, and nothing about a token that depends on the block
    it rides in but where its K/V lies (ALiBi's bias is laid out over a
    block's ``q_slots`` and chatglm v1's positions have two channels: those
    families keep one block a forward)."""
    return not (cfg.is_mla or cfg.recurrent_layers or cfg.num_experts
                or cfg.index_topk or cfg.hc_mult > 1 or cfg.alibi
                or cfg.glm_alpha or cfg.rope_2d)


def forward_blocks(cfg: ModelConfig, params: dict[str, Any], cache,
                   blocks: tuple[TokenBlock, ...]):
    """Several token blocks over a paged pool in ONE pass over the weights
    (the serving engine's fused mixed tick: a prefill block ``[P, W]`` and
    the decode rows ``[R, 1]``).  Returns (one logits array ``[B, V]`` a
    block, the cache with its pool written).

    Everything that is per token sees the blocks' tokens as ONE axis ``[1,
    T, hidden]``, T the sum of the blocks' ``B * T``: the embedding, the
    norms, the projections with the rotary tables gathered per token, the
    MLP and the head (the chosen position of every row of every block, one
    call), so every linear is called once, at M = T.  What is per ROW is
    called per block, with that block's table, offsets and lengths, in the
    shapes :func:`decoder_forward` gives it: the pool write
    (``layer/kv_write``) and attention (``layer/attn``), which read no
    weight.  The blocks' rows are distinct, so the order of the blocks does
    not show; all writes of a layer come before its reads.

    A token's bits are those of :func:`decoder_forward` over its block
    alone: a row's dot products do not depend on M (ops/pallas/qmatmul.py),
    the norms reduce over ``[N, H]`` whatever the block (ops/norms.py
    ``_rows``), and attention is the same call.  Only for a model
    :func:`fuses_blocks` admits."""
    if not fuses_blocks(cfg):
        raise ValueError(f"{cfg.model_type} does not run as fused blocks")

    def flat(arrays):
        return jnp.concatenate([a.reshape(1, -1) for a in arrays], axis=1)

    positions = flat([b.positions for b in blocks])
    x, cos, sin = embed_prelude(cfg, params, flat([b.tokens for b in blocks]),
                                positions)
    cos_l, sin_l = local_rope_tables(cfg, params, positions)
    # a block's cut (offset, B, T) of the token axis, what its pool write
    # and attention call take, and where its chosen positions lie
    calls, at, off = [], [], 0
    for blk in blocks:
        b, t = blk.tokens.shape
        calls.append((blk, (off, b, t), replace(cache, tables=blk.tables),
                      blk.slot_offsets[:, None] + jnp.arange(t)[None, :],
                      blk.slot_offsets + blk.chunk_lens))
        last = (t - 1 if blk.gather is None
                else jnp.clip(blk.gather, 0, t - 1))
        at.append(off + jnp.arange(b) * t + last)
        off += b * t

    def attention(lp, x, kl, vl, c, s_, sliding, layer):
        with jax.named_scope("layer/qkv"):
            h = x if cfg.norm_after else _in_norm(x, lp, "attn_norm", cfg)
            q, k, v, _ = _qkv_rope(cfg, lp, x, h, c, s_, 0)

        def cut(z, o, b, t):
            return z[0, o:o + b * t].reshape(b, t, *z.shape[2:])

        for blk, span, pool, _, _ in calls:
            kl, vl = paged_kv_write(pool, kl, vl, layer, cut(k, *span),
                                    cut(v, *span), blk.slot_offsets)
        outs = []
        for blk, span, pool, q_slots, kv_len in calls:
            with jax.named_scope("layer/attn"):
                a = cached_sdpa(
                    cut(q, *span), kl, vl, pool, compute_dtype=COMPUTE_DTYPE,
                    causal=True, q_positions=q_slots, kv_len=kv_len,
                    kv_start=None, window=cfg.sliding_window,
                    window_on=sliding, softcap=cfg.attn_softcap,
                    scale=cfg.attn_scale, chunk_lens=blk.chunk_lens,
                    layer=layer)
            outs.append(a.reshape(1, -1, *a.shape[2:]))
        return _o_proj(cfg, lp, jnp.concatenate(outs, axis=1)), kl, vl

    tree_xs, view = layer_views(params["layers"])
    rm = (jnp.asarray(cfg.residual_multiplier, COMPUTE_DTYPE)
          if cfg.residual_multiplier != 1.0 else None)

    def add(res, out):
        return res + out if rm is None else res + rm * out

    def body(carry, xs):
        x, kl, vl = carry
        lp, i, sliding = xs
        lp = view(lp, i)
        if cos_l is not None:
            # gemma3 dual rope: sliding layers use the local table
            c, s_ = jnp.where(sliding, cos_l, cos), jnp.where(sliding, sin_l,
                                                              sin)
        else:
            c, s_ = cos, sin
        attn_out, kl, vl = attention(lp, x, kl, vl, c, s_, sliding, i)
        if cfg.parallel_blocks:
            x = add(x, attn_out + _mlp_block(cfg, lp, x))
        else:
            x = add(x, attn_out)
            x = add(x, _mlp_block(cfg, lp, x))
        return (x, kl, vl), None

    sliding_flags = jnp.array(
        [cfg.layer_is_sliding(l) for l in range(cfg.num_layers)], dtype=bool)
    (x, k_new, v_new), _ = jax.lax.scan(
        body, (x, cache.k, cache.v),
        (tree_xs, jnp.arange(cfg.num_layers, dtype=jnp.int32), sliding_flags))
    logits = logits_tail(cfg, params, x[:, jnp.concatenate(at)])[0]
    rows = np.cumsum([blk.tokens.shape[0] for blk in blocks])
    return (tuple(jnp.split(logits, rows[:-1])),
            replace(cache, k=k_new, v=v_new))


def _valid_positions(b: int, t: int, q_slots, kv_start, chunk_lens):
    """[B, T]: which positions of a call hold a token (not padding)."""
    if chunk_lens is not None:
        return jnp.arange(t)[None, :] < chunk_lens[:, None]
    if kv_start is not None:
        return q_slots >= kv_start[:, None]
    return jnp.ones((b, t), bool)


def _chosen_logits(cfg: ModelConfig, params, x, last_token_only: bool,
                   gather_positions):
    """The logits tail over the positions a caller wants: all of them
    [B, T, V], or one a row [B, V]."""
    if last_token_only:
        # left-padding puts every sequence's last token at T-1; slice BEFORE
        # the norm+head tail so decode steps never project the full window
        x = x[:, -1:, :]
    elif gather_positions is not None:
        # ragged chunk: each row's last valid token sits at its own index
        x = jnp.take_along_axis(
            x, jnp.clip(gather_positions, 0, x.shape[1] - 1)[:, None, None],
            axis=1)
    logits = logits_tail(cfg, params, x)
    if last_token_only or gather_positions is not None:
        logits = logits[:, 0]
    return logits.astype(jnp.float32)
