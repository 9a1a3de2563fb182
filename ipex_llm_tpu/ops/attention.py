"""Scaled dot-product attention (unified SDPA).

Reference counterpart: the single entry point
``models/common.py:219-306 scaled_dot_product_attention`` dispatching to
``xe_addons.sdp / sdp_causal / sdp_non_causal / sdp_fp8*`` (§2.3).  Here one
jnp reference implementation covers causal/non-causal, GQA, sliding window,
and Gemma-style logit softcapping; the Pallas flash kernel
(ops/pallas/flash_attention.py) takes over on TPU for the long-sequence
prefill path.  All masking is static-shape: the KV buffer has a fixed
``S_max`` and validity is derived from integer lengths, which keeps every
shape XLA-static (SURVEY.md §7 hard part (b)).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from ipex_llm_tpu.ops.pallas._compat import heads_shardable

NEG_INF = -1e30


def _repeat_kv(kv: jnp.ndarray, n_rep: int) -> jnp.ndarray:
    """[B, S, Hkv, D] -> [B, S, Hkv*n_rep, D] (GQA head broadcast)."""
    if n_rep == 1:
        return kv
    b, s, h, d = kv.shape
    return jnp.broadcast_to(kv[:, :, :, None, :], (b, s, h, n_rep, d)).reshape(
        b, s, h * n_rep, d
    )


def sdpa_reference(
    q: jnp.ndarray,          # [B, T, Hq, D]
    k: jnp.ndarray,          # [B, S, Hkv, D]
    v: jnp.ndarray,          # [B, S, Hkv, Dv]
    *,
    scale: float | None = None,
    causal: bool = True,
    q_positions: jnp.ndarray | None = None,  # [B, T] absolute slot positions
    kv_len: jnp.ndarray | None = None,       # [B] valid cache length
    kv_start: jnp.ndarray | None = None,     # [B] first valid slot (left pad)
    window: int | None = None,               # sliding-window size (static)
    window_on: jnp.ndarray | bool = True,    # traced per-layer window enable
    softcap: float | None = None,            # gemma2 logit softcapping
    bias: jnp.ndarray | None = None,         # additive mask/bias [B,1|Hq,T,S]
) -> jnp.ndarray:
    """Returns [B, T, Hq, Dv] in q.dtype; softmax in fp32."""
    b, t, hq, d = q.shape
    s = k.shape[1]
    hkv = k.shape[2]
    n_rep = hq // hkv
    k = _repeat_kv(k, n_rep)
    v = _repeat_kv(v, n_rep)
    if scale is None:
        scale = d ** -0.5

    scores = jnp.einsum(
        "bthd,bshd->bhts", q.astype(jnp.float32), k.astype(jnp.float32)
    ) * scale
    if softcap is not None:
        scores = jnp.tanh(scores / softcap) * softcap
    if bias is not None:
        scores = scores + bias.astype(jnp.float32)

    kv_pos = jnp.arange(s)[None, None, None, :]  # [1,1,1,S]
    mask = jnp.ones((b, 1, t, s), dtype=bool)
    if kv_len is not None:
        mask &= kv_pos < kv_len[:, None, None, None]
    if kv_start is not None:
        mask &= kv_pos >= kv_start[:, None, None, None]
    if causal:
        if q_positions is None:
            q_positions = jnp.broadcast_to(jnp.arange(t)[None, :], (b, t))
        qp = q_positions[:, None, :, None]  # [B,1,T,1]
        mask &= kv_pos <= qp
        if window is not None:
            in_window = kv_pos > qp - window
            mask &= in_window | jnp.logical_not(window_on)
    scores = jnp.where(mask, scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhts,bshd->bthd", probs, v.astype(jnp.float32))
    return out.astype(q.dtype)


def sdpa(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    **kwargs,
) -> jnp.ndarray:
    """Backend-dispatching SDPA; see ``sdpa_reference`` for semantics."""
    from ipex_llm_tpu.ops import dispatch

    rm = dispatch.ring_mesh()
    if (
        rm is not None
        and q.shape[1] == k.shape[1]                  # full self-attention
        and q.shape[1] % rm.shape["cp"] == 0
        and kwargs.get("kv_start") is None            # no left padding
        and kwargs.get("bias") is None
    ):
        from ipex_llm_tpu.ops.ring_attention import ring_sdpa

        return ring_sdpa(
            q, k, v, rm, causal=kwargs.get("causal", True),
            scale=kwargs.get("scale"),
            window=kwargs.get("window"),
            window_on=kwargs.get("window_on", True),
            softcap=kwargs.get("softcap"),
        )

    # every route below is decided from the shapes and the mesh before the
    # call: a kernel that raises is an error, never a reason to fall back
    flash_ok = q.shape[1] >= 128 and kwargs.get("bias") is None
    mesh = dispatch.spmd_mesh()
    if (
        flash_ok
        and mesh is not None
        and mesh.shape.get("tp", 1) > 1
        and dispatch.use_pallas_sharded()
        and kwargs.get("causal", True)
        and q.shape[2] % mesh.shape["tp"] == 0
        and k.shape[2] % mesh.shape["tp"] == 0
    ):
        # (kernel modules import pallas, ~1 s: only where a kernel runs)
        from ipex_llm_tpu.ops.pallas import flash_attention

        kw = dict(kwargs)
        kw.pop("causal", None)
        return flash_attention.flash_sdpa_sharded(
            q, k, v, mesh,
            q_positions=kw.pop("q_positions", None),
            kv_len=kw.pop("kv_len", None),
            kv_start=kw.pop("kv_start", None),
            window_on=kw.pop("window_on", True),
            causal=True, **kw,
        )
    if flash_ok and dispatch.use_pallas():
        from ipex_llm_tpu.ops.pallas import flash_attention

        return flash_attention.flash_sdpa(q, k, v, **kwargs)
    return sdpa_reference(q, k, v, **kwargs)


def _decode_kernel_mode(dispatch, hq: int, hkv: int) -> str | None:
    """Which decode-kernel variant the active dispatch state and the head
    counts allow: 'single' (no mesh, Pallas on), 'sharded' (tp mesh whose
    degree the shard_map wrappers can split these heads over), or None
    (jnp/gather path).  One policy for both the paged and the dense decode
    branch of :func:`cached_sdpa`."""
    mesh = dispatch.spmd_mesh()
    if mesh is None:
        return "single" if dispatch.use_pallas() else None
    tp = mesh.shape.get("tp", 1)
    if (tp > 1 and dispatch.use_pallas_sharded()
            and heads_shardable(hq, hkv, tp)):
        return "sharded"
    return None


@jax.named_scope("layer/kv_write")
def paged_kv_write(cache, k, v, layer, new_k, new_v, pos):
    """Write a tick's new K/V [B, T, Hkv, D] into the paged pool's stacks
    ``k``/``v`` [L, P, Hkv, page, D] at ``(layer, page, :, offset)``,
    in place, and return the stacks.

    Chosen by the rule that chooses the paged attention kernel (so pool
    reads and writes change backend together): the
    Pallas kernel (ops/pallas/paged_kv_write.py) where kernels run,
    ``cache.write_layer``'s XLA scatter elsewhere (the CPU; a GSPMD mesh
    the kv heads do not divide over).  Both put the same bytes in the same
    places."""
    from ipex_llm_tpu.ops import dispatch

    hkv = k.shape[2]
    mode = _decode_kernel_mode(dispatch, hkv, hkv)
    if mode is None:
        return cache.write_layer(k, v, layer, new_k, new_v, pos)
    from ipex_llm_tpu.ops.pallas import paged_kv_write as kernel

    return kernel.paged_kv_write(
        cache, k, v, layer, new_k, new_v, pos,
        mesh=dispatch.spmd_mesh() if mode == "sharded" else None)


@jax.named_scope("layer/kv_write")
def paged_pool_write(cache, pool, layer, new, pos):
    """:func:`paged_kv_write` for ONE token-major pool ``[L, P, H, page,
    D]``: new [B, T, H, D] at ``(layer, page, :, offset)``, in place.  A
    sparse latent pool's two kinds of page are written apart (kv.py): a
    latent row every layer, an index key where the layer's indexer is full.
    The same choice of backend."""
    from ipex_llm_tpu.ops import dispatch

    if dispatch.spmd_mesh() is not None or not dispatch.use_pallas():
        return cache.write_pool(pool, layer, new, pos)
    from ipex_llm_tpu.ops.pallas import paged_kv_write as kernel

    return kernel.paged_pool_write(cache, pool, layer, new, pos)


def _latent_walk_xla(q_lat, q_rope, c_rows, r_rows, kv_len, chunk_lens,
                     scale):
    """The absorbed walk as XLA operations: q_lat [B, T, H, rank], q_rope
    [B, T, H, rope] against the rows' gathered latent c_rows [B, S, rank],
    r_rows [B, S, rope]; query t of a row sits at slot ``kv_len - chunk_len
    + t``.  Returns o_lat float32 [B, T, H, rank] (zeros for an idle row)."""
    f32 = jnp.float32
    s = (jnp.einsum("bthc,bsc->bths", q_lat.astype(f32), c_rows.astype(f32))
         + jnp.einsum("bthr,bsr->bths", q_rope.astype(f32),
                      r_rows.astype(f32))) * scale
    t = q_lat.shape[1]
    kpos = jnp.arange(c_rows.shape[1])[None, None, :]             # [1, 1, S]
    qpos = (kv_len - chunk_lens)[:, None, None] + jnp.arange(t)[None, :, None]
    live = ((kpos <= qpos) & (kpos < kv_len[:, None, None])
            & (chunk_lens > 0)[:, None, None])                    # [B, T, S]
    s = jnp.where(live[:, :, None, :], s, NEG_INF)
    p = jnp.where(live[:, :, None, :],
                  jnp.exp(s - jnp.max(s, axis=-1, keepdims=True)), 0.0)
    l = jnp.sum(p, axis=-1, keepdims=True)
    return jnp.einsum("bths,bsc->bthc", p / jnp.where(l > 0.0, l, 1.0),
                      c_rows.astype(f32))


def latent_attention(q_lat, q_rope, c_pool, r_pool, cache, kv_len,
                     chunk_lens, *, scale: float, layer):
    """Latent (MLA) attention off a latent paged pool (kv.py
    ``pool_geometry``), which already holds this call's tokens: the ABSORBED
    form.  q_lat [B, T, H, rank] is the position-free query with ``W_UK``
    folded in, q_rope [B, T, H, rope] the rotated one; every head attends
    the one shared row a token, key ``[c ; k_r]``, value ``c``, causal
    against the row's own history like :func:`cached_sdpa`'s paged branch
    (queries at slots ``[kv_len - chunk_len, kv_len)``; an idle row gives
    zeros).  On the chip the page walk
    (ops/pallas/ragged_paged_attention.py ``latent_paged_sdpa``); elsewhere
    the same arithmetic as XLA operations over the rows' gathered pages.
    Returns the latent-space output [B, T, H, rank] in q's dtype: the caller
    applies ``W_UV``."""
    from ipex_llm_tpu.ops import dispatch

    b, t = q_lat.shape[:2]
    if chunk_lens is None:
        chunk_lens = jnp.full((b,), t, jnp.int32)
    if dispatch.spmd_mesh() is not None:
        raise NotImplementedError(
            "latent (MLA) attention over a paged pool under a mesh: the "
            "latent row is shared by every head and is not sharded")
    if dispatch.use_pallas():
        from ipex_llm_tpu.ops.pallas import ragged_paged_attention as rpa

        return rpa.latent_paged_sdpa(
            q_lat, q_rope, c_pool, r_pool, cache.tables, kv_len, chunk_lens,
            scale=scale, layer=layer)
    return _latent_walk_xla(
        q_lat, q_rope, cache.gather_layer(c_pool, layer)[:, 0],
        cache.gather_layer(r_pool, layer, token_minor=True)[:, 0], kv_len,
        chunk_lens, scale).astype(q_lat.dtype)


# --- learned sparse attention over a sparse latent pool ----------------------
# (kv.py ``pool_geometry``: a latent-attention model with an INDEXER.)  A
# layer whose indexer is "full" scores every cached token of a query
# (:func:`index_scores`), keeps the best ``index_topk`` (:func:`select_topk`)
# and attends over them alone (:func:`sparse_latent_attention`); the layers
# after it that share its selection call the third only.  What a selection IS
# follows the call's shape: a decode row (one query) carries the chosen
# positions, which its walk GATHERS, a token's whole key a contiguous row of
# the pool; a chunk carries a mask over the row's slots, under which its walk
# reads the pages in order, like the absorbed walk of a dense latent pool.
# The chosen SET is the same function of the scores in both: the ``k``
# largest, equal scores to the earlier position.


@jax.named_scope("dsa/score")
def index_scores(q_idx, w_idx, ik_pool, cache, q_slots, kv_len, *, layer,
                 kpool: int = 1):
    """Indexer scores of a call's queries against their rows' cached index
    keys: q_idx [B, T, J, D] (rotated), w_idx [B, T, J] float32 (the head
    weights, scaled), the index-key pool whole with the full layer's index
    ``layer``.  ``I[t, s] = sum_j w[t, j] relu(q[t, j] . k[s])`` in float32
    over bf16 products, the heads added in index order (a reduction would be
    ordered by the block's shape); -inf where query t does not see slot s
    (``s > q_slots[t]`` or past ``kv_len``).  Returns [B, T, S] over the
    table's whole width ``S``.  ``kpool`` > 1: the pool holds one key a
    block of that many positions, ``s`` counts BLOCKS, and query t sees the
    complete blocks that end before it (``s < q_slots[t] // kpool``)."""
    f32 = jnp.float32
    keys = cache.gather_layer(ik_pool, layer)[:, 0]               # [B, S, D]
    b, t, n_j, _ = q_idx.shape
    q_idx = q_idx.astype(keys.dtype)
    if t == 1:
        d = jnp.einsum("bjd,bsd->bjs", q_idx[:, 0], keys,
                       preferred_element_type=f32)
        acc = jnp.zeros((b, keys.shape[1]), f32)
        for j in range(n_j):
            acc = acc + w_idx[:, 0, j, None] * jnp.maximum(d[:, j], 0.0)
        acc = acc[:, None]
    else:
        def head(j, acc):
            qj = jax.lax.dynamic_index_in_dim(q_idx, j, 2, False)
            wj = jax.lax.dynamic_index_in_dim(w_idx, j, 2, False)
            d = jnp.einsum("btd,bsd->bts", qj, keys,
                           preferred_element_type=f32)
            return acc + wj[..., None] * jnp.maximum(d, 0.0)

        acc = jax.lax.fori_loop(
            0, n_j, head, jnp.zeros((b, t, keys.shape[1]), f32))
    kpos = jnp.arange(keys.shape[1])[None, None, :]
    if kpool > 1:
        seen = kpos < (q_slots // kpool)[:, :, None]
    else:
        seen = (kpos <= q_slots[:, :, None]) & (kpos < kv_len[:, None, None])
    # -0.0 and 0.0 are one score
    return jnp.where(seen, jnp.where(acc == 0.0, 0.0, acc), -jnp.inf)


def _kth_largest(u, k: int):
    """The ``k``-th largest of uint32 ``u`` [..., S] along its last axis, by
    32 counting passes (one bit of the answer a pass): exact, and integer
    counts do not depend on how a reduction is ordered."""
    def bit(i, thr):
        cand = thr | jnp.left_shift(jnp.uint32(1), (31 - i).astype(jnp.uint32))
        n = jnp.sum(u >= cand[..., None], axis=-1, dtype=jnp.int32)
        return jnp.where(n >= k, cand, thr)

    return jax.lax.fori_loop(0, 32, bit,
                             jnp.zeros(u.shape[:-1], jnp.uint32))


@jax.named_scope("dsa/select")
def select_topk(scores, k: int):
    """The selection of :func:`index_scores`' output [B, T, S]: a query's
    ``k`` largest scores among the slots it sees (all of them while it sees
    at most ``k``), equal scores to the earlier slot.  A decode call (T = 1)
    gets ``(idx [B, k] int32, ok [B, k])``, the chosen slots and which of
    them are real; a chunk gets the mask [B, T, S].  No sort of the row: a
    threshold by bisection on the scores' bits, then one on the position
    among the scores equal to it."""
    b, t, s = scores.shape
    k = min(k, s)
    if t == 1:
        vals, idx = jax.lax.top_k(scores[:, 0], k)
        return idx.astype(jnp.int32), vals > -jnp.inf
    seen = scores > -jnp.inf
    bits = jax.lax.bitcast_convert_type(scores, jnp.uint32)
    # a monotone map of float32 onto uint32
    u = jnp.where(bits >> 31 == 1, ~bits, bits | jnp.uint32(0x80000000))
    thr = _kth_largest(u, k)[..., None]
    above = u > thr
    tied = (u == thr) & seen
    need = k - jnp.sum(above, axis=-1, dtype=jnp.int32)           # [B, T]
    pos = jnp.arange(s, dtype=jnp.int32)

    def halve(_, lohi):
        lo, hi = lohi
        mid = (lo + hi) // 2
        n = jnp.sum(tied & (pos < mid[..., None]), axis=-1, dtype=jnp.int32)
        enough = n >= need
        return jnp.where(enough, lo, mid + 1), jnp.where(enough, mid, hi)

    # the smallest bound under which ``need`` of the tied slots lie
    bound, _ = jax.lax.fori_loop(
        0, s.bit_length() + 1, halve,
        (jnp.zeros((b, t), jnp.int32), jnp.full((b, t), s, jnp.int32)))
    return seen & (above | (tied & (pos < bound[..., None])))


def _softmax_rows(s, live, rows_v):
    """Masked softmax of float32 scores ``s`` [..., Q, K] times the values
    [..., K, C] (bf16 products, float32 sums); zeros where nothing is live."""
    m = jnp.max(jnp.where(live, s, NEG_INF), axis=-1, keepdims=True)
    p = jnp.where(live, jnp.exp(s - m), 0.0)
    l = jnp.sum(p, axis=-1, keepdims=True)
    o = jnp.einsum("...qk,...kc->...qc", p.astype(rows_v.dtype), rows_v,
                   preferred_element_type=jnp.float32)
    return o / jnp.where(l > 0.0, l, 1.0)


@jax.named_scope("dsa/attend")
def sparse_latent_attention(q_row, pool, cache, sel, chunk_lens, kv_len, *,
                            rank: int, scale: float, layer):
    """Absorbed latent attention over a SELECTION (:func:`select_topk`) off a
    sparse latent pool, which already holds this call's tokens.  q_row [B, T,
    H, W] is the query against a pool row ``[c ; k_r ; 0]`` (``W_UK`` folded
    in, the rotated slice beside it, zeros on the pad lanes); the value is
    the row's first ``rank`` lanes.  A decode call gathers its chosen rows,
    one contiguous row a token, and attends over them: what it reads is
    bounded by the selection, not by the cache's length.  A chunk walks its
    row's pages under the mask (ops/pallas/ragged_paged_attention.py
    ``sparse_paged_sdpa`` on the chip, the same arithmetic over the gathered
    pages elsewhere).  Returns o_lat [B, T, H, rank] in q's dtype."""
    from ipex_llm_tpu.ops import dispatch

    b, t = q_row.shape[:2]
    if dispatch.spmd_mesh() is not None:
        raise NotImplementedError(
            "sparse latent attention under a mesh: neither the latent row "
            "nor the index key is sharded")
    if t == 1:
        idx, ok = sel
        ps = cache.page_size
        pages = cache.route_pages(idx // ps)
        rows = pool[layer, pages, 0, idx % ps]                    # [B, K, W]
        s = jnp.einsum("bhw,bkw->bhk", q_row[:, 0].astype(rows.dtype), rows,
                       preferred_element_type=jnp.float32) * scale
        live = (ok & (chunk_lens > 0)[:, None])[:, None, :]
        return _softmax_rows(s, live, rows[..., :rank])[:, None].astype(
            q_row.dtype)
    if dispatch.use_pallas():
        from ipex_llm_tpu.ops.pallas import ragged_paged_attention as rpa

        return rpa.sparse_paged_sdpa(q_row, pool, cache.tables, sel, kv_len,
                                     chunk_lens, rank=rank, scale=scale,
                                     layer=layer)
    rows = cache.gather_layer(pool, layer)[:, 0]                  # [B, S, W]
    s = jnp.einsum("bthw,bsw->bhts", q_row.astype(rows.dtype), rows,
                   preferred_element_type=jnp.float32) * scale
    o = _softmax_rows(s, sel[:, None], rows[:, None, :, :rank])   # [B,H,T,C]
    return o.transpose(0, 2, 1, 3).astype(q_row.dtype)


def cached_sdpa(
    q: jnp.ndarray,            # [B, T, Hq, D]
    kl: jnp.ndarray,           # [B, Hkv, S, D] raw cache layer (maybe fp8)
    vl: jnp.ndarray,
    cache,
    *,
    compute_dtype=jnp.bfloat16,
    **kwargs,
) -> jnp.ndarray:
    """SDPA over a cache layer in its *storage* layout and dtype.

    Decode steps (T=1) route to the specialized Pallas kernel
    (ops/pallas/decode_attention.py) which reads the head-major cache
    natively — including fp8 tiles dequantized in-kernel, the
    ``xe_addons.sdp_fp8`` equivalent (reference models/common.py:273-286).
    Every other shape casts/permutes the layer once and uses the generic
    :func:`sdpa` dispatch (XLA cancels the permute against the flash
    kernel's own head-major view).
    """
    from ipex_llm_tpu.ops import dispatch

    chunk_lens = kwargs.pop("chunk_lens", None)
    layer = kwargs.pop("layer", None)
    if hasattr(cache, "tables"):
        # paged pool (serving engine; rows right-aligned from slot 0,
        # queries at slots [kv_len - T, kv_len) — the engine's invariant).
        # With ``layer`` (the decoder's scan) kl/vl are the WHOLE stacks
        # [L, P, Hkv, page, D] and that layer of them is read in place,
        # by the kernel's page copies or by the fallback's gather; without,
        # they are one layer [P, Hkv, page, D].
        # The pool arrives in STORAGE dtype: an fp8(e5m2) pool streams its
        # tiles into the Pallas kernels, which widen to bf16 in-kernel (the
        # ``xe_addons.sdp_fp8`` equivalent — HBM reads stay half-width),
        # and the gather fallback gathers the fp8 codes (still half the
        # bytes) before ``decode_layer`` casts once next to the op.
        # The ragged tick rides this same path with a RAGGED right-padded
        # chunk: ``chunk_lens`` [B] names each row's real query count (a
        # decode row has 1, a prefill row up to T, an idle row 0), and the
        # pad tail past a row's last valid token is causally hidden — so
        # ONE kernel program (ops/pallas/ragged_paged_attention.py) serves
        # every row shape in the batch without per-row dispatch.
        if (
            kwargs.get("bias") is None
            and kwargs.get("window") is None
            and kwargs.get("softcap") is None
            and kwargs.get("kv_start") is None
            and kwargs.get("kv_len") is not None
            and q.shape[2] % kl.shape[-3] == 0
        ):
            # read ONLY the row's own pages through the scalar-prefetched
            # block table — no table-width gather
            mode = _decode_kernel_mode(dispatch, q.shape[2], kl.shape[-3])
            if mode is not None:
                from ipex_llm_tpu.ops.pallas import ragged_paged_attention
            if mode == "single":
                return ragged_paged_attention.ragged_paged_sdpa(
                    q, kl, vl, cache.tables, kwargs.get("kv_len"),
                    chunk_lens, scale=kwargs.get("scale"), layer=layer)
            if mode == "sharded":
                # TP serving: per-shard kernel over the kv-head split
                return ragged_paged_attention.ragged_paged_sdpa_sharded(
                    q, kl, vl, cache.tables, kwargs.get("kv_len"),
                    dispatch.spmd_mesh(), chunk_lens,
                    scale=kwargs.get("scale"), layer=layer)
        # fallback: gather the rows' pages into the head-major
        # [B, Hkv, S, D] view; tail pages beyond kv_len are garbage and
        # masked exactly like dense-cache slack (per-row chunk lens are
        # already folded into kv_len by the caller, so the reference mask
        # needs no extra input)
        kl = cache.gather_layer(kl, layer)
        vl = cache.gather_layer(vl, layer)

    t = q.shape[1]
    decode_ok = (
        t == 1
        and kwargs.get("bias") is None
        and dispatch.ring_mesh() is None
        and q.shape[2] % kl.shape[1] == 0
    )
    if decode_ok:
        dk = dict(
            scale=kwargs.get("scale"),
            kv_len=kwargs.get("kv_len"),
            kv_start=kwargs.get("kv_start"),
            window=kwargs.get("window"),
            window_on=kwargs.get("window_on", True),
            softcap=kwargs.get("softcap"),
        )
        mode = _decode_kernel_mode(dispatch, q.shape[2], kl.shape[1])
        if mode is not None:
            from ipex_llm_tpu.ops.pallas import decode_attention
        if mode == "single":
            return decode_attention.decode_sdpa(q, kl, vl, **dk)
        if mode == "sharded":
            return decode_attention.decode_sdpa_sharded(
                q, kl, vl, dispatch.spmd_mesh(), **dk)
    kd = cache.decode_layer(kl, compute_dtype).transpose(0, 2, 1, 3)
    vd = cache.decode_layer(vl, compute_dtype).transpose(0, 2, 1, 3)
    return sdpa(q, kd, vd, **kwargs)


def packed_mha(x_q, x_k, x_v, in_proj, in_proj_b, o, o_b, n_heads: int):
    """torch ``nn.MultiheadAttention`` semantics over a packed [3E, E]
    ``in_proj`` weight (quantized), shared by the Qwen-VL and MiniCPM-V
    towers.  When q/k/v come from the SAME tensor (ViT self-attention) the
    projection runs as ONE GEMM and splits; the cross-attention form pays
    the packed width per distinct input.
    """
    import jax.numpy as jnp

    from ipex_llm_tpu.ops import linear as linear_ops

    b, nq, e = x_q.shape
    if x_q is x_k and x_k is x_v:
        qkv = linear_ops.linear(x_q.astype(jnp.bfloat16), in_proj, in_proj_b)
        q, k, v = qkv[..., :e], qkv[..., e:2 * e], qkv[..., 2 * e:]
    else:
        q = linear_ops.linear(x_q.astype(jnp.bfloat16), in_proj,
                              in_proj_b)[..., :e]
        k = linear_ops.linear(x_k.astype(jnp.bfloat16), in_proj,
                              in_proj_b)[..., e:2 * e]
        v = linear_ops.linear(x_v.astype(jnp.bfloat16), in_proj,
                              in_proj_b)[..., 2 * e:]
    hd = e // n_heads
    attn = sdpa_reference(
        q.reshape(b, nq, n_heads, hd),
        k.reshape(b, k.shape[1], n_heads, hd),
        v.reshape(b, v.shape[1], n_heads, hd),
        causal=False,
    ).reshape(b, nq, e)
    return linear_ops.linear(attn.astype(jnp.bfloat16), o, o_b
                             ).astype(jnp.float32)
