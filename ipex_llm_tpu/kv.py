"""KV caches.

Reference counterparts: ``DynamicNormalCache`` / ``DynamicFp8Cache`` /
``DynamicCompressCache`` (reference kv.py:33,79,296) and the alloc/append
helpers of models/utils.py:39-75.  The reference grows torch buffers in
KV_ALLOC_BLOCK_LENGTH=256 chunks because eager PyTorch allows dynamic shapes;
under XLA every shape must be static, so the TPU-native design is:

- one pre-allocated ring of shape ``[L, B, Hkv, S_max, D]`` per k/v —
  head-major so each head's ``[S, D]`` plane is contiguous, which is both
  the DMA-friendly stream for the decode attention kernel (Mosaic requires
  the last two block dims be the tile) and a free reshape for the flash
  prefill kernel's ``[B·H, S, D]`` view,
- an integer ``length`` scalar tracking the filled prefix,
- updates via ``lax.dynamic_update_slice`` inside the jitted step,
- capacity chosen by the generate loop from bucketed prompt+max_new lengths
  (re-jit only when the bucket changes, like the reference re-allocs).

``Fp8KVCache`` stores e5m2 codes (uint8) — the same format the reference's
fp8 cache uses (models/utils.py:102-192) — halving KV HBM traffic; dequant
happens next to the attention op (in-kernel for the Pallas path).
"""

from __future__ import annotations

from dataclasses import dataclass, replace
from typing import Any

import jax
import jax.numpy as jnp

# KV storage formats: the engine axis this module exposes.  "bf16" is the
# full-width default; "fp8" stores e5m2 codes (the reference DynamicFp8Cache
# format) — half the bytes per slot, so a byte-budgeted paged pool holds
# exactly twice the pages.  Dequant happens next to the attention op (the
# Pallas kernels widen tiles in-kernel; the XLA fallback casts the gathered
# layer once).
KV_STORAGE_DTYPES = {
    "bf16": jnp.bfloat16,
    "fp8": jnp.float8_e5m2,
}


def kv_storage_dtype(storage: str):
    """Storage-format name -> pool dtype; raises listing the valid names."""
    try:
        return KV_STORAGE_DTYPES[storage]
    except KeyError:
        raise ValueError(
            f"unknown kv storage {storage!r}: valid storages are "
            f"{sorted(KV_STORAGE_DTYPES)}") from None


def paged_page_bytes(n_layers: int, n_kv_heads: int, page_size: int,
                     head_dim: int, v_head_dim: int | None = None,
                     storage: str = "bf16") -> int:
    """Bytes ONE page occupies across all layers and both k/v pools — the
    unit the serving engine's ``kv_pool_bytes`` budget divides by (so the
    page count, and with it effective batch capacity, follows the storage
    width: fp8 => 2x the pages of bf16 at the same byte budget)."""
    vd = v_head_dim if v_head_dim is not None else head_dim
    itemsize = jnp.dtype(kv_storage_dtype(storage)).itemsize
    return n_layers * n_kv_heads * page_size * (head_dim + vd) * itemsize


def pool_geometry(cfg, storage: str = "bf16") -> tuple[int, int, int]:
    """What ONE token holds in a layer of the paged pool, by the layer's
    kind: (heads, width of the ``k`` pool's row, width of the ``v``
    pool's).  An attention layer holds K and V a kv head.  A latent-attention
    (MLA) layer holds the LATENT: one row a token shared by every head, the
    normalised compressed KV ``c`` (``kv_lora_rank`` wide, the ``k`` pool)
    and the rotated shared key ``k_r`` (``qk_rope_head_dim``, the ``v``
    pool): the absorbed walk's key is the two side by side and its value is
    ``c`` (ops/pallas/ragged_paged_attention.py); the per-head K and V are
    never stored.  bf16 only: the latent is already the compressed form, and
    an e5m2 ``c`` feeds every head's keys AND values.

    A latent-attention model with a learned INDEXER (``cfg.index_topk``)
    holds a third kind of page, the indexer's key, and its walk reads chosen
    TOKENS, not whole pages, so its latent is laid out again: the ``k`` pool's
    row is the whole key a token, ``[c ; k_r]`` padded to whole lanes
    (:func:`latent_row_width`; one contiguous row a token, no strided column
    of a token-minor page to fetch), and the ``v`` pool holds the INDEX KEY
    (``index_head_dim`` wide), for the ``cfg.index_layers`` layers whose
    indexer is "full" only.  Both lie under the one block table, so a page
    id allocates, frees, shares and moves a row's latent and its index keys
    together."""
    if not cfg.is_mla:
        return cfg.num_kv_heads, cfg.head_dim, cfg.v_dim
    if storage != "bf16":
        kv_storage_dtype(storage)        # an unknown name raises its own way
        kind = ("latent and index-key pages are" if cfg.index_topk
                else "latent page is")
        raise ValueError(
            f"kv storage {storage!r} is not supported for a latent-attention "
            f"(MLA) pool ({cfg.model_type}): the {kind} bf16 only")
    if cfg.index_topk:
        return 1, latent_row_width(cfg), cfg.index_head_dim
    return 1, cfg.kv_lora_rank, cfg.qk_rope_head_dim


def latent_row_width(cfg) -> int:
    """Width of a sparse latent pool's row: ``[c ; k_r]`` and zeros up to a
    whole number of 128-lane tiles (the device pads the minor axis to that
    anyway; here the bytes are counted)."""
    return -(-(cfg.kv_lora_rank + cfg.qk_rope_head_dim) // 128) * 128


def model_page_bytes(cfg, page_size: int, storage: str = "bf16") -> int:
    """Bytes of one page of ``cfg``'s pool across its layers
    (:func:`pool_geometry`): :func:`paged_page_bytes`, or for a sparse
    latent pool its two kinds of page by their own layer counts."""
    heads, kd, vd = pool_geometry(cfg, storage)
    if cfg.is_mla and cfg.index_topk:
        return (paged_page_bytes(cfg.kv_layers, 1, page_size, kd,
                                 v_head_dim=0, storage=storage)
                + index_page_bytes(cfg, page_size, storage))
    return paged_page_bytes(cfg.kv_layers, heads, page_size, kd,
                            v_head_dim=vd, storage=storage)


def index_page_bytes(cfg, page_size: int, storage: str = "bf16") -> int:
    """Bytes of one page's INDEX KEYS across the layers that hold them (0
    for a model without an indexer)."""
    if not cfg.index_topk:
        return 0
    return paged_page_bytes(cfg.index_layers, 1, page_size // cfg.index_kpool,
                            cfg.index_head_dim, v_head_dim=0,
                            storage=storage)


@jax.tree_util.register_pytree_node_class
@dataclass
class KVCache:
    """Static-shape stacked-layer KV cache (the DynamicNormalCache peer)."""

    k: jnp.ndarray  # [L, B, Hkv, S_max, D] storage dtype (bf16)
    v: jnp.ndarray
    length: jnp.ndarray  # scalar int32: filled prefix length

    storage: str = "bf16"  # static: bf16 | fp8
    # recurrent state beside the KV (:func:`recurrent_state`), one slot a
    # batch row; None for a model whose layers all attend
    state: Any = None

    def tree_flatten(self):
        return (self.k, self.v, self.length, self.state), (self.storage,)

    @classmethod
    def tree_unflatten(cls, aux, children):
        k, v, length, state = children
        return cls(k, v, length, storage=aux[0], state=state)

    # -- construction -------------------------------------------------------

    @classmethod
    def init(cls, n_layers: int, batch: int, max_len: int, n_kv_heads: int,
             head_dim: int, dtype=jnp.bfloat16, v_head_dim: int | None = None):
        vd = v_head_dim if v_head_dim is not None else head_dim
        return cls(
            k=jnp.zeros((n_layers, batch, n_kv_heads, max_len, head_dim), dtype),
            v=jnp.zeros((n_layers, batch, n_kv_heads, max_len, vd), dtype),
            length=jnp.zeros((), jnp.int32),
            storage="bf16",
        )

    @property
    def max_len(self) -> int:
        return self.k.shape[3]

    # -- per-layer access (used inside the layer scan) ----------------------

    def encode(self, x: jnp.ndarray) -> jnp.ndarray:
        return x.astype(self.k.dtype)

    def decode_layer(self, kl: jnp.ndarray, compute_dtype=jnp.bfloat16) -> jnp.ndarray:
        return kl.astype(compute_dtype)

    @jax.named_scope("layer/kv_write")
    def update_layer(self, kl: jnp.ndarray, vl: jnp.ndarray,
                     new_k: jnp.ndarray, new_v: jnp.ndarray, pos: jnp.ndarray):
        """Write new_k/new_v [B, T, H, D] into layer slices [B, H, S, D] at
        slot offset pos.

        ``pos`` scalar: one uniform slot offset for the whole batch (the
        generate loop's invariant).  ``pos`` [B]: per-row offsets (the
        continuous-batching engine, where rows decode at different lengths).
        """
        new_k = self.encode(new_k).transpose(0, 2, 1, 3)   # [B, H, T, D]
        new_v = self.encode(new_v).transpose(0, 2, 1, 3)
        if getattr(pos, "ndim", 0) == 1:
            write = jax.vmap(
                lambda buf, new, p: jax.lax.dynamic_update_slice(
                    buf, new, (0, p, 0)
                )
            )
            return write(kl, new_k, pos), write(vl, new_v, pos)
        kl = jax.lax.dynamic_update_slice(kl, new_k, (0, 0, pos, 0))
        vl = jax.lax.dynamic_update_slice(vl, new_v, (0, 0, pos, 0))
        return kl, vl

    def advanced(self, n: int | jnp.ndarray) -> "KVCache":
        return replace(self, length=self.length + n)


@jax.tree_util.register_pytree_node_class
@dataclass
class Fp8KVCache(KVCache):
    """fp8(e5m2) KV storage (DynamicFp8Cache peer, reference kv.py:33)."""

    @classmethod
    def init(cls, n_layers: int, batch: int, max_len: int, n_kv_heads: int,
             head_dim: int, dtype=jnp.bfloat16, v_head_dim: int | None = None):
        vd = v_head_dim if v_head_dim is not None else head_dim
        return cls(
            k=jnp.zeros((n_layers, batch, n_kv_heads, max_len, head_dim),
                        jnp.float8_e5m2),
            v=jnp.zeros((n_layers, batch, n_kv_heads, max_len, vd),
                        jnp.float8_e5m2),
            length=jnp.zeros((), jnp.int32),
            storage="fp8",
        )

    def encode(self, x: jnp.ndarray) -> jnp.ndarray:
        return x.astype(jnp.float8_e5m2)

    def decode_layer(self, kl: jnp.ndarray, compute_dtype=jnp.bfloat16) -> jnp.ndarray:
        return kl.astype(compute_dtype)


@jax.tree_util.register_pytree_node_class
@dataclass
class PagedKVCache:
    """Block-table KV over a shared page pool (the vLLM paged-KV peer).

    The reference delegates this axis to vLLM's PagedAttention (SURVEY §2.1
    vllm/, 4,488 LoC); the TPU-native form keeps every shape static:

    - ONE pool per k/v of shape ``[L, P, Hkv, page, D]`` shared by all rows,
    - a per-row block table ``[R, maxP]`` of page ids (-1 = unallocated),
    - the pool is addressed IN PLACE by ``(layer, page)``: the decoder's
      layer scan carries the two stacks whole (never a per-layer slice) and
      hands every layer its index, so a tick moves the bytes it writes and
      the pages it reads and nothing else,
    - writes (:meth:`write_layer`) scatter into ``(layer, table[r,
      slot//page], :, slot % page)`` of the carried stack,
    - reads go through the ragged kernel's page walk, one copy of
      ``pool[layer, table[r, trip]]`` a live page and none for a dead one
      (ops/pallas/ragged_paged_attention.py); the XLA fallback
      (:meth:`gather_layer`) gathers the row's pages of that layer into the
      head-major ``[R, H, S, D]`` view; invalid tail pages are masked by
      ``kv_len`` exactly like dense-cache slack.

    Page allocation, refcounts, and prefix sharing are host-side concerns
    (serving/engine.py PageAllocator) — the device object is pure data.

    What a page holds follows the layers' KIND (:func:`pool_geometry`,
    :meth:`for_model`): K and V a kv head, or a latent-attention model's
    LATENT (``latent``, below).  Everything that moves whole pages (prefix
    reuse, spill, gather/scatter) is the same for both.
    """

    k: jnp.ndarray       # [L, P, Hkv, page, D]
    v: jnp.ndarray       # [L, P, Hkv, page, Dv]
    tables: jnp.ndarray  # [R, maxP] int32 page ids, -1 = unallocated
    length: jnp.ndarray  # scalar int32 (engines drive per-row slot_offsets)

    storage: str = "bf16"
    # a LATENT pool (:func:`pool_geometry`; a latent-attention model's):
    # ``k`` [L, P, 1, page, rank] holds the compressed KV ``c`` a token,
    # laid out like K of one head, and ``v`` [L, P, 1, rope, page] the
    # shared rotated key TOKEN-MINOR: a page of it is ``[rope, page]``, which
    # the absorbed walk multiplies as it lies and whose copy is whole tiles
    # (a ``[page, 64]`` page is padded to 128 lanes on the chip and cannot
    # be cut out of its stack by a copy)
    latent: bool = False
    # a SPARSE latent pool (a latent-attention model with an indexer):
    # ``k`` [L, P, 1, page, row] holds the whole key a token, ``[c ; k_r ;
    # 0]`` (:func:`latent_row_width`), and ``v`` [full layers, P, 1, page,
    # index dim] the indexer's key of the layers whose indexer is "full"
    sparse: bool = False
    # the second kind of serving state, beside the pool in the SAME object:
    # a hybrid model's recurrent state (:func:`recurrent_state`), addressed
    # by ROW and not through the block table.  Carried through the tick like
    # the pool (each layer's slots rewritten in place, never copied whole).
    # None for a model whose layers all attend: no leaf, the same program
    state: Any = None

    def tree_flatten(self):
        return ((self.k, self.v, self.tables, self.length, self.state),
                (self.storage, self.latent, self.sparse))

    @classmethod
    def tree_unflatten(cls, aux, children):
        k, v, tables, length, state = children
        return cls(k, v, tables, length, storage=aux[0], latent=aux[1],
                   sparse=aux[2], state=state)

    @classmethod
    def init(cls, n_layers: int, n_pages: int, n_rows: int, max_pages: int,
             n_kv_heads: int, page_size: int, head_dim: int,
             dtype=None, v_head_dim: int | None = None,
             storage: str | None = None, latent: bool = False,
             index_layers: int = 0, index_kpool: int = 1):
        """``storage`` selects the pool width ("bf16" | "fp8" e5m2); the
        whole access surface (encode/decode_layer/update/gather) keys off
        ``self.k.dtype``, so one class serves both formats — the serving
        engine's Fp8 pool is this init with ``storage="fp8"``.  An
        explicit ``dtype`` must itself be a storage format: with
        ``storage=None`` (default) the tag is derived from it, and a
        contradictory explicit pair raises — ``self.storage`` can never
        lie about what the pool holds."""
        vd = v_head_dim if v_head_dim is not None else head_dim
        if storage is None:
            if dtype is None:
                storage, dtype = "bf16", jnp.bfloat16
            else:
                match = [n for n, d in KV_STORAGE_DTYPES.items()
                         if jnp.dtype(d) == jnp.dtype(dtype)]
                if not match:
                    raise ValueError(
                        f"dtype {jnp.dtype(dtype).name} is not a kv "
                        f"storage format: valid storages are "
                        f"{sorted(KV_STORAGE_DTYPES)}")
                storage = match[0]
        else:
            storage_dtype = kv_storage_dtype(storage)  # validates the name
            if dtype is None:
                dtype = storage_dtype
            elif jnp.dtype(dtype) != jnp.dtype(storage_dtype):
                raise ValueError(
                    f"dtype {jnp.dtype(dtype).name} contradicts "
                    f"storage {storage!r} ({jnp.dtype(storage_dtype).name})")
        # ``index_layers``: a sparse latent pool, whose ``v`` holds the
        # index key of that many layers, token-major (a key a block of
        # ``index_kpool`` tokens where they are pooled)
        if page_size % index_kpool:
            raise ValueError(f"page_size {page_size} is not a multiple of "
                             f"index_kpool {index_kpool}")
        v_page = ((vd, page_size) if latent and not index_layers
                  else (page_size // index_kpool, vd))
        return cls(
            k=jnp.zeros((n_layers, n_pages, n_kv_heads, page_size, head_dim),
                        dtype),
            v=jnp.zeros((index_layers or n_layers, n_pages, n_kv_heads,
                         *v_page), dtype),
            tables=jnp.full((n_rows, max_pages), -1, jnp.int32),
            length=jnp.zeros((), jnp.int32),
            storage=storage,
            latent=latent,
            sparse=index_layers > 0,
        )

    @classmethod
    def for_model(cls, cfg, n_pages: int, n_rows: int, max_pages: int,
                  page_size: int, storage: str = "bf16"):
        """The pool of ``cfg``, its page shaped by the layers' kind
        (:func:`pool_geometry`): K and V a kv head, or the latent."""
        heads, kd, vd = pool_geometry(cfg, storage)
        return cls.init(cfg.kv_layers, n_pages, n_rows, max_pages, heads,
                        page_size, kd, v_head_dim=vd, storage=storage,
                        latent=cfg.is_mla,
                        index_layers=cfg.index_layers if cfg.is_mla else 0,
                        index_kpool=cfg.index_kpool)

    @property
    def page_size(self) -> int:
        return self.k.shape[3]

    @property
    def max_len(self) -> int:
        return self.tables.shape[1] * self.page_size

    @property
    def page_bytes(self) -> int:
        """Bytes one page occupies across all layers and both pools (the
        byte-budget unit the engine sizes ``kv_pool_bytes`` with — one
        formula, :func:`paged_page_bytes`; init guarantees the storage
        tag matches the pool dtypes)."""
        l, _, h, ps, d = self.k.shape
        if self.sparse:      # two kinds of page, each by its own layers
            return (self.k.nbytes + self.v.nbytes) // self.k.shape[1]
        return paged_page_bytes(
            l, h, ps, d, v_head_dim=self.v.shape[3 if self.latent else 4],
            storage=self.storage)

    @property
    def pool_bytes(self) -> int:
        """Total k+v pool footprint in bytes."""
        return self.page_bytes * self.k.shape[1]

    def encode(self, x: jnp.ndarray) -> jnp.ndarray:
        return x.astype(self.k.dtype)

    def decode_layer(self, kl: jnp.ndarray, compute_dtype=jnp.bfloat16) -> jnp.ndarray:
        return kl.astype(compute_dtype)

    def with_tables(self, tables: jnp.ndarray) -> "PagedKVCache":
        """This pool with a different row->page ``tables`` view (pure-data
        replace).  The serving engine's device-resident-state contract
        hangs off this: tables are swapped in ONLY at epoch boundaries
        (admission / prefill / finish / page allocation); between epochs
        the fused decode horizon carries the same device array forward, so
        steady-state decode re-uploads nothing."""
        return replace(self, tables=tables)

    def route_pages(self, page_idx: jnp.ndarray) -> jnp.ndarray:
        """Page indexes [B, N] of every row -> pool pages through the block
        table.  Page 0 is the engine's scratch page (never allocated to a
        row): an index past the table width (the right-padded prefill
        tail) or an unallocated one lands there instead of corrupting
        live pages."""
        maxp = self.tables.shape[1]
        pages = self.tables[jnp.arange(page_idx.shape[0])[:, None],
                            jnp.clip(page_idx, 0, maxp - 1)]
        return jnp.where((page_idx < maxp) & (pages >= 0), pages, 0)

    def _route(self, b: int, t: int, pos: jnp.ndarray):
        """(pages, offs) [B, T]: where tokens ``pos + [0, T)`` of every row
        land."""
        if getattr(pos, "ndim", 0) == 0:
            pos = jnp.broadcast_to(pos, (b,))
        slots = pos[:, None] + jnp.arange(t)[None, :]           # [B, T]
        ps = self.page_size
        return self.route_pages(slots // ps), slots % ps

    @jax.named_scope("layer/kv_write")
    def update_layer(self, kl: jnp.ndarray, vl: jnp.ndarray,
                     new_k: jnp.ndarray, new_v: jnp.ndarray, pos: jnp.ndarray):
        """Scatter new_k/new_v [B, T, H, D] into ONE pool layer [P, H,
        page, D] through the block table at per-row slot offsets ``pos``
        [B].  The per-layer form: what callers holding a layer slice use,
        and the reference :meth:`write_layer` is tested against."""
        pages, offs = self._route(*new_k.shape[:2], pos)
        kl = kl.at[pages, :, offs].set(self.encode(new_k))
        if self.latent:      # token-minor: [P, 1, rope, page]
            return kl, vl.at[pages, 0, :, offs].set(
                self.encode(new_v[:, :, 0]))
        vl = vl.at[pages, :, offs].set(self.encode(new_v))
        return kl, vl

    def write_layer(self, k: jnp.ndarray, v: jnp.ndarray, layer,
                    new_k: jnp.ndarray, new_v: jnp.ndarray, pos: jnp.ndarray):
        """:meth:`update_layer` addressed in place: write new_k/new_v
        [B, T, H, D] into the WHOLE stacks ``k``/``v`` [L, P, H, page, D]
        at ``(layer, page, :, offset)`` and return the stacks.  The same
        bytes in the same places as the per-layer form (storage-dtype
        encode, pad tail and unallocated slots to scratch page 0), but
        no layer is sliced out or written back: the decoder's scan
        carries the stacks, so the scatter updates the carried buffer."""
        pages, offs = self._route(*new_k.shape[:2], pos)
        k = k.at[layer, pages, :, offs].set(self.encode(new_k))
        if self.latent:
            return k, v.at[layer, pages, 0, :, offs].set(
                self.encode(new_v[:, :, 0]))
        v = v.at[layer, pages, :, offs].set(self.encode(new_v))
        return k, v

    def write_pool(self, pool: jnp.ndarray, layer, new: jnp.ndarray,
                   pos: jnp.ndarray) -> jnp.ndarray:
        """:meth:`write_layer` for ONE token-major pool ``[L, P, H, page,
        D]``: new [B, T, H, D] at ``(layer, page, :, offset)``.  A sparse
        latent pool's two kinds of page are written apart: a latent row
        every layer, an index key where the layer's indexer is full."""
        pages, offs = self._route(*new.shape[:2], pos)
        return pool.at[layer, pages, :, offs].set(self.encode(new))

    def gather_pages(self, page_ids) -> tuple[jnp.ndarray, jnp.ndarray]:
        """Pull whole pages out of the pool: ``page_ids`` [N] ->
        (k [L, N, Hkv, page, D], v [L, N, Hkv, page, Dv]) in the pool's
        own storage dtype — the export half of the transportable-KV
        surface (host spill tier + disaggregated prefill/decode handoff,
        serving/pagestore.py / serving/kv_transport.py).  Epoch-boundary
        work by contract: callers gather at admission/eviction/finish
        epochs, never inside the fused tick (JP106)."""
        ids = jnp.asarray(page_ids, jnp.int32)
        return self.k[:, ids], self.v[:, ids]

    def scatter_pages(self, page_ids, k_pages: jnp.ndarray,
                      v_pages: jnp.ndarray) -> "PagedKVCache":
        """Write whole pages back into the pool (the import half):
        ``page_ids`` [N], ``k_pages``/``v_pages`` shaped as
        :meth:`gather_pages` returns.  Values are cast to the pool dtype
        — a same-storage round trip is byte-identical (the spill tier's
        swap-in contract); a widening/narrowing import (e5m2 wire onto a
        bf16 pool) goes through the ordinary storage cast."""
        ids = jnp.asarray(page_ids, jnp.int32)
        return replace(
            self,
            k=self.k.at[:, ids].set(k_pages.astype(self.k.dtype)),
            v=self.v.at[:, ids].set(v_pages.astype(self.v.dtype)),
        )

    @jax.named_scope("layer/kv_read")
    def gather_layer(self, kl: jnp.ndarray, layer=None,
                     token_minor: bool = False) -> jnp.ndarray:
        """The rows' pages as head-major rows [R, H, maxP*page, D] (the raw
        layout cached_sdpa's decode path consumes): from a pool layer
        [P, H, page, D], or — ``layer`` given — from the whole stack
        [L, P, H, page, D] by ``(layer, page)``, one gather with no
        whole-layer slice in between (the XLA fallback of the in-place
        addressing; no benchmark cell runs it).  ``token_minor``: ``kl`` is
        a latent pool's ``v``."""
        r, maxp = self.tables.shape
        t = jnp.clip(self.tables, 0, kl.shape[-4] - 1)
        g = kl[t] if layer is None else kl[layer, t]   # [R, maxP, H, page, D]
        if token_minor:      # a latent pool's ``v``: [.., rope, page]
            g = g.transpose(0, 1, 2, 4, 3)
        g = g.transpose(0, 2, 1, 3, 4)
        return g.reshape(r, g.shape[1], maxp * g.shape[3], g.shape[4])

    def advanced(self, n):
        return replace(self, length=self.length + n)


# counters a hybrid tick accumulates ON THE DEVICE, in ``state["stats"]``
# (they ride the state, so a rolled-back tick's are dropped with it); the
# engine reads them on the fetch it already makes and publishes them under
# these names with ``_total`` appended
# what a held expert layer counts a call (decoder._held_moe_block): pairs
# routed, pairs that landed on a held expert, experts hit, 1, and the rows
# its expert kernel multiplied (ops/moe.py ``moe_held``: hit experts x the
# block's rows, or live row tiles x the tile where rows are grouped)
MOE_COUNTERS = ("moe_pairs_routed", "moe_pairs_local", "moe_experts_hit",
                "moe_layer_calls", "moe_rows_computed")
STATE_COUNTERS = MOE_COUNTERS + ("ssm_state_resets",)
# and what a model with an indexer counts besides, over its attending
# queries: index keys scored (full layers), positions a dense walk would
# read and positions read (every layer), layer calls by indexer type
DSA_COUNTERS = ("dsa_keys_scored", "dsa_tokens_visible",
                "dsa_tokens_selected", "dsa_full_layer_calls",
                "dsa_shared_layer_calls")
# (where the index keys are pooled, ``dsa_keys_scored`` counts BLOCKS scored
# and ``dsa_tokens_selected`` the positions attended: the chosen blocks'
# and the always-attended tail's)
# and what a model with Kimi Delta Attention layers counts (ops/kda.py):
# tokens through the chunked form and rows stepped, summed over its layers
KDA_COUNTERS = ("kda_chunk_tokens", "kda_step_rows")


def state_counters(cfg) -> tuple[str, ...]:
    """The device counters ``cfg``'s state carries, in their order (the
    indexer's last: models/decoder.py adds them from the end)."""
    return (STATE_COUNTERS + (KDA_COUNTERS if cfg.kda_layers else ())
            + (DSA_COUNTERS if cfg.index_topk else ()))


def recurrent_state(cfg, rows: int) -> dict | None:
    """The per-ROW state ``cfg`` carries beside its paged pool for ``rows``
    rows, zeroed, by what its layers are:

    - Mamba-2 blocks (``cfg.ssm_layers``): ``h`` [M blocks, rows, H, P, N]
      float32 and ``conv`` [M blocks, rows, K-1, conv width] bf16 (the last
      K-1 conv inputs);
    - Kimi Delta Attention layers (``cfg.kda_layers``; ops/kda.py):
      ``kda_s`` [KDA layers, rows, H, D, D] float32 (the delta-rule state,
      keys x values) and ``kda_conv`` [KDA layers, rows, K-1, 3 H D] bf16
      (the conv tails of q, k and v side by side);
    - an indexer over POOLED keys (``cfg.index_kpool`` > 1): ``ix_tail``
      [full layers, rows, kpool-1, index dim] bf16, the index keys of a
      row's incomplete block (how many are live follows from the row's
      length);
    - always the ``stats`` counters (:func:`state_counters`).

    A model with none of the three whose expert layers hold a SHARE of
    their experts (``cfg.moe_experts_held``) carries the counters alone:
    they ride the state so that a rolled-back tick's are dropped with it.
    None where the model has nothing to carry."""
    state = {}
    if cfg.ssm_layers:
        state["h"] = jnp.zeros(
            (cfg.ssm_layers, rows, cfg.ssm_num_heads, cfg.ssm_head_dim,
             cfg.ssm_state_size), jnp.float32)
        state["conv"] = jnp.zeros(
            (cfg.ssm_layers, rows, cfg.ssm_conv_kernel - 1,
             cfg.ssm_conv_dim), jnp.bfloat16)
    if cfg.kda_layers:
        heads, d = cfg.kda_num_heads, cfg.kda_head_dim
        state["kda_s"] = jnp.zeros((cfg.kda_layers, rows, heads, d, d),
                                   jnp.float32)
        state["kda_conv"] = jnp.zeros(
            (cfg.kda_layers, rows, cfg.kda_conv_kernel - 1, 3 * heads * d),
            jnp.bfloat16)
    if cfg.index_topk and cfg.index_kpool > 1:
        state["ix_tail"] = jnp.zeros(
            (cfg.index_layers, rows, cfg.index_kpool - 1,
             cfg.index_head_dim), jnp.bfloat16)
    if not state and not cfg.moe_experts_held:
        return None
    state["stats"] = jnp.zeros((len(state_counters(cfg)),), jnp.int32)
    return state


def state_leaves(state) -> dict:
    """{leaf: [shape, dtype]} of a cache's recurrent state, for /health."""
    return {name: [list(x.shape), str(x.dtype)]
            for name, x in sorted((state or {}).items())}


def with_recurrent_state(cache, cfg, rows: int):
    """``cache`` with ``cfg``'s zeroed recurrent state for ``rows`` rows as
    its ``state`` (the cache itself for a model without one)."""
    state = recurrent_state(cfg, rows)
    return cache if state is None else replace(cache, state=state)


def state_bytes(state) -> int:
    """Bytes of a cache's recurrent state (0 without one)."""
    return sum(x.nbytes for x in jax.tree_util.tree_leaves(state))


# cache-kind registry: name -> constructor.  Dense kinds take the KVCache
# init signature; paged kinds take PagedKVCache.init's (the serving pool).
# The compress/SnapKV variant lives in ipex_llm_tpu.compresskv.
CACHE_KINDS = {
    "normal": KVCache.init,
    "fp8": Fp8KVCache.init,
    "paged": PagedKVCache.init,
    "paged_fp8": lambda *a, **kw: PagedKVCache.init(*a, storage="fp8", **kw),
}


def make_cache(kind: str, *args: Any, **kwargs: Any) -> KVCache:
    """kind: 'normal' | 'fp8' (dense) | 'paged' | 'paged_fp8' (pool)."""
    try:
        ctor = CACHE_KINDS[kind]
    except KeyError:
        raise ValueError(
            f"unknown kv cache kind {kind!r}: valid kinds are "
            f"{sorted(CACHE_KINDS)}") from None
    return ctor(*args, **kwargs)


def use_quantize_kv_cache() -> bool:
    """Opt-in gate for fp8 KV (reference models/utils.py:77).

    Quantized KV is never enabled silently — e5m2 storage costs generation
    quality, so it only turns on via IPEX_LLM_TPU_QUANTIZE_KV_CACHE=1 (or the
    reference's IPEX_LLM_QUANTIZE_KV_CACHE), matching the reference's explicit
    env/device gating rather than a blanket GQA heuristic.
    """
    import os

    flag = os.environ.get("IPEX_LLM_TPU_QUANTIZE_KV_CACHE",
                          os.environ.get("IPEX_LLM_QUANTIZE_KV_CACHE", ""))
    return flag == "1"
