"""The paged KV pool addressed in place by (layer, page) (PR 26).

The decoder's layer scan carries the stacked pool ``[L, P, Hkv, page, D]``
whole: a layer's new rows are written into the stack at ``(layer, page, :,
offset)`` (``PagedKVCache.write_layer``, the XLA form, and
ops/pallas/paged_kv_write.py, the kernel) and the ragged attention kernel
reads ``(layer, table[row, page])`` by its page walk's copies.  Each half must
put and find the bytes exactly where the per-layer forms did
(``update_layer`` on ``pool[l]``, the kernel over ``pool[l]``), for both
storage widths.  The kernels run in interpret mode here.
"""

from dataclasses import replace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ipex_llm_tpu.kv import PagedKVCache
from ipex_llm_tpu.models.decoder import decoder_forward
from ipex_llm_tpu.ops.pallas.paged_kv_write import paged_kv_write
from ipex_llm_tpu.ops.pallas.ragged_paged_attention import ragged_paged_sdpa
from tests.test_decoder import rand_params, tiny_cfg

L, P, H, PS, D, MAXP = 3, 10, 2, 16, 8, 3
KVS = ("bf16", "fp8")


def _bytes(a) -> np.ndarray:
    """The array's storage, bit for bit."""
    a = np.asarray(a)
    return a.view({1: np.uint8, 2: np.uint16, 4: np.uint32}[a.itemsize])


def _pool(storage: str, tables, seed: int = 0) -> PagedKVCache:
    """A pool holding random values in every slot, so a write that lands
    in the wrong place or a slot that should have kept its bytes shows."""
    rng = np.random.default_rng(seed)
    c = PagedKVCache.init(L, P, len(tables), MAXP, H, PS, D, storage=storage)
    fill = lambda a: jnp.asarray(                           # noqa: E731
        rng.standard_normal(a.shape), jnp.float32).astype(a.dtype)
    return replace(c, k=fill(c.k), v=fill(c.v),
                   tables=jnp.asarray(tables, jnp.int32))


# -- the read: the ragged kernel over the stack with a layer index -------------

READS = {
    # (chunk width, kv_len, chunk_len) for rows over tables of 3 pages
    "decode": (1, [5, 16, 33, 48], [1, 1, 1, 1]),
    "chunk": (8, [8, 21, 40, 5], [8, 5, 8, 3]),
    "idle": (4, [20, MAXP * PS + 7, 9, MAXP * PS + 7], [4, 0, 1, 0]),
    # the ends of the page walk: a row at its table's capacity, one ending
    # exactly on a page, one of a single token, one idle at the sentinel
    "edges": (1, [MAXP * PS, 2 * PS, 1, MAXP * PS], [1, 1, 1, 0]),
}


@pytest.mark.parametrize("case", sorted(READS))
@pytest.mark.parametrize("kv", KVS)
def test_ragged_kernel_over_stack_equals_over_layer(kv, case):
    c, kv_len, chunk_len = READS[case]
    tables = [[1, 2, 3], [4, 5, 6], [7, 8, 9], [3, -1, -1]]
    cache = _pool(kv, tables)
    q = jnp.asarray(np.random.default_rng(1).standard_normal(
        (len(tables), c, 2 * H, D)), jnp.bfloat16)
    kv_len = jnp.asarray(kv_len, jnp.int32)
    chunk_len = jnp.asarray(chunk_len, jnp.int32)
    for layer in range(L):
        want = ragged_paged_sdpa(q, cache.k[layer], cache.v[layer],
                                 cache.tables, kv_len, chunk_len)
        got = ragged_paged_sdpa(q, cache.k, cache.v, cache.tables, kv_len,
                                chunk_len, layer=jnp.asarray(layer))
        np.testing.assert_array_equal(_bytes(got), _bytes(want))
    idle = np.asarray(chunk_len) == 0
    assert not np.asarray(got, np.float32)[idle].any()      # exact zeros


# -- the write: into the carried stack at (layer, page, :, offset) -------------

WRITES = {
    # (T, tables, pos): what a tick's rows ask of the write
    "decode": (1, [[1, 2, 3], [4, 5, 6], [7, 8, -1]], [0, 17, 31]),
    "cross_page": (5, [[1, 2, 3], [4, 5, 6]], [PS - 2, 2 * PS - 1]),
    # a right-padded chunk whose tail runs past the row's last page: the
    # first 3 tokens are the row's, the pad tail goes to scratch page 0
    "pad_tail": (8, [[1, -1, -1]], [PS - 3]),
    "unallocated": (1, [[1, 2, -1], [4, 5, 6]], [2 * PS + 4, 3]),
    "past_table": (1, [[1, 2, 3], [4, 5, 6]], [MAXP * PS + 5, 9]),
    # two idle rows at the sentinel slot: both land on the same scratch
    # slot, and which of them it keeps is nobody's promise
    "scratch_duplicates": (4, [[1, 2, 3], [4, -1, -1], [7, -1, -1]],
                           [6, MAXP * PS, MAXP * PS]),
}
FORMS = {
    "xla": lambda cache, *a: cache.write_layer(*a),
    "kernel": paged_kv_write,
}


@pytest.mark.parametrize("case", sorted(WRITES))
@pytest.mark.parametrize("form", sorted(FORMS))
@pytest.mark.parametrize("kv", KVS)
def test_write_in_place_equals_update_layer(kv, form, case):
    t, tables, pos = WRITES[case]
    cache = _pool(kv, tables)
    rng = np.random.default_rng(2)
    new_k, new_v = (jnp.asarray(rng.standard_normal((len(tables), t, H, D)),
                                jnp.bfloat16) for _ in range(2))
    pos = jnp.asarray(pos, jnp.int32)
    for layer in (0, L - 1):
        kl, vl = cache.update_layer(cache.k[layer], cache.v[layer], new_k,
                                    new_v, pos)
        want = cache.k.at[layer].set(kl), cache.v.at[layer].set(vl)
        got = FORMS[form](cache, cache.k, cache.v, jnp.asarray(layer),
                          new_k, new_v, pos)
        for g, w in zip(got, want):
            assert g.dtype == w.dtype
            g, w = _bytes(g), _bytes(w)
            if case == "scratch_duplicates":
                g, w = g[:, 1:], w[:, 1:]
            np.testing.assert_array_equal(g, w)
        # the write moved something, and only in this layer
        assert (_bytes(got[0])[layer] != _bytes(cache.k)[layer]).any()


@pytest.mark.parametrize("kv", KVS)
def test_write_kernel_split_over_kv_heads_equals_whole(kv):
    """Under a GSPMD tp mesh the kernel runs once a shard over its kv
    heads of the same blocks: the stacks come back as the unsplit write's."""
    from ipex_llm_tpu.parallel import MeshSpec, make_mesh

    t, tables, pos = WRITES["cross_page"]
    cache = _pool(kv, tables)
    rng = np.random.default_rng(4)
    new_k, new_v = (jnp.asarray(rng.standard_normal((len(tables), t, H, D)),
                                jnp.bfloat16) for _ in range(2))
    args = (cache.k, cache.v, jnp.asarray(1), new_k, new_v,
            jnp.asarray(pos, jnp.int32))
    want = cache.write_layer(*args)
    mesh = make_mesh(MeshSpec(tp=H))
    got = jax.jit(lambda *a: paged_kv_write(cache, *a, mesh=mesh))(*args)
    for g, w in zip(got, want):
        np.testing.assert_array_equal(_bytes(g), _bytes(w))


# -- the scan: a two-stack model addresses the second stack from its offset ----

@pytest.mark.parametrize("kv", KVS)
def test_two_stack_model_writes_layer_offset(kv):
    """A dense-prefix model's two trees scan one after the other over the
    SAME carried stacks; tree two's layer i is layer ``lo + i`` of the
    pool.  Split a plain 3-layer model after its first layer: logits and
    every byte of the pool must be the single stack's."""
    cfg = tiny_cfg(num_layers=L, num_heads=2 * H, num_kv_heads=H, head_dim=D)
    params = rand_params(cfg)
    split = dict(params)
    split["layers_dense"], split["layers"] = (
        jax.tree_util.tree_map(lambda a, s=s: a[s], params["layers"])
        for s in (slice(0, 1), slice(1, L)))
    cfg_split = replace(cfg, moe_layer_start=1)

    tables = [[1, 2, 3], [4, 5, -1]]
    cache = _pool(kv, tables)
    tokens = jnp.asarray(np.random.default_rng(3).integers(0, 97, (2, 6)))
    base = jnp.asarray([0, PS - 2], jnp.int32)
    kw = dict(slot_offsets=base, chunk_lens=jnp.asarray([6, 4], jnp.int32),
              gather_positions=jnp.asarray([5, 3], jnp.int32))
    pos = base[:, None] + jnp.arange(6)[None, :]
    want_logits, want = decoder_forward(cfg, params, tokens, cache, pos, **kw)
    got_logits, got = decoder_forward(cfg_split, split, tokens, cache, pos,
                                      **kw)
    np.testing.assert_array_equal(np.asarray(got_logits),
                                  np.asarray(want_logits))
    for g, w, before in ((got.k, want.k, cache.k), (got.v, want.v, cache.v)):
        np.testing.assert_array_equal(_bytes(g), _bytes(w))
        for layer in range(L):      # every layer took its own rows
            assert (_bytes(g)[layer, 1] != _bytes(before)[layer, 1]).any()
