"""fp8 paged KV storage — the serving engine's KV-width axis.

The contracts under test (the fp8-paged-pool PR):

- **mechanism exactness**: a paged fp8 write->gather roundtrip produces
  exactly the direct e5m2 cast chain (``x -> e5m2 -> bf16``) — the paged
  scatter/gather machinery adds no numerics of its own;
- **engine-path bit-identity under fp8**: e5m2 storage is lossy vs bf16,
  but the engine stays bit-identical to ITSELF across tick shapes — the
  row-in-a-wave ≡ row-alone and fused-horizon H8≡H1 suites re-run
  under ``kv_storage="fp8"``;
- **byte-budget capacity**: at a fixed ``kv_pool_bytes``, fp8 storage
  yields exactly 2x the pages of bf16 (half the bytes per slot), visible
  in ``kv_stats()`` and the ``/health`` kv block;
- **fault-domain composition**: a transient fault mid-generation rolls
  back and retries bit-identically over the fp8 pool (checkpoint /
  rollback never touch the storage format);
- **registry**: ``make_cache`` knows the paged kinds and fails loudly,
  listing the valid kinds, on an unknown one;
- **pressure counters**: prefix-cache LRU evictions and allocation-fail
  clamps leave a trace (the capacity symptoms the fp8 pool halves).

Plus a slow-marked quality gate: a >=64-step greedy stream through the
fp8 engine stays self-consistent across horizons, and the dense-chain
fp8 sliding-ppl delta (benchmark/ppl.py) stays bounded.
"""

import json
import os
import sys
import urllib.request

import jax.numpy as jnp
import numpy as np
import pytest

from ipex_llm_tpu.kv import (
    PagedKVCache,
    make_cache,
    paged_page_bytes,
)
from ipex_llm_tpu.serving.engine import (
    EngineConfig,
    Request,
    ServingEngine,
    stream_tokens,
)
from ipex_llm_tpu.serving.faults import FaultInjector, TransientFault
from tests.test_decoder import rand_params, tiny_cfg
from tests.test_serving_mixed import (_assert_rows_do_not_depend_on_batch,
                                      _drive)

RNG = np.random.default_rng(91)

EC = dict(max_rows=4, max_seq_len=256, page_size=32, prefill_bucket=32)


@pytest.fixture(scope="module")
def cfg_params():
    cfg = tiny_cfg(vocab_size=131, hidden_size=48, intermediate_size=96,
                   num_heads=4, num_kv_heads=2, head_dim=12,
                   max_position_embeddings=512)
    return cfg, rand_params(cfg, qtype="bf16")


# -- make_cache registry -----------------------------------------------------

def test_make_cache_paged_kinds():
    args = (2, 6, 3, 4, 2, 8, 4)   # L, P, R, maxP, Hkv, page, D
    c = make_cache("paged", *args)
    assert isinstance(c, PagedKVCache)
    assert c.k.dtype == jnp.bfloat16 and c.storage == "bf16"
    c8 = make_cache("paged_fp8", *args)
    assert isinstance(c8, PagedKVCache)
    assert c8.k.dtype == jnp.float8_e5m2 and c8.v.dtype == jnp.float8_e5m2
    assert c8.storage == "fp8"
    assert c8.page_bytes * 2 == c.page_bytes       # half the bytes per page
    assert c8.tables.shape == (3, 4)


def test_make_cache_unknown_kind_lists_valid():
    with pytest.raises(ValueError, match="valid kinds") as ei:
        make_cache("int3", 1, 1, 1, 1, 1)
    msg = str(ei.value)
    for kind in ("normal", "fp8", "paged", "paged_fp8"):
        assert kind in msg, msg
    assert "int3" in msg


def test_engine_rejects_unknown_storage_and_negative_budget(cfg_params):
    cfg, params = cfg_params
    with pytest.raises(ValueError, match="valid storages"):
        ServingEngine(cfg, params, EngineConfig(kv_storage="int3", **EC))
    with pytest.raises(ValueError, match="kv_pool_bytes"):
        ServingEngine(cfg, params, EngineConfig(kv_pool_bytes=-1, **EC))


def test_engine_refuses_budget_too_small_for_rows(cfg_params):
    """An explicit byte cap the engine cannot honor (fewer pages than
    max_rows + scratch) must raise, never silently overshoot the
    operator's budget."""
    cfg, params = cfg_params
    pb = paged_page_bytes(cfg.num_layers, cfg.num_kv_heads, 32,
                          cfg.head_dim, v_head_dim=cfg.v_dim)
    with pytest.raises(ValueError, match="kv_pool_bytes.*max_rows"):
        ServingEngine(cfg, params,
                      EngineConfig(kv_pool_bytes=3 * pb, **EC))
    # the same budget DOES fit under fp8 (half the bytes per page: 6
    # pages >= max_rows 4 + scratch + 1) — the error message's own advice
    eng = ServingEngine(cfg, params,
                        EngineConfig(kv_pool_bytes=3 * pb,
                                     kv_storage="fp8", **EC))
    assert eng.kv_stats()["pages_total"] == 6


def test_init_dtype_keeps_storage_tag_truthful():
    """An explicit pool dtype must be a storage format: alone it derives
    the tag, a contradictory explicit (dtype, storage) pair raises —
    ``storage`` can never lie about what the pool holds, and
    ``make_cache("paged_fp8", ..., dtype=bf16)`` fails loudly instead of
    silently handing back a full-width pool."""
    args = (1, 4, 2, 2, 2, 8, 4)
    c = PagedKVCache.init(*args, dtype=jnp.float8_e5m2)
    assert c.storage == "fp8" and c.k.dtype == jnp.float8_e5m2
    c = PagedKVCache.init(*args, dtype=jnp.bfloat16)
    assert c.storage == "bf16" and c.k.dtype == jnp.bfloat16
    with pytest.raises(ValueError, match="contradicts"):
        PagedKVCache.init(*args, dtype=jnp.bfloat16, storage="fp8")
    with pytest.raises(ValueError, match="contradicts"):
        make_cache("paged_fp8", *args, dtype=jnp.bfloat16)
    with pytest.raises(ValueError, match="valid storages"):
        PagedKVCache.init(*args, dtype=jnp.float32)


# -- mechanism exactness -----------------------------------------------------

def test_fp8_paged_roundtrip_matches_direct_cast_chain():
    """Writing bf16 values through the fp8 pool's scatter and gathering
    them back must equal the direct ``bf16 -> e5m2 -> bf16`` cast chain:
    the paged machinery stores exactly the e5m2 codes the dense
    Fp8KVCache (reference DynamicFp8Cache) stores."""
    cache = PagedKVCache.init(1, 6, 2, 4, 2, 8, 4, storage="fp8")
    tables = jnp.asarray(np.array([[1, 2, -1, -1], [3, 4, 5, -1]],
                                  np.int32))
    cache = cache.with_tables(tables)
    rng = np.random.default_rng(5)
    new_k = jnp.asarray(rng.standard_normal((2, 10, 2, 4)), jnp.bfloat16)
    new_v = jnp.asarray(rng.standard_normal((2, 10, 2, 4)), jnp.bfloat16)
    kl, vl = cache.update_layer(cache.k[0], cache.v[0], new_k, new_v,
                                jnp.asarray([0, 0], jnp.int32))
    assert kl.dtype == jnp.float8_e5m2
    got_k = cache.gather_layer(kl)     # [R, H, maxP*page, D] e5m2 codes
    got_v = cache.gather_layer(vl)
    assert got_k.dtype == jnp.float8_e5m2
    # the direct cast chain in the cache's head-major layout
    ref_k = new_k.transpose(0, 2, 1, 3).astype(jnp.float8_e5m2)
    ref_v = new_v.transpose(0, 2, 1, 3).astype(jnp.float8_e5m2)
    np.testing.assert_array_equal(
        np.asarray(got_k[:, :, :10].astype(jnp.bfloat16), np.float32),
        np.asarray(ref_k.astype(jnp.bfloat16), np.float32))
    np.testing.assert_array_equal(
        np.asarray(got_v[:, :, :10].astype(jnp.bfloat16), np.float32),
        np.asarray(ref_v.astype(jnp.bfloat16), np.float32))
    # and the decode hook widens losslessly from the stored codes
    np.testing.assert_array_equal(
        np.asarray(cache.decode_layer(got_k), np.float32),
        np.asarray(got_k.astype(jnp.bfloat16), np.float32))


# -- byte-budget capacity ----------------------------------------------------

def test_fixed_pool_bytes_doubles_pages(cfg_params):
    """The acceptance number: same ``kv_pool_bytes``, half the storage
    width, exactly twice the pages — and the engine's pool really is
    e5m2."""
    cfg, params = cfg_params
    pb16 = paged_page_bytes(cfg.num_layers, cfg.num_kv_heads, 32,
                            cfg.head_dim, v_head_dim=cfg.v_dim)
    budget = 40 * pb16
    eng16 = ServingEngine(cfg, params,
                          EngineConfig(kv_pool_bytes=budget, **EC))
    eng8 = ServingEngine(cfg, params,
                         EngineConfig(kv_pool_bytes=budget,
                                      kv_storage="fp8", **EC))
    kv16, kv8 = eng16.kv_stats(), eng8.kv_stats()
    assert kv16["pages_total"] == 40
    assert kv8["pages_total"] == 80          # 2x pages at the same bytes
    assert kv8["page_bytes"] * 2 == kv16["page_bytes"]
    assert kv8["pool_bytes"] == kv16["pool_bytes"] == budget
    assert eng8.cache.k.dtype == jnp.float8_e5m2
    assert eng8.cache.v.dtype == jnp.float8_e5m2
    assert eng16.cache.k.dtype == jnp.bfloat16
    # both device pools cost exactly the budget — fp8 spent its half-width
    # savings on pages, not on a smaller footprint
    assert eng8.cache.pool_bytes == eng16.cache.pool_bytes == budget


# -- engine-path bit-identity under fp8 --------------------------------------

def _wave_specs(cfg):
    """Greedy long row, seeded sampled longer row, greedy short row that
    finishes prefill mid-wave (the mixed suite's wave, re-run on fp8)."""
    p1 = list(RNG.integers(0, cfg.vocab_size, 40))
    p2 = list(RNG.integers(0, cfg.vocab_size, 70))
    p3 = list(RNG.integers(0, cfg.vocab_size, 24))
    return [
        dict(prompt_ids=p1, max_new_tokens=12),
        dict(prompt_ids=p2, max_new_tokens=12, temperature=0.8, top_p=0.9,
             top_k=40, seed=123),
        dict(prompt_ids=p3, max_new_tokens=12),
    ]


def test_a_row_in_a_wave_matches_the_row_alone_fp8(cfg_params):
    """Batch invariance survives the storage change: over an fp8 pool a
    request inside a staggered wave emits the exact token AND logprob
    streams it emits served alone (both lossy vs bf16 in the same way)."""
    cfg, params = cfg_params
    specs = _wave_specs(cfg)
    eng, _ = _assert_rows_do_not_depend_on_batch(
        cfg, params, EngineConfig(kv_storage="fp8", **EC),
        {0: [specs[0]], 1: [specs[1]], 3: [specs[2]]})
    assert eng.metrics["mixed_steps"] > 0
    assert eng.cache.k.dtype == jnp.float8_e5m2


def test_fused_h8_bit_identical_to_h1_fp8(cfg_params):
    """The PR-1 equivalence contract over the quantized pool: H=8 fused
    decode on fp8 storage emits the H=1 fp8 engine's exact streams
    (greedy and seeded sampled)."""
    cfg, params = cfg_params
    p1 = list(RNG.integers(0, cfg.vocab_size, 9))
    p2 = list(RNG.integers(0, cfg.vocab_size, 17))
    specs = [
        dict(prompt_ids=p1, max_new_tokens=16),
        dict(prompt_ids=p2, max_new_tokens=16, temperature=0.8,
             top_p=0.9, top_k=40, seed=123),
    ]

    def run(h):
        sched = {0: [Request(**s) for s in specs]}
        eng = ServingEngine(cfg, params, EngineConfig(
            kv_storage="fp8", decode_horizon=h, **EC))
        streams = _drive(eng, sched)
        return [r for rs in sched.values() for r in rs], streams, eng

    r1, s1, _ = run(1)
    r8, s8, e8 = run(8)
    for a, b in zip(s1, s8):
        assert a == b, (a, b)
    for a, b in zip(r1, r8):
        assert a.finish_reason == b.finish_reason
        np.testing.assert_array_equal(
            np.asarray(a.logprobs, np.float32),
            np.asarray(b.logprobs, np.float32))
    assert e8.metrics["decode_horizon_effective"] == 8
    assert e8.metrics["host_syncs"] < e8.metrics["steps"]


# -- fault-domain composition ------------------------------------------------

def _drive_ticks(eng, reqs, max_ticks=3000):
    """Synchronous loop through the transactional tick (the fault path)."""
    for r in reqs:
        eng.submit(r)
    for _ in range(max_ticks):
        eng._tick()
        if all(r.finish_reason is not None for r in reqs):
            break
    assert all(r.finish_reason is not None for r in reqs)
    return [list(stream_tokens(r, timeout=10)) for r in reqs]


def test_transient_fault_rollback_preserves_fp8_pool(cfg_params):
    """A transient fault mid-tick over the fp8 pool: rollback + retry must
    reproduce the unfaulted fp8 run bit-for-bit, the pool must drain back
    to idle, and the storage format must survive the rollback's full
    epoch re-upload."""
    cfg, params = cfg_params
    prompts = [list(RNG.integers(0, cfg.vocab_size, n)) for n in (40, 70)]

    def wave():
        return [Request(prompt_ids=p, max_new_tokens=8) for p in prompts]

    base_eng = ServingEngine(cfg, params,
                             EngineConfig(kv_storage="fp8",
                                          retry_backoff_s=0.001, **EC))
    base_streams = _drive_ticks(base_eng, wave())

    inj = FaultInjector().inject("decode-dispatch", TransientFault, nth=2)
    eng = ServingEngine(cfg, params,
                        EngineConfig(kv_storage="fp8",
                                     retry_backoff_s=0.001, **EC),
                        fault_injector=inj)
    reqs = wave()
    streams = _drive_ticks(eng, reqs)
    assert inj.fired == 1
    assert eng.metrics["retries"] == 1
    assert streams == base_streams
    assert all(r.finish_reason == "length" for r in reqs)
    # the rollback-forced epoch re-upload kept the e5m2 pool
    assert eng.cache.k.dtype == jnp.float8_e5m2
    assert eng.cache.v.dtype == jnp.float8_e5m2
    # pool idle: only prefix-cached pages hold a ref
    cached = set(eng.alloc.prefix.values())
    for pid in range(1, eng.alloc.n_pages):
        refs = int(eng.alloc.ref[pid])
        assert refs == 0 or (pid in cached and refs == 1), (pid, refs)


# -- pressure counters -------------------------------------------------------

def test_prefix_eviction_and_alloc_clamp_counters(cfg_params):
    """The two previously-invisible pool-pressure events leave a trace:
    LRU-evicting a cached prefix page bumps ``prefix_evictions``, and an
    allocation failure (horizon pre-alloc / admission clamp) bumps
    ``alloc_fail_clamps`` — both surfaced via ``kv_stats()``."""
    cfg, params = cfg_params
    ec = EngineConfig(max_rows=2, max_seq_len=256, page_size=16,
                      pool_pages=8, prefill_bucket=32, decode_horizon=8)
    eng = ServingEngine(cfg, params, ec)
    # serially: each prompt registers full prefix pages at completion;
    # the 7-usable-page pool must evict earlier cached pages to admit the
    # later prompts
    for i in range(3):
        p = list(RNG.integers(0, cfg.vocab_size, 40 + 16 * i))
        _drive(eng, {0: [Request(prompt_ids=p, max_new_tokens=20)]})
    kv = eng.kv_stats()
    assert kv["prefix_evictions"] > 0, kv
    assert kv["prefix_evictions"] == eng.alloc.prefix_evictions

    # two CONCURRENT rows overcommitting a 5-usable-page pool: eviction
    # can't save an allocation whose pages are all live, so ensure fails
    # and the horizon clamps — both now leave a trace
    eng2 = ServingEngine(cfg, params, EngineConfig(
        max_rows=2, max_seq_len=256, page_size=16, pool_pages=6,
        prefill_bucket=32, decode_horizon=8))
    reqs = [Request(prompt_ids=list(RNG.integers(0, cfg.vocab_size, n)),
                    max_new_tokens=m) for n, m in ((25, 26), (16, 20))]
    _drive(eng2, {0: reqs})
    kv2 = eng2.kv_stats()
    assert kv2["alloc_fail_clamps"] > 0, kv2
    assert kv2["alloc_fail_clamps"] == eng2.metrics["alloc_fail_clamps"]
    assert kv2["horizon_clamped"] >= 1, kv2
    # checkpoint/rollback carries the counter (a rolled-back tick's
    # evictions never happened)
    snap = eng._checkpoint()
    eng.alloc.prefix_evictions += 5
    eng._staging, eng._tick_arrivals = [], []
    eng._rollback(snap)
    assert eng.alloc.prefix_evictions == kv["prefix_evictions"]


# -- /health kv block --------------------------------------------------------

def test_health_kv_block_reports_doubled_pages(cfg_params):
    """End-to-end /health: the kv block carries the pool's storage, byte
    footprint, occupancy, and pressure counters — and an fp8 engine at a
    fixed byte budget reports exactly 2x the bf16 pages_total."""
    pytest.importorskip("aiohttp")
    from ipex_llm_tpu.serving.api_server import OpenAIServer
    from tests.test_serving_faults import _Tok, _spin_server

    cfg, params = cfg_params
    pb16 = paged_page_bytes(cfg.num_layers, cfg.num_kv_heads, 32,
                            cfg.head_dim, v_head_dim=cfg.v_dim)
    budget = 24 * pb16
    ref16 = ServingEngine(cfg, params,
                          EngineConfig(kv_pool_bytes=budget, **EC))
    eng = ServingEngine(cfg, params,
                        EngineConfig(kv_pool_bytes=budget,
                                     kv_storage="fp8", **EC)).start()
    srv = OpenAIServer(eng, _Tok(), "tiny")
    loop, port = _spin_server(srv)
    try:
        health = json.loads(urllib.request.urlopen(
            f"http://127.0.0.1:{port}/health", timeout=30).read())
        kv = health["kv"]
        assert kv["storage"] == "fp8"
        assert kv["pages_total"] == 48
        assert kv["pages_total"] == 2 * ref16.kv_stats()["pages_total"]
        assert kv["pool_bytes"] == budget
        for field in ("pages_free", "page_bytes", "prefix_evictions",
                      "alloc_fail_clamps", "horizon_clamped"):
            assert field in kv, kv
    finally:
        loop.call_soon_threadsafe(loop.stop)
        eng.stop()


# -- quality gate (slow tier) ------------------------------------------------

@pytest.mark.slow
def test_fp8_quality_gate_long_greedy_and_ppl_delta(cfg_params):
    """Slow quality gate for e5m2 KV: (1) a >=64-step greedy stream over
    the fp8 pool is self-consistent across horizons (H=8 reproduces H=1
    bit-for-bit over the whole stream); (2) the fp8 sliding-ppl delta on
    the tiny model stays bounded (benchmark/ppl.py's dense chain — the
    identical e5m2 encode/decode transform the paged pool applies)."""
    cfg, params = cfg_params
    prompt = list(RNG.integers(0, cfg.vocab_size, 24))

    def run(h):
        eng = ServingEngine(cfg, params, EngineConfig(
            max_rows=2, max_seq_len=256, page_size=32, prefill_bucket=32,
            kv_storage="fp8", decode_horizon=h))
        (stream,) = _drive(eng, {0: [Request(prompt_ids=prompt,
                                             max_new_tokens=96)]},
                           max_ticks=6000)
        return stream

    s1, s8 = run(1), run(8)
    assert len(s1) == 96 and s1 == s8

    bench_dir = os.path.join(os.path.dirname(__file__), "..", "benchmark")
    sys.path.insert(0, bench_dir)
    try:
        import ppl as ppl_mod
    finally:
        sys.path.remove(bench_dir)

    ids = (np.asarray(ppl_mod.builtin_tokens(None, n_tokens=768), np.int64)
           % cfg.vocab_size).astype(np.int32)
    p_norm = ppl_mod.sliding_ppl(cfg, params, ids, seq_len=256, stride=128,
                                 kv_kind="normal")
    p_fp8 = ppl_mod.sliding_ppl(cfg, params, ids, seq_len=256, stride=128,
                                 kv_kind="fp8")
    ratio = p_fp8 / p_norm
    # e5m2 KV costs a little quality, never an order of magnitude: the
    # reference ships fp8 KV as a production format, and the dense chain
    # here is bit-identical to what the paged pool stores
    assert ratio < 1.25, (p_norm, p_fp8)
