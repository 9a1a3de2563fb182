"""Observability tier: request-lifecycle tracing, the tick flight
recorder, and honest latency histograms (PR 13, serving/observe.py).

The contracts under test:

- **Histogram**: fixed-bucket math (bucket placement, cumulative
  Prometheus ``_bucket/_sum/_count`` exposition, percentile
  interpolation), O(buckets) snapshot/restore, fleet ``merge`` that
  refuses mismatched bucket bounds;
- **Tracer**: bounded LRU of traces, per-trace span cap with a dropped
  count, Chrome trace-event export;
- **engine spans**: a mixed speculative admission wave yields a COMPLETE
  per-request trace (queue wait, every prefill chunk, first token, every
  decode horizon / spec round with accept counts, finish) whose token
  accounting matches the emitted stream exactly; a rolled-back tick
  leaves NO span residue (the retry event is the only trace of it); a
  quarantine freezes the flight recorder automatically;
- **cross-process assembly** (the acceptance gate): one request driven
  through the router with a disaggregated handoff and one injected
  failover assembles into ONE trace — queue wait, both handoff legs, the
  failover replay, and every decode horizon — via the propagated W3C
  traceparent;
- **swap-in honesty**: ``swap_in_p95_s`` is measured through a
  completion barrier, so it is >= the enqueue-only figure the old code
  recorded;
- satellites: ``pagestore.peek`` is truly non-counting (snapshot memo
  survives an export), handoff-leg timeouts at an expired client
  deadline are NOT replica health strikes (both legs), ``import_pages``
  lands a multi-page blob in ONE batched scatter, and ``/kv/import``
  rejects unauthenticated callers when a shared token is configured.
"""

import asyncio
import json
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

from ipex_llm_tpu.serving.engine import (EngineConfig, Request,
                                         ServingEngine, _chain_hashes,
                                         stream_tokens)
from ipex_llm_tpu.serving.faults import (DeterministicFault, FaultInjector,
                                         ReplicaConnectRefused,
                                         TransientFault)
from ipex_llm_tpu.serving.observe import (FAST_LATENCY_BUCKETS_S,
                                          FlightRecorder, Histogram, Tracer,
                                          make_traceparent, new_trace_id,
                                          parse_traceparent)
from ipex_llm_tpu.serving.pagestore import PageStore
from tests.test_decoder import rand_params, tiny_cfg

RNG = np.random.default_rng(23)

EC = dict(max_rows=4, max_seq_len=256, page_size=32, prefill_bucket=32,
          retry_backoff_s=0.001)


@pytest.fixture(scope="module")
def cfg_params():
    cfg = tiny_cfg(vocab_size=131, hidden_size=48, intermediate_size=96,
                   num_heads=4, num_kv_heads=2, head_dim=12,
                   max_position_embeddings=512)
    return cfg, rand_params(cfg, qtype="bf16")


def _drive(eng, reqs, ticks=3000):
    """Synchronous deterministic drive: submit all, tick until done."""
    if isinstance(reqs, Request):
        reqs = [reqs]
    for r in reqs:
        eng.submit(r)
    for _ in range(ticks):
        eng._tick()
        if all(r.finish_reason is not None for r in reqs):
            return [list(stream_tokens(r, timeout=5)) for r in reqs]
    raise AssertionError("requests never finished")


def _spans(eng, req, name=None):
    tv = eng.trace_view(req.trace_id or req.request_id)
    assert tv is not None, "no trace recorded"
    if name is None:
        return tv["spans"]
    return [s for s in tv["spans"] if s["name"] == name]


# -- Histogram ---------------------------------------------------------------

def test_histogram_bucket_math_and_prometheus_exposition():
    h = Histogram(bounds=(0.01, 0.1, 1.0))
    for v in (0.005, 0.01, 0.05, 0.5, 5.0):
        h.observe(v)
    # bucket placement: le=0.01 gets 0.005 AND 0.01 (inclusive upper)
    assert h.counts == [2, 1, 1, 1]
    assert h.count == 5
    assert abs(h.sum - 5.565) < 1e-9
    lines = h.prometheus_lines("lat_s", labels='replica_id="r0"')
    # cumulative buckets + sum + count, labels merged with le
    assert 'lat_s_bucket{replica_id="r0",le="0.01"} 2' in lines
    assert 'lat_s_bucket{replica_id="r0",le="0.1"} 3' in lines
    assert 'lat_s_bucket{replica_id="r0",le="1"} 4' in lines
    assert 'lat_s_bucket{replica_id="r0",le="+Inf"} 5' in lines
    assert 'lat_s_sum{replica_id="r0"} 5.565' in lines
    assert 'lat_s_count{replica_id="r0"} 5' in lines
    # unlabelled form
    assert 'lat_s_bucket{le="+Inf"} 5' in h.prometheus_lines("lat_s")
    # percentile interpolation: p40 (rank 2) lands in the first bucket
    assert 0.0 < h.percentile(40) <= 0.01
    assert 0.1 < h.percentile(80) <= 1.0
    assert Histogram().percentile(95) == 0.0      # empty = 0
    with pytest.raises(ValueError):
        Histogram(bounds=(1.0, 0.5))              # must be increasing


def test_histogram_snapshot_restore_and_fleet_merge():
    h = Histogram(bounds=(0.1, 1.0))
    h.observe(0.05)
    snap = h.snapshot()
    h.observe(10.0)
    h.observe(0.5)
    h.restore(snap)
    assert h.counts == [1, 0, 0] and h.count == 1
    assert abs(h.sum - 0.05) < 1e-12
    # fleet merge folds matching-bounds dicts, refuses mismatches
    other = Histogram(bounds=(0.1, 1.0))
    other.observe(0.5)
    assert h.merge(other.to_dict()) is True
    assert h.counts == [1, 1, 0] and h.count == 2
    alien = Histogram(bounds=(0.2, 2.0))
    alien.observe(0.5)
    before = h.to_dict()
    assert h.merge(alien.to_dict()) is False      # nothing folded
    assert h.to_dict() == before


def test_traceparent_roundtrip_and_malformed():
    tid = new_trace_id()
    tp = make_traceparent(tid)
    parsed = parse_traceparent(tp)
    assert parsed is not None and parsed[0] == tid
    assert len(parsed[1]) == 16
    for bad in (None, "", "garbage", "00-short-span-01",
                "00-" + "0" * 32 + "-" + "1" * 16 + "-01",     # zero trace
                "00-" + "z" * 32 + "-" + "1" * 16 + "-01"):    # non-hex
        assert parse_traceparent(bad) is None, bad


def test_tracer_lru_bounds_span_cap_and_chrome_export():
    tr = Tracer(max_traces=2, max_spans=16)
    from ipex_llm_tpu.serving.observe import span
    tr.add("t1", span("a", 1.0, 2.0, origin="engine", x=1))
    tr.add("t2", span("b", 2.0))
    tr.add("t3", span("c", 3.0, 4.0))
    assert tr.get("t1") is None           # LRU-evicted
    assert len(tr) == 2
    # per-trace span cap: extras count as dropped, never unbounded
    for i in range(20):
        tr.add("t2", span(f"s{i}", 2.0 + i))
    got = tr.get("t2")
    assert len(got["spans"]) == 16 and got["spans_dropped"] == 5
    # Chrome trace-event export: complete (X) spans carry dur, instants
    # are "i"; origins become process rows
    out = Tracer.chrome_events([tr.get("t3")])
    evs = [e for e in out["traceEvents"] if e.get("ph") in ("X", "i")]
    assert evs and evs[0]["ph"] == "X" and evs[0]["dur"] == 1e6
    assert any(e.get("ph") == "M" for e in out["traceEvents"])


def test_flight_recorder_ring_and_dump():
    fr = FlightRecorder(size=8, max_dumps=2)
    for i in range(20):
        fr.record({"tick": i})
    fr.skip_idle()
    v = fr.view()
    assert [r["tick"] for r in v["ring"]] == list(range(12, 20))
    assert v["recorded"] == 20 and v["idle_skipped"] == 1
    fr.dump("first", extra=1)
    fr.record({"tick": 99})
    fr.dump("second")
    fr.dump("third")
    v = fr.view()
    assert len(v["dumps"]) == 2            # bounded
    assert v["dumps"][0]["reason"] == "second"
    # the dump froze the ring AT dump time
    assert v["dumps"][1]["ring"][-1]["tick"] == 99


# -- engine spans ------------------------------------------------------------

def test_mixed_spec_wave_span_completeness(cfg_params):
    """A mixed speculative admission wave (multi-chunk prompts, spec
    riding the fused horizon, one opt-out) produces a COMPLETE trace per
    request: one queue span, prefill chunks summing to the prompt, one
    first token, spec rounds whose token counts sum to the rest of the
    stream, one finish — nothing missing, nothing double-counted."""
    cfg, params = cfg_params
    eng = ServingEngine(cfg, params, EngineConfig(
        spec_k=2, decode_horizon=4, trace_requests=True, **EC))
    prompts = [list(RNG.integers(1, 131, n).astype(int))
               for n in (48, 70, 40)]
    reqs = [Request(prompt_ids=prompts[0], max_new_tokens=8),
            Request(prompt_ids=prompts[1], max_new_tokens=8, seed=7,
                    temperature=0.8),
            Request(prompt_ids=prompts[2], max_new_tokens=8,
                    speculative=False)]
    outs = _drive(eng, reqs)
    for req, out in zip(reqs, outs):
        assert req.finish_reason == "length" and len(out) == 8
        qs = _spans(eng, req, "queue")
        assert len(qs) == 1
        assert qs[0]["attrs"]["prompt_tokens"] == len(req.prompt_ids)
        assert qs[0]["t1"] >= qs[0]["t0"]
        chunks = _spans(eng, req, "prefill_chunk")
        assert sum(s["attrs"]["tokens"] for s in chunks) == \
            len(req.prompt_ids)
        assert len(_spans(eng, req, "first_token")) == 1
        rounds = _spans(eng, req, "spec_round")
        assert rounds, "no spec_round spans"
        assert sum(s["attrs"]["tokens"] for s in rounds) == len(out) - 1
        assert all("accepted" in s["attrs"] for s in rounds)
        fin = _spans(eng, req, "finish")
        assert len(fin) == 1
        assert fin[0]["attrs"] == {"reason": "length", "output_tokens": 8}
        # the opt-out request accepted nothing (its traced spec width
        # is 0: one plain token per round)
        if req.speculative is False:
            assert all(s["attrs"]["accepted"] == 0 for s in rounds)
    # histograms saw the wave
    assert eng.hists["ttft_s"].count == 3
    assert eng.hists["token_latency_s"].count == 3 * 7
    assert eng.hists["tick_sync_s"].count > 0


def test_rollback_leaves_no_span_residue(cfg_params):
    """A transient fault rolls the tick back: its staged spans are
    discarded (the retried tick re-records them once), and the only
    extra trace evidence is the explicit retry event."""
    cfg, params = cfg_params
    inj = FaultInjector().inject("decode-dispatch", TransientFault, nth=3)
    eng = ServingEngine(cfg, params,
                        EngineConfig(trace_requests=True, **EC),
                        fault_injector=inj)
    req = Request(prompt_ids=list(RNG.integers(1, 131, 40).astype(int)),
                  max_new_tokens=8)
    (out,) = _drive(eng, [req])
    assert inj.fired == 1 and eng.metrics["retries"] == 1
    assert len(out) == 8
    retries = _spans(eng, req, "retry")
    assert len(retries) == 1
    assert retries[0]["attrs"]["error"].startswith("TransientFault")
    # span accounting is EXACT despite the rollback: no duplicated
    # horizon/finish spans from the doomed tick
    assert len(_spans(eng, req, "first_token")) == 1
    assert len(_spans(eng, req, "finish")) == 1
    horizons = _spans(eng, req, "decode_horizon")
    assert sum(s["attrs"]["tokens"] for s in horizons) == len(out) - 1
    # histograms rolled back with the tick: exactly one TTFT, exactly
    # out-1 inter-token observations
    assert eng.hists["ttft_s"].count == 1
    assert eng.hists["token_latency_s"].count == len(out) - 1


def test_quarantine_dumps_flight_recorder(cfg_params):
    """Quarantine (the blast-radius decision) freezes the flight ring
    automatically and stamps the culprit's trace; the survivor's stream
    and trace are intact."""
    cfg, params = cfg_params
    good = Request(prompt_ids=list(RNG.integers(1, 131, 24).astype(int)),
                   max_new_tokens=6)
    bad = Request(prompt_ids=list(RNG.integers(1, 131, 24).astype(int)),
                  max_new_tokens=6, request_id="poisoned")
    inj = FaultInjector().inject("decode-dispatch", DeterministicFault,
                                 request_id="poisoned", times=None)
    eng = ServingEngine(cfg, params,
                        EngineConfig(trace_requests=True, **EC),
                        fault_injector=inj)
    _drive(eng, [good, bad])
    assert bad.finish_reason == "error"
    assert good.finish_reason == "length"
    dumps = eng.flight.view()["dumps"]
    assert dumps and dumps[-1]["reason"] == "quarantine"
    assert dumps[-1]["request_id"] == "poisoned"
    assert dumps[-1]["ring"], "dump carried an empty ring"
    assert {"tick", "dispatches", "sync_s", "rows_active",
            "pages_in_use"} <= set(dumps[-1]["ring"][-1])
    assert len(_spans(eng, bad, "quarantine")) == 1
    assert len(_spans(eng, good, "finish")) == 1


def test_tracing_disabled_is_inert_flight_and_hists_always_on(cfg_params):
    """The default engine records NO spans (tracer is None — each site
    is one attribute check), while the flight recorder and histograms —
    pure host bookkeeping — stay on."""
    cfg, params = cfg_params
    eng = ServingEngine(cfg, params, EngineConfig(**EC))
    assert eng.tracer is None
    req = Request(prompt_ids=list(RNG.integers(1, 131, 40).astype(int)),
                  max_new_tokens=6)
    _drive(eng, [req])
    assert eng.trace_view(req.request_id) is None
    ring = eng.flight.view()["ring"]
    assert ring and sum(r["tokens"] for r in ring) == 6
    # idle ticks were skipped, not recorded
    assert eng.flight.idle_skipped >= 0
    assert all(r["tokens"] or r["admitted"] or r["dispatches"]
               for r in ring)
    assert eng.hists["ttft_s"].count == 1


# -- swap-in honesty ---------------------------------------------------------

def test_swap_in_latency_measured_past_completion_barrier(cfg_params):
    """The recorded swap-in latency must cover the scatter's COMPLETION
    (>= the enqueue-only span the old code timed): on an async dispatch
    the enqueue returns in microseconds regardless of page size, which
    made swap_in_p95_s vacuous."""
    from ipex_llm_tpu.kv import PagedKVCache

    cfg, params = cfg_params
    ec = dict(EC, max_rows=2, pool_pages=8)
    eng = ServingEngine(cfg, params,
                        EngineConfig(kv_spill_bytes=1 << 22, **ec))
    enqueue_s = []
    orig = PagedKVCache.scatter_pages

    def timed(self, *a, **kw):
        t0 = time.perf_counter()
        out = orig(self, *a, **kw)
        enqueue_s.append(time.perf_counter() - t0)   # dispatch only
        return out

    prompt = list(RNG.integers(1, 131, 70).astype(int))
    _drive(eng, Request(prompt_ids=prompt, max_new_tokens=8))
    for _ in range(4):   # pool pressure: demote the prompt's pages
        _drive(eng, Request(
            prompt_ids=list(RNG.integers(1, 131, 70).astype(int)),
            max_new_tokens=8))
    assert eng.pagestore.stats()["spills"] > 0
    try:
        PagedKVCache.scatter_pages = timed
        _drive(eng, Request(prompt_ids=prompt, max_new_tokens=8))
    finally:
        PagedKVCache.scatter_pages = orig
    st = eng.pagestore.stats()
    assert st["swap_ins"] >= 1 and enqueue_s
    recorded = list(eng.pagestore.swap_in_s)[-len(enqueue_s):]
    # the barrier makes each recorded figure >= its own enqueue span
    for rec, enq in zip(recorded, enqueue_s):
        assert rec >= enq
    assert st["swap_in_p95_s"] > 0.0
    assert eng.hists["swap_in_s"].count >= 1


def test_swap_in_chain_is_one_batched_scatter(cfg_params):
    """A multi-page spilled prefix chain promotes with reserve() + ONE
    stacked scatter and ONE completion barrier (the per-page form
    serialized N full device round-trips behind per-page barriers on
    exactly the spill-heavy admission path)."""
    from ipex_llm_tpu.kv import PagedKVCache

    cfg, params = cfg_params
    ec = dict(EC, max_rows=2, pool_pages=8)
    eng = ServingEngine(cfg, params,
                        EngineConfig(kv_spill_bytes=1 << 22, **ec))
    prompt = list(RNG.integers(1, 131, 70).astype(int))   # 2 full pages
    _drive(eng, Request(prompt_ids=prompt, max_new_tokens=8))
    for _ in range(4):   # pool pressure: demote the prompt's pages
        _drive(eng, Request(
            prompt_ids=list(RNG.integers(1, 131, 70).astype(int)),
            max_new_tokens=8))
    assert eng.pagestore.stats()["spills"] > 0
    swap_ins0 = eng.pagestore.swap_ins

    calls = []
    orig = PagedKVCache.scatter_pages

    def counting(self, pids, *a, **kw):
        calls.append(len(pids))
        return orig(self, pids, *a, **kw)

    try:
        PagedKVCache.scatter_pages = counting
        _drive(eng, Request(prompt_ids=prompt, max_new_tokens=8))
    finally:
        PagedKVCache.scatter_pages = orig
    assert calls == [2], f"expected ONE batched 2-page scatter, saw {calls}"
    assert eng.pagestore.swap_ins - swap_ins0 == 2   # per-page counting


def test_flight_recorder_carries_rollback_retry_evidence(cfg_params):
    """The retries and injector hits a FAILED tick leaves behind must
    reach the ring: the failed tick rolls back and never records, and
    _recover bumps its counter afterwards, so a per-tick checkpoint
    delta is structurally 0 — the next committed record carries them
    against the last-record baseline instead."""
    cfg, params = cfg_params
    inj = FaultInjector().inject("decode-dispatch", TransientFault, nth=3)
    eng = ServingEngine(cfg, params, EngineConfig(**EC),
                        fault_injector=inj)
    _drive(eng, Request(prompt_ids=list(RNG.integers(1, 131, 40)
                                        .astype(int)), max_new_tokens=8))
    assert inj.fired == 1 and eng.metrics["retries"] == 1
    ring = eng.flight.view()["ring"]
    assert sum(r.get("retries", 0) for r in ring) == 1, \
        "the rollback's retry never reached the flight ring"
    carrier = next(r for r in ring if r.get("retries"))
    # the failed tick's decode-dispatch visit rides the same record
    assert carrier.get("fault_sites", {}).get("decode-dispatch", 0) >= 1


# -- satellites --------------------------------------------------------------

def test_pagestore_peek_is_truly_noncounting():
    """peek() must not bump the mutation counter (it invalidated the
    snapshot memo on every export — the checkpoint then re-copied the
    whole store per tick), must not count an LRU hit, and must not
    perturb eviction order."""
    st = PageStore(1000)
    k = np.zeros((2, 2, 4, 3), np.uint8)
    st.spill(b"a", k, k)
    st.spill(b"b", k, k)
    snap = st.snapshot()
    hits0, mut0 = st.lru.hits, st._mut
    assert st.peek(b"a") is not None
    assert st.peek(b"missing") is None
    assert st._mut == mut0, "peek bumped the mutation counter"
    assert st.lru.hits == hits0, "peek counted an LRU hit"
    # the memoized snapshot survives the peek (O(1) checkpoint path)
    assert st.snapshot() is snap
    # and eviction order is untouched: 'a' (peeked last) is still the
    # LRU victim when the budget forces exactly one eviction
    big = np.zeros((2, 2, 4, 26), np.uint8)    # 832 B pair: evicts one
    st.spill(b"c", big, big)
    assert b"a" not in st.lru and b"b" in st.lru


def test_handoff_deadline_timeout_is_not_a_health_strike():
    """A handoff leg that times out because the CLIENT's deadline is
    (nearly) spent says nothing about the replica: handoff_failures
    counts, health strikes do not — on BOTH legs (the PR 10
    no-strike-on-deadline rule, restored for disagg).  An identical
    stall with NO deadline remains a genuine strike."""
    from ipex_llm_tpu.serving.router import (BackendError, Backend,
                                             Router, RouterConfig)

    class StallPrefill(Backend):
        target = "pre"
        role_probe = {"status": "ok"}

        async def probe(self, timeout=2.0):
            return {"status": "ok"}

        async def send_json(self, path, body, timeout):
            await asyncio.sleep(min(timeout, 0.15))
            raise BackendError("slow prefill", stage="stall")

    class OkPrefill(StallPrefill):
        async def send_json(self, path, body, timeout):
            return 200, {}, b"blob-bytes"

    class StallImport(Backend):
        target = "dec"

        async def probe(self, timeout=2.0):
            return {"status": "ok"}

        async def send_json(self, path, body, timeout):
            return 200, {}, b"{}"

        async def send_bytes(self, path, data, timeout):
            await asyncio.sleep(min(timeout, 0.15))
            raise BackendError("slow import", stage="stall")

    rc = RouterConfig(disagg_prefill_chars=4, handoff_timeout_s=30.0)

    async def leg1():
        router = Router([StallPrefill(), StallImport()], rc,
                        roles=["prefill", "decode"])
        # near-expired client deadline: the leg budget clamps to it
        deadline = time.monotonic() + 0.05
        await router._handoff("/v1/completions", {"prompt": "a b c d"},
                              None, deadline)
        assert router.counters["handoff_failures"] == 1
        assert router.replicas[0].fails == 0, "deadline counted a strike"
        # same stall with NO deadline: a genuine replica strike
        await router._handoff("/v1/completions", {"prompt": "a b c d"},
                              None, None)
        assert router.counters["handoff_failures"] == 2
        assert router.replicas[0].fails == 1

    async def leg2():
        router = Router([OkPrefill(), StallImport()], rc,
                        roles=["prefill", "decode"])
        deadline = time.monotonic() + 0.05
        await router._handoff("/v1/completions", {"prompt": "a b c d"},
                              None, deadline)
        assert router.counters["handoff_failures"] == 1
        assert router.replicas[1].fails == 0, "deadline counted a strike"
        await router._handoff("/v1/completions", {"prompt": "a b c d"},
                              None, None)
        assert router.replicas[1].fails == 1

    asyncio.run(leg1())
    asyncio.run(leg2())


def test_import_pages_is_one_batched_scatter(cfg_params):
    """A multi-page blob lands with reserve() + ONE scatter (the old
    loop paid one allocate+scatter+upload per page), registers the same
    prefix chain, and a dry pool still keeps the unbroken head."""
    from ipex_llm_tpu.kv import PagedKVCache

    cfg, params = cfg_params
    prompt = list(RNG.integers(1, 131, 100).astype(int))   # 3 full pages
    src = ServingEngine(cfg, params, EngineConfig(**EC))
    _drive(src, Request(prompt_ids=prompt, max_new_tokens=4))
    blob = src.export_prefix(prompt)
    assert blob is not None

    calls = []
    orig = PagedKVCache.scatter_pages

    def counting(self, pids, *a, **kw):
        calls.append(len(pids))
        return orig(self, pids, *a, **kw)

    dst = ServingEngine(cfg, params, EngineConfig(**EC))
    try:
        PagedKVCache.scatter_pages = counting
        res = dst.import_pages(blob)
    finally:
        PagedKVCache.scatter_pages = orig
    assert res["imported_pages"] == 3 and res["skipped_pages"] == 0
    assert calls == [3], f"expected ONE batched scatter, saw {calls}"
    # the imported chain is live: the same prompt prefix-hits on arrival
    _drive(dst, Request(prompt_ids=prompt, max_new_tokens=4))
    assert dst.metrics["prefix_hits"] == 1
    assert dst.metrics["prefix_pages_shared"] == 3
    # re-import skips everything (no scatter at all)
    res2 = dst.import_pages(blob)
    assert res2["imported_pages"] == 0 and res2["skipped_pages"] == 3
    # dry pool: what fits is the unbroken head, not an error
    tight = ServingEngine(cfg, params,
                          EngineConfig(**dict(EC, max_rows=2,
                                              pool_pages=6)))
    keys = _chain_hashes(np.asarray(prompt, np.int32), EC["page_size"])
    res3 = tight.import_pages(blob)
    assert 0 < res3["imported_pages"] <= 3
    for i in range(res3["imported_pages"]):
        assert keys[i] in tight.alloc.prefix


# -- HTTP surfaces (replica + router) ---------------------------------------

class _Tok:
    eos_token_id = None
    chat_template = None

    def __call__(self, text):
        def tid(x):
            try:
                return int(x) % 131
            except ValueError:
                return hash(x) % 131
        return {"input_ids": [tid(x) for x in text.split()]}

    def decode(self, ids):
        return " ".join(str(i) for i in ids)


def _serve(srv):
    """Run an OpenAIServer on a loopback port in a daemon thread;
    returns (port, loop)."""
    from aiohttp import web

    loop = asyncio.new_event_loop()
    started = threading.Event()
    holder = {}

    def run():
        asyncio.set_event_loop(loop)
        runner = web.AppRunner(srv.app)
        loop.run_until_complete(runner.setup())
        site = web.TCPSite(runner, "127.0.0.1", 0)
        loop.run_until_complete(site.start())
        holder["port"] = site._server.sockets[0].getsockname()[1]
        started.set()
        loop.run_forever()

    threading.Thread(target=run, daemon=True).start()
    assert started.wait(10)
    return holder["port"], loop


def _get(port, path):
    return urllib.request.urlopen(
        f"http://127.0.0.1:{port}{path}", timeout=30).read().decode()


def test_replica_http_surface_trace_flight_metrics(cfg_params):
    """One replica end to end over HTTP: a traceparent header keys the
    engine's spans to the caller's trace id (/trace/{id}, Chrome
    export), /debug/flight serves the ring, /metrics carries real
    histogram series in both text and json shapes, and /kv/import
    requires the shared token when configured."""
    pytest.importorskip("aiohttp")
    from ipex_llm_tpu.serving.api_server import OpenAIServer

    cfg, params = cfg_params
    eng = ServingEngine(cfg, params,
                        EngineConfig(trace_requests=True, **EC)).start()
    srv = OpenAIServer(eng, _Tok(), "tiny", kv_import_token="s3cret")
    port, _ = _serve(srv)
    try:
        tid = new_trace_id()
        body = json.dumps({"prompt": "1 2 3 4 5 6 7 8",
                           "max_tokens": 4, "temperature": 0.0}).encode()
        http_req = urllib.request.Request(
            f"http://127.0.0.1:{port}/v1/completions", data=body,
            headers={"Content-Type": "application/json",
                     "traceparent": make_traceparent(tid)})
        res = json.loads(urllib.request.urlopen(http_req,
                                                timeout=60).read())
        assert res["choices"][0]["finish_reason"] == "length"

        tr = json.loads(_get(port, f"/trace/{tid}"))
        names = [s["name"] for s in tr["spans"]]
        assert "queue" in names and "finish" in names
        assert "first_token" in names
        chrome = json.loads(_get(port, f"/trace/{tid}?format=chrome"))
        assert any(e.get("ph") == "X" for e in chrome["traceEvents"])
        assert tid in json.loads(_get(port, "/debug/traces"))["trace_ids"]
        # unknown trace: a clean 404
        with pytest.raises(urllib.error.HTTPError) as ei:
            _get(port, f"/trace/{new_trace_id()}")
        assert ei.value.code == 404

        flight = json.loads(_get(port, "/debug/flight"))
        assert flight["ring"] and "dumps" in flight
        assert sum(r["tokens"] for r in flight["ring"]) >= 4

        text = _get(port, "/metrics")
        assert "ipex_llm_tpu_ttft_s_bucket" in text
        assert 'le="+Inf"' in text and "ipex_llm_tpu_ttft_s_count" in text
        assert "ipex_llm_tpu_tick_sync_s_bucket" in text
        js = json.loads(_get(port, "/metrics?format=json"))
        assert js["histograms"]["ttft_s"]["count"] == 1
        assert js["histograms"]["token_latency_s"]["bounds"]

        # /kv/import authn: no token = 401 BEFORE any parsing; the right
        # token proceeds to verification (garbage = 400 TransportError)
        for hdrs, want in (({}, 401),
                           ({"X-KV-Import-Token": "wrong"}, 401),
                           ({"X-KV-Import-Token": "s3cret"}, 400)):
            r = urllib.request.Request(
                f"http://127.0.0.1:{port}/kv/import", data=b"garbage",
                headers={"Content-Type": "application/octet-stream",
                         **hdrs})
            with pytest.raises(urllib.error.HTTPError) as ei:
                urllib.request.urlopen(r, timeout=30)
            assert ei.value.code == want, hdrs
    finally:
        eng.stop()


def test_phase_and_wait_counters_on_metrics_json_advance_monotonically(
        cfg_params):
    """The ``perf_phase_*_s`` counters and the three wait counters
    reach ``/metrics?format=json`` (and the text exposition); scraped
    between requests none of them ever goes back, and every one of them
    has advanced once two requests were served."""
    pytest.importorskip("aiohttp")
    from ipex_llm_tpu.serving.api_server import OpenAIServer
    from ipex_llm_tpu.serving.perfwatch import PHASES

    cfg, params = cfg_params
    eng = ServingEngine(cfg, params, EngineConfig(**EC)).start()
    port, _ = _serve(OpenAIServer(eng, _Tok(), "tiny"))
    keys = [f"perf_phase_{p}_s" for p in PHASES] + [
        "queue_wait_s_total", "admit_to_first_token_s_total",
        "first_tokens"]
    try:
        scrapes = []
        for i in range(3):
            m = json.loads(_get(port, "/metrics?format=json"))["metrics"]
            scrapes.append({k: m[k] for k in keys})   # all there, from 0
            if i == 2:
                break
            body = json.dumps({"prompt": " ".join(str(7 * i + j)
                                                  for j in range(1, 20)),
                               "max_tokens": 5,
                               "temperature": 0.0}).encode()
            urllib.request.urlopen(urllib.request.Request(
                f"http://127.0.0.1:{port}/v1/completions", data=body,
                headers={"Content-Type": "application/json"}), timeout=60)
        assert all(v == 0 for v in scrapes[0].values())
        for a, b in zip(scrapes, scrapes[1:]):
            assert all(b[k] >= a[k] for k in keys), (a, b)
        assert all(scrapes[2][k] > scrapes[0][k] for k in keys), scrapes[2]
        assert scrapes[2]["first_tokens"] == 2
        text = _get(port, "/metrics")
        assert "ipex_llm_tpu_perf_phase_dispatch_s" in text
        assert "ipex_llm_tpu_queue_wait_s_total" in text
    finally:
        eng.stop()


def test_router_assembles_disagg_failover_trace_e2e(cfg_params):
    """THE acceptance gate: one request through the router with a
    disaggregated handoff and one injected failover yields ONE
    assembled trace covering queue wait, both handoff legs, the
    failover replay, and every decode horizon — across three processes'
    span stores (router + prefill replica + serving decode replica),
    keyed by the propagated traceparent."""
    pytest.importorskip("aiohttp")
    from ipex_llm_tpu.serving.router import (InProcessBackend, Router,
                                             RouterConfig, RouterStream)

    cfg, params = cfg_params
    tec = dict(EC, kv_storage="fp8", trace_requests=True)

    def factory():
        return ServingEngine(cfg, params, EngineConfig(**tec)).start()

    prompt = " ".join(str((7 * i) % 131 or 1) for i in range(48))
    ids = [int(x) for x in prompt.split()]
    ref_eng = ServingEngine(cfg, params, EngineConfig(**tec))
    (ref,) = _drive(ref_eng, Request(prompt_ids=ids, max_new_tokens=8))

    async def scenario():
        # decode A dies on the STREAM attempt (hit 1 = the import leg,
        # which must succeed; hit 2 = open_sse → connect refused): the
        # handoff lands, then the stream fails over to decode B
        inj = FaultInjector().inject("replica-connect",
                                     ReplicaConnectRefused, nth=2,
                                     times=1)
        b_pre = InProcessBackend(factory, _Tok(), "tiny")
        b_a = InProcessBackend(factory, _Tok(), "tiny", injector=inj)
        b_b = InProcessBackend(factory, _Tok(), "tiny")
        for b in (b_pre, b_a, b_b):
            await b.start()
        router = Router(
            [b_pre, b_a, b_b],
            RouterConfig(probe_interval_s=0.01, probe_timeout_s=1.0,
                         eject_after=3, stall_timeout_s=30.0,
                         disagg_prefill_chars=16),
            roles=["prefill", "decode", "decode"])
        try:
            await router.poll_once()
            tid = new_trace_id()
            res = await router.dispatch_stream(
                "/v1/completions",
                {"prompt": prompt, "max_tokens": 8, "temperature": 0.0,
                 "stream": True}, trace_id=tid)
            assert isinstance(res, RouterStream), res
            pieces = []
            async for ev in res.events:
                for line in ev.decode().strip().split("\n"):
                    if line.startswith("data: ") and line[6:] != "[DONE]":
                        j = json.loads(line[6:])
                        assert "error" not in j, j
                        if j.get("choices"):
                            pieces.append(j["choices"][0].get("text", ""))
            # bit-identical despite handoff + failover
            assert "".join(pieces).strip() == _Tok().decode(ref)
            assert router.counters["handoffs"] == 1
            assert router.counters["failovers"] == 1

            tr = await router.assemble_trace(tid)
            assert tr is not None and tr["trace_id"] == tid
            by_name = {}
            for s in tr["spans"]:
                by_name.setdefault(s["name"], []).append(s)
            # both handoff legs, router-side, successful
            (pre_leg,) = by_name["handoff_prefill"]
            assert pre_leg["origin"] == "router"
            assert pre_leg["attrs"]["status"] == 200
            assert pre_leg["attrs"]["bytes"] > 0
            (imp_leg,) = by_name["handoff_import"]
            assert imp_leg["attrs"]["status"] == 200
            # the failover replay, with the failed attempt before it
            assert len(by_name["failover"]) == 1
            outcomes = [s["attrs"].get("outcome")
                        for s in by_name["route_attempt"]]
            assert "transport_connect" in outcomes
            assert "stream_committed" in outcomes
            # queue wait on the replica that SERVED the stream (decode
            # B, replica index 2).  The handoff imported into decode A —
            # the replica the failover then abandoned — so B honestly
            # re-prefilled from scratch (shared_pages 0): exactly the
            # kind of where-did-the-time-go fact the trace exists to show
            queues = [s for s in by_name["queue"]
                      if s["origin"].startswith("replica2")]
            assert len(queues) == 1
            assert queues[0]["attrs"]["shared_pages"] == 0
            assert b_a.engine.metrics.get("kv_pages_imported", 0) >= 1
            # every decode horizon: spans on the serving replica account
            # for every token after the first
            horizons = [s for s in by_name["decode_horizon"]
                        if s["origin"].startswith("replica2")]
            assert horizons
            assert sum(s["attrs"]["tokens"] for s in horizons) == 7
            assert [s for s in by_name["first_token"]
                    if s["origin"].startswith("replica2")]
            # the prefill replica's own spans joined the same trace
            # (the traceparent rode the /kv/prefill leg)
            assert any(s["origin"].startswith("replica0")
                       for s in tr["spans"])

            # fleet metrics carry the histogram sums + handoff legs
            text = await router.metrics_text()
            assert "ipex_llm_tpu_router_handoff_prefill_s_bucket" in text
            assert "ipex_llm_tpu_fleet_ttft_s_bucket" in text
        finally:
            await router.close()

    asyncio.run(scenario())
