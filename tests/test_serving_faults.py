"""Fault-isolated serving: the unit of failure is a REQUEST, not the engine.

The contracts under test (PR 3):

- transient step faults (device preemption / RESOURCE_EXHAUSTED shapes) are
  retried with the tick rolled back first, so the committed output stream is
  bit-identical to an unfaulted run — at every guarded site;
- deterministic faults are bisected to the culprit request, which alone
  finishes with ``finish_reason="error"`` while every survivor's tokens AND
  logprobs stay bit-identical to an unfaulted run, and the quarantined
  row's pages return to the pool (no refcount leak);
- ``_fail_all`` (whole-engine blast radius) is reached ONLY when bisection
  cannot localize the fault — an engine-level failure;
- admission control: a full bounded queue raises ``EngineOverloaded``
  (HTTP 429), a draining engine rejects with 503;
- per-request deadlines cover queue wait + generation: an expired request
  finishes ``"timeout"`` — at admission without ever occupying a row, or
  mid-generation at the next tick — and surfaces as HTTP 408 / an SSE
  error event;
- graceful drain finishes in-flight work, then aborts stragglers;
- FIFO regression: a pool-dry requeue re-admits at the HEAD of the pending
  queue (arrival order), not behind later arrivals.

Engines are driven synchronously through ``_tick`` (the transactional
entry the serving loop runs), so fault timing is deterministic.
"""

import json
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

from ipex_llm_tpu.serving.engine import (
    EngineConfig,
    Request,
    ServingEngine,
    stream_tokens,
)
from ipex_llm_tpu.serving.faults import (
    FAULT_SITES,
    DeterministicFault,
    EngineOverloaded,
    FaultInjector,
    TransientFault,
    is_transient,
)
from tests.test_decoder import rand_params, tiny_cfg

EC = dict(max_rows=4, max_seq_len=256, page_size=32, prefill_bucket=32,
          retry_backoff_s=0.001)


@pytest.fixture(scope="module")
def cfg_params():
    cfg = tiny_cfg(vocab_size=131, hidden_size=48, intermediate_size=96,
                   num_heads=4, num_kv_heads=2, head_dim=12,
                   max_position_embeddings=512)
    return cfg, rand_params(cfg, qtype="bf16")


def _drive(eng, reqs, max_ticks=3000):
    """Synchronous loop through the transactional tick; returns each
    request's drained stream in submission order."""
    for r in reqs:
        eng.submit(r)
    for _ in range(max_ticks):
        eng._tick()
        if all(r.finish_reason is not None for r in reqs):
            break
    assert all(r.finish_reason is not None for r in reqs), (
        [r.finish_reason for r in reqs])
    return [list(stream_tokens(r, timeout=10)) for r in reqs]


def _wave(cfg, seed=7):
    """4-row admission wave: greedy rows of mixed prompt lengths plus one
    seeded sampled row — prompts long enough that several mixed ticks run
    while rows are decoding (every fault site gets hit)."""
    rng = np.random.default_rng(seed)
    spec = [(40, {}), (70, {"temperature": 0.8, "seed": 99}),
            (24, {}), (50, {})]
    return [Request(prompt_ids=list(rng.integers(0, cfg.vocab_size, n)),
                    max_new_tokens=8, **kw) for n, kw in spec]


@pytest.fixture(scope="module")
def baseline(cfg_params):
    """Unfaulted reference run (tokens, logprobs, reasons, idle pool)."""
    cfg, params = cfg_params
    eng = ServingEngine(cfg, params, EngineConfig(**EC))
    reqs = _wave(cfg)
    streams = _drive(eng, reqs)
    return {
        "streams": streams,
        "logprobs": [list(r.logprobs) for r in reqs],
        "reasons": [r.finish_reason for r in reqs],
        "pages_idle": eng.alloc.pages_in_use,
    }


# -- transient faults: rollback + retry, bit-identical ----------------------

# the tick's own sites (faults.FAULT_SITES' first four; the rest guard the
# spill tier and KV transport)
_MIXED_SITES = ("page-alloc", "mixed-step", "decode-dispatch", "sample")


@pytest.mark.parametrize("site", _MIXED_SITES)
def test_transient_fault_retried_bit_identical(cfg_params, baseline, site):
    cfg, params = cfg_params
    inj = FaultInjector().inject(site, TransientFault, nth=2)
    eng = ServingEngine(cfg, params, EngineConfig(**EC), fault_injector=inj)
    reqs = _wave(cfg)
    streams = _drive(eng, reqs)
    assert inj.fired == 1, f"site {site} never hit"
    assert eng.metrics["retries"] == 1
    assert eng.metrics.get("errors_isolated", 0) == 0
    assert eng.metrics.get("errors", 0) == 0
    assert streams == baseline["streams"]
    assert [r.finish_reason for r in reqs] == baseline["reasons"]
    for got, want in zip(reqs, baseline["logprobs"]):
        np.testing.assert_array_equal(
            np.asarray(got.logprobs, np.float32),
            np.asarray(want, np.float32))


def test_retries_exhausted_escalates_to_isolation(cfg_params, baseline):
    """A transient fault that keeps firing for ONE request exhausts the
    retry budget, then bisection takes over and isolates it."""
    cfg, params = cfg_params
    reqs = _wave(cfg)
    reqs[1].request_id = "sticky-transient"
    inj = FaultInjector().inject("mixed-step", TransientFault,
                                 request_id="sticky-transient", times=None)
    eng = ServingEngine(cfg, params, EngineConfig(**EC), fault_injector=inj)
    streams = _drive(eng, reqs)
    assert reqs[1].finish_reason == "error"
    assert eng.metrics["retries"] == eng.ec.max_step_retries
    assert eng.metrics["errors_isolated"] == 1
    for i in (0, 2, 3):
        assert streams[i] == baseline["streams"][i]


# -- deterministic faults: bisection quarantines exactly one row ------------

@pytest.mark.parametrize("site", ("mixed-step", "decode-dispatch"))
def test_poisoned_request_quarantined_survivors_identical(
        cfg_params, baseline, site):
    """THE acceptance scenario: a deterministic fault tied to one request
    of a 4-row wave fails exactly that request; the other three produce
    tokens and logprobs bit-identical to an unfaulted run; no pages leak;
    _fail_all never runs."""
    cfg, params = cfg_params
    reqs = _wave(cfg)
    culprit = 0 if site == "decode-dispatch" else 2
    reqs[culprit].request_id = "poisoned"
    inj = FaultInjector().inject(site, DeterministicFault,
                                 request_id="poisoned", times=None)
    eng = ServingEngine(cfg, params, EngineConfig(**EC), fault_injector=inj)
    streams = _drive(eng, reqs)
    assert reqs[culprit].finish_reason == "error"
    # no token of a poisoned tick leaks to the client.  A dense model's
    # row takes its first DECODE step in the tick after the one that
    # completes its prompt (the fused mixed tick), so a row poisoned at
    # that site has streamed the first token its prompt gave it
    assert streams[culprit] == (
        [] if site == "mixed-step" else baseline["streams"][culprit][:1])
    assert eng.metrics["errors_isolated"] == 1
    assert eng.metrics.get("errors", 0) == 0   # _fail_all never ran
    for i in range(4):
        if i == culprit:
            continue
        assert streams[i] == baseline["streams"][i], f"survivor {i} diverged"
        assert reqs[i].finish_reason == baseline["reasons"][i]
        np.testing.assert_array_equal(
            np.asarray(reqs[i].logprobs, np.float32),
            np.asarray(baseline["logprobs"][i], np.float32))
    # page accounting: the quarantined row's pages (shared prefix refs AND
    # fresh allocations) returned to the pool.  Every page still in use is
    # held ONLY by the prefix cache (ref from register_prefix) — a page a
    # finished/quarantined request still referenced would break this.
    assert eng.alloc.pages_in_use == len(eng.alloc.prefix)
    assert all(eng.alloc.ref[p] == 1 for p in eng.alloc.prefix.values())
    if culprit == 2:
        # the 24-token culprit registers no prefix pages even unfaulted,
        # so the idle free-count matches the baseline engine's exactly
        assert eng.alloc.pages_in_use == baseline["pages_idle"]


def test_quarantine_pool_returns_fully_idle(cfg_params):
    """With prompts too short to register prefix-cache pages, the pool is
    COMPLETELY free after a quarantine + normal completions."""
    cfg, params = cfg_params
    rng = np.random.default_rng(5)
    reqs = [Request(prompt_ids=list(rng.integers(0, cfg.vocab_size, 20)),
                    max_new_tokens=4) for _ in range(3)]
    reqs[1].request_id = "poisoned"
    inj = FaultInjector().inject("decode-dispatch", DeterministicFault,
                                 request_id="poisoned", times=None)
    eng = ServingEngine(cfg, params, EngineConfig(**EC), fault_injector=inj)
    _drive(eng, reqs)
    assert reqs[1].finish_reason == "error"
    assert eng.alloc.pages_in_use == 0
    assert not eng.alloc.prefix


def test_vanished_fault_resolves_without_quarantine(cfg_params, baseline):
    """A one-shot deterministic fault that does not reproduce under
    bisection is treated as transient-resolved: nobody is quarantined and
    every stream commits bit-identically."""
    cfg, params = cfg_params
    inj = FaultInjector().inject("decode-dispatch", DeterministicFault,
                                 nth=1, times=1)
    eng = ServingEngine(cfg, params, EngineConfig(**EC), fault_injector=inj)
    reqs = _wave(cfg)
    streams = _drive(eng, reqs)
    assert eng.metrics.get("errors_isolated", 0) == 0
    assert eng.metrics.get("errors", 0) == 0
    assert streams == baseline["streams"]
    assert [r.finish_reason for r in reqs] == baseline["reasons"]


def test_fail_all_only_when_bisection_fails(cfg_params):
    """An engine-level fault — one that fires even with every request
    masked — is the ONLY path to _fail_all."""
    cfg, params = cfg_params
    rng = np.random.default_rng(6)
    eng = ServingEngine(cfg, params, EngineConfig(**EC))
    reqs = [Request(prompt_ids=list(rng.integers(0, cfg.vocab_size, 20)),
                    max_new_tokens=4) for _ in range(2)]
    for r in reqs:
        eng.submit(r)

    def bad_admit():
        raise DeterministicFault("engine-level, not request-level")

    eng._admit = bad_admit
    eng._tick()
    assert all(r.finish_reason == "error" for r in reqs)
    assert eng.metrics["errors"] == 1
    assert eng.metrics.get("errors_isolated", 0) == 0
    for r in reqs:     # terminal None delivered: no client hangs
        assert list(stream_tokens(r, timeout=1)) == []


def test_injector_validates_sites():
    with pytest.raises(ValueError):
        FaultInjector().inject("not-a-site", TransientFault)
    # 4 engine-step sites + the PR 11 spill/transport sites
    assert len(FAULT_SITES) == 8
    for site in ("spill-store", "swap-in", "kv-export", "kv-import"):
        assert site in FAULT_SITES


def test_is_transient_classification():
    assert is_transient(TransientFault("x"))
    assert not is_transient(DeterministicFault("x"))
    assert is_transient(RuntimeError("RESOURCE_EXHAUSTED: oom on chip"))
    assert is_transient(ConnectionError("connection dropped"))
    assert not is_transient(RuntimeError("INVALID_ARGUMENT: bad shape"))


# -- deadlines, admission control, drain ------------------------------------

def test_deadline_expires_in_queue_without_row(cfg_params):
    cfg, params = cfg_params
    eng = ServingEngine(cfg, params, EngineConfig(**EC))
    req = Request(prompt_ids=[1, 2, 3], max_new_tokens=4, deadline_s=0.05)
    req.submitted_s -= 10.0          # aged in the queue
    eng.submit(req)
    eng._tick()
    assert req.finish_reason == "timeout"
    assert eng.metrics["timeouts"] == 1
    assert eng.metrics["requests"] == 0      # never occupied a row
    assert list(stream_tokens(req, timeout=1)) == []


def test_deadline_expires_mid_generation(cfg_params):
    cfg, params = cfg_params
    rng = np.random.default_rng(8)
    eng = ServingEngine(cfg, params, EngineConfig(**EC))
    req = Request(prompt_ids=list(rng.integers(0, cfg.vocab_size, 10)),
                  max_new_tokens=64, deadline_s=60.0)
    eng.submit(req)
    for _ in range(5):
        eng._tick()
    assert req.finish_reason is None and len(req.output_ids) > 0
    req.submitted_s -= 120.0         # deadline now past
    eng._tick()
    assert req.finish_reason == "timeout"
    # emitted-so-far tokens were already committed to the stream
    assert list(stream_tokens(req, timeout=1)) == req.output_ids


def test_bounded_queue_load_shedding(cfg_params):
    cfg, params = cfg_params
    eng = ServingEngine(cfg, params, EngineConfig(max_queue=2, **EC))
    eng.submit(Request(prompt_ids=[1]))
    eng.submit(Request(prompt_ids=[2]))
    with pytest.raises(EngineOverloaded) as ei:
        eng.submit(Request(prompt_ids=[3]))
    assert ei.value.queue_depth == 2 and not ei.value.draining
    assert eng.metrics["rejected"] == 1
    assert eng.queue_depth == 2


def test_drain_finishes_in_flight_then_rejects(cfg_params):
    cfg, params = cfg_params
    rng = np.random.default_rng(9)
    eng = ServingEngine(cfg, params, EngineConfig(**EC)).start()
    try:
        req = Request(prompt_ids=list(rng.integers(0, cfg.vocab_size, 20)),
                      max_new_tokens=8)
        eng.submit(req)
        assert eng.drain(timeout=120)
        assert req.finish_reason == "length"
        assert len(list(stream_tokens(req, timeout=5))) == 8
        assert eng.draining
        with pytest.raises(EngineOverloaded) as ei:
            eng.submit(Request(prompt_ids=[1]))
        assert ei.value.draining
    finally:
        eng.stop()


def test_drain_deadline_aborts_stragglers(cfg_params):
    cfg, params = cfg_params
    rng = np.random.default_rng(10)
    eng = ServingEngine(cfg, params, EngineConfig(**EC)).start()
    try:
        req = Request(prompt_ids=list(rng.integers(0, cfg.vocab_size, 20)),
                      max_new_tokens=200)   # outlives the zero-width window
        eng.submit(req)
        clean = eng.drain(timeout=0.0)   # expires immediately
        assert not clean
        assert req.finish_reason == "abort"
        list(stream_tokens(req, timeout=5))   # terminal None arrived
    finally:
        eng.stop()


def test_shed_abort_maps_to_error_not_success():
    """A drain-deadline shed ("abort" without req.cancelled) must surface
    as an error object — never a 200 with truncated text — while a
    client-initiated abort stays a non-failure."""
    from ipex_llm_tpu.serving.api_server import OpenAIServer, _req_failed

    shed = Request(prompt_ids=[1], finish_reason="abort")
    assert _req_failed(shed)
    payload = OpenAIServer._error_payload(shed)
    assert payload["error"]["type"] == "unavailable_error"
    assert payload["error"]["code"] == "server_draining"
    tgi = OpenAIServer._tgi_error_payload(shed)
    assert tgi["error_type"] == "unavailable"

    client_abort = Request(prompt_ids=[1], finish_reason="abort")
    client_abort.cancelled = True
    assert not _req_failed(client_abort)
    for fr, failed in (("error", True), ("timeout", True), ("stop", False),
                       ("length", False), ("stop_string", False)):
        assert _req_failed(Request(prompt_ids=[1], finish_reason=fr)) is failed


# -- FIFO regression: pool-dry requeue keeps arrival order ------------------

def test_pool_dry_requeue_preserves_fifo(cfg_params):
    """r2 (big, pool-dry at admission) must re-admit BEFORE r3 (small,
    would fit immediately) — the old inbox.put() requeue rotated r2
    behind r3."""
    cfg, params = cfg_params
    rng = np.random.default_rng(11)
    ec = EngineConfig(max_rows=2, max_seq_len=64, page_size=32,
                      pool_pages=4, prefill_bucket=32,
                      retry_backoff_s=0.001)
    eng = ServingEngine(cfg, params, ec)
    r1 = Request(prompt_ids=list(rng.integers(0, cfg.vocab_size, 40)),
                 max_new_tokens=8)    # 2 of the 3 usable pages
    r2 = Request(prompt_ids=list(rng.integers(0, cfg.vocab_size, 40)),
                 max_new_tokens=8)    # needs 2 pages: dry while r1 runs
    r3 = Request(prompt_ids=list(rng.integers(0, cfg.vocab_size, 20)),
                 max_new_tokens=4)    # needs 1 page: would fit right away
    admitted_at: dict[int, int] = {}
    for r in (r1, r2, r3):
        eng.submit(r)
    for t in range(3000):
        eng._tick()
        for r, name in ((r1, 1), (r2, 2), (r3, 3)):
            if name not in admitted_at and r in eng.rows:
                admitted_at[name] = t
        if all(r.finish_reason is not None for r in (r1, r2, r3)):
            break
    assert [r.finish_reason for r in (r1, r2, r3)] == ["length"] * 3
    assert admitted_at[2] <= admitted_at[3], admitted_at


# -- HTTP surfaces: 429 / 503 / 408 / error events / draining health --------

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


def _spin_server(srv):
    import asyncio

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
    started.wait(10)
    return loop, holder["port"]


def _post(port, path, body, timeout=120):
    data = json.dumps(body).encode()
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}{path}", data=data,
        headers={"Content-Type": "application/json"})
    return urllib.request.urlopen(req, timeout=timeout)


def test_http_deadline_maps_to_408_and_sse_error(cfg_params):
    """An expired per-request deadline surfaces as HTTP 408 with an
    OpenAI-style error object (non-streaming) and as a terminal error
    event (streaming) — never a 200 with empty text."""
    pytest.importorskip("aiohttp")
    from ipex_llm_tpu.serving.api_server import OpenAIServer

    cfg, params = cfg_params
    eng = ServingEngine(
        cfg, params,
        EngineConfig(request_deadline_s=0.02, **EC)).start()
    srv = OpenAIServer(eng, _Tok(), "tiny")
    loop, port = _spin_server(srv)
    try:
        with pytest.raises(urllib.error.HTTPError) as ei:
            _post(port, "/v1/completions",
                  {"prompt": "1 2 3", "max_tokens": 64})
        assert ei.value.code == 408
        body = json.loads(ei.value.read())
        assert body["error"]["type"] == "timeout_error"
        assert body["error"]["code"] == "timeout"

        resp = _post(port, "/v1/completions",
                     {"prompt": "4 5 6", "max_tokens": 64, "stream": True})
        events = [json.loads(line.decode().strip()[6:]) for line in resp
                  if line.decode().strip().startswith("data: ")
                  and line.decode().strip() != "data: [DONE]"]
        assert any("error" in e for e in events)

        with pytest.raises(urllib.error.HTTPError) as ei:
            _post(port, "/generate",
                  {"inputs": "7 8 9", "parameters": {"max_new_tokens": 64}})
        assert ei.value.code == 408
        body = json.loads(ei.value.read())
        assert body["error_type"] == "timeout"
    finally:
        loop.call_soon_threadsafe(loop.stop)
        eng.stop()


def test_http_overload_draining_and_health(cfg_params):
    """End-to-end lifecycle: bounded queue → 429 with queue_depth in
    /health; drain → in-flight finishes, /health "draining", submit 503."""
    pytest.importorskip("aiohttp")
    from ipex_llm_tpu.serving.api_server import OpenAIServer

    cfg, params = cfg_params
    eng = ServingEngine(
        cfg, params,
        EngineConfig(max_rows=1, max_seq_len=512, page_size=32,
                     pool_pages=12, prefill_bucket=32, max_queue=1,
                     retry_backoff_s=0.001)).start()
    srv = OpenAIServer(eng, _Tok(), "tiny")
    loop, port = _spin_server(srv)
    results = {}

    def slow(name, n):
        try:
            results[name] = _post(port, "/v1/completions",
                                  {"prompt": "1 2 3", "max_tokens": n})
        except urllib.error.HTTPError as e:
            results[name] = e
    try:
        t1 = threading.Thread(target=slow, args=("r1", 300))
        t1.start()
        # wait until r1 occupies the row, then fill the queue with r2
        for _ in range(3000):
            if eng.metrics["requests"] >= 1:
                break
            time.sleep(0.01)
        t2 = threading.Thread(target=slow, args=("r2", 4))
        t2.start()
        for _ in range(500):
            if eng.queue_depth >= 1:
                break
            time.sleep(0.01)
        with pytest.raises(urllib.error.HTTPError) as ei:
            _post(port, "/v1/completions", {"prompt": "9", "max_tokens": 2})
        assert ei.value.code == 429
        body = json.loads(ei.value.read())
        assert body["error"]["code"] == "queue_full"
        assert body["error"]["queue_depth"] == 1

        health = json.loads(urllib.request.urlopen(
            f"http://127.0.0.1:{port}/health", timeout=30).read())
        assert health["fault_domain"]["queue_depth"] == 1
        assert health["fault_domain"]["rejected"] >= 1

        assert eng.drain(timeout=120)     # r1 + queued r2 run to completion
        t1.join(60)
        t2.join(60)
        assert not isinstance(results["r1"], urllib.error.HTTPError)
        assert not isinstance(results["r2"], urllib.error.HTTPError)

        health = json.loads(urllib.request.urlopen(
            f"http://127.0.0.1:{port}/health", timeout=30).read())
        assert health["status"] == "draining"
        with pytest.raises(urllib.error.HTTPError) as ei:
            _post(port, "/v1/completions", {"prompt": "9", "max_tokens": 2})
        assert ei.value.code == 503
        assert json.loads(ei.value.read())["error"]["code"] == (
            "engine_draining")
    finally:
        loop.call_soon_threadsafe(loop.stop)
        eng.stop()


def test_dead_engine_fails_clients_instead_of_hanging(cfg_params):
    """A dead engine thread must fail a waiting HTTP client promptly
    (bounded-wait loop) instead of blocking on the stream queue forever."""
    pytest.importorskip("aiohttp")
    from ipex_llm_tpu.serving.api_server import OpenAIServer

    cfg, params = cfg_params
    eng = ServingEngine(cfg, params, EngineConfig(**EC)).start()
    srv = OpenAIServer(eng, _Tok(), "tiny")
    loop, port = _spin_server(srv)
    try:
        # kill the engine thread; the request never gets a terminal None
        eng._stop.set()
        eng._thread.join(10)
        with pytest.raises(urllib.error.HTTPError) as ei:
            _post(port, "/v1/completions",
                  {"prompt": "1 2 3", "max_tokens": 8}, timeout=30)
        assert ei.value.code == 500
        assert json.loads(ei.value.read())["error"]["type"] == "server_error"
    finally:
        loop.call_soon_threadsafe(loop.stop)
        eng.stop()


# -- donation vs the rollback contract (PR 6 trace-audit sweep) -------------

def test_rollback_after_decode_dispatch_restores_usable_key(cfg_params):
    """_checkpoint snapshots self.key BY REFERENCE (the bit-identical
    retry contract), so the fused decode program must never donate the
    key: a fault landing AFTER the dispatch (async XLA faults surface at
    the d2h sync) rolls back to that snapshot, and a donated key would be
    a deleted buffer — every retry would then fail, turning a retryable
    transient into mis-quarantine/_fail_all.  Regression for the PR 6
    donation sweep: replay checkpoint -> decode tick -> rollback and
    prove the engine keeps ticking on the restored key."""
    cfg, params = cfg_params
    eng = ServingEngine(cfg, params, EngineConfig(**EC))
    req = Request(prompt_ids=list(range(1, 30)), max_new_tokens=6)
    eng.submit(req)
    for _ in range(200):                     # advance into steady decode
        eng._tick()
        if len(req.output_ids) >= 2:
            break
    assert len(req.output_ids) >= 2 and req.finish_reason is None
    snap = eng._checkpoint()
    eng._staging, eng._tick_arrivals = [], []
    eng._step_once()                         # dispatches the donated program
    eng._rollback(snap)                      # the fault-path restore
    assert not eng.key.is_deleted()          # snapshot survived the dispatch
    out_before = len(req.output_ids)
    for _ in range(200):                     # the retried ticks must commit
        eng._tick()
        if req.finish_reason is not None:
            break
    assert req.finish_reason == "length"
    assert len(req.output_ids) == 6 and len(req.output_ids) > out_before


# -- the tick plan under faults (PR 16 planner) -----------------------------

def test_plan_rides_checkpoint_and_rollback(cfg_params, baseline):
    """The planner's per-tick plan is part of the transactional tick
    state: _checkpoint snapshots it (by reference — TickPlan is frozen),
    _rollback restores it, and a faulted wave driven with the planner on
    (the EngineConfig default) still commits streams bit-identical to
    the unfaulted baseline — the retried tick replays its checkpointed
    plan instead of replanning against a mid-fault queue."""
    cfg, params = cfg_params
    inj = FaultInjector().inject("mixed-step", TransientFault, nth=2)
    eng = ServingEngine(cfg, params, EngineConfig(**EC), fault_injector=inj)
    held = eng._plan
    assert held is not None
    snap = eng._checkpoint()
    assert snap["plan"] is held
    eng._plan = None
    eng._rollback(snap)
    assert eng._plan is held
    reqs = _wave(cfg)
    streams = _drive(eng, reqs)
    assert inj.fired == 1 and eng.metrics["retries"] == 1
    assert streams == baseline["streams"]
    assert [r.finish_reason for r in reqs] == baseline["reasons"]
    # planner state carries no fault residue: one plan per LOGICAL tick
    # (rolled-back ticks replay, bisection probes reuse), so the retry
    # did not inflate the plan counter past the committed tick count + 1
    assert eng.planner.plans <= eng.metrics["ticks"] + 1
