"""Replica-fault-tolerant serving tier: the REPLICA is the unit of failure.

The contracts under test (PR 10, serving/router.py):

- the per-replica health state machine (healthy → suspect → ejected →
  probing → reinstated) is driven by /health polls AND per-request
  transport outcomes, with exponential probe backoff — a crashed or
  wedged (slow-loris) replica stops receiving traffic, and a restarted
  one reinstates itself;
- failover with the safe-replay contract: a request that fails before
  any token was delivered replays on another replica and the client sees
  the EXACT stream the healthy fleet would have produced (bit-identity);
  a mid-stream death surfaces a terminal error object — never a silent
  truncation, never a duplicated token (delivered text is always a
  prefix of the reference stream);
- the deadline budget spans failover attempts (each retry runs under the
  REMAINING budget) and attempts are bounded;
- backpressure propagates: replica 429/503 re-routes with a cooloff and
  honors Retry-After; the router's own bounded inbox sheds with 429 +
  Retry-After; prefix-affinity routes repeat prefixes to the replica
  that served them, degrading to least-loaded on ejection/pool pressure;
- drain_replica → restart → reinstate is invisible to in-flight work
  while the other replicas absorb new traffic;
- satellites: /health carries replica_id/uptime_s/ticks, /metrics is
  Prometheus-style per-replica, 429/503 carry a derived Retry-After.

Scripted-backend tests drive the router core directly (no sockets, no
engines); the e2e tests run REAL engines behind in-process replicas over
loopback HTTP — the same transport as a multi-process fleet.
"""

import asyncio
import json
import threading
import time
import urllib.error
import urllib.request

import pytest

from ipex_llm_tpu.serving.engine import (
    EngineConfig,
    Request,
    ServingEngine,
    stream_tokens,
)
from ipex_llm_tpu.serving.faults import (
    FaultInjector,
    ReplicaConnectRefused,
    ReplicaSlowHealth,
    ReplicaStreamHang,
)
from ipex_llm_tpu.serving.router import (
    EJECTED,
    HEALTHY,
    PROBING,
    SUSPECT,
    Backend,
    BackendError,
    InProcessBackend,
    Router,
    RouterConfig,
    RouterResponse,
    RouterStream,
    SSEOpen,
)
from tests.test_decoder import rand_params, tiny_cfg

pytest.importorskip("aiohttp")

EC = dict(max_rows=4, max_seq_len=256, page_size=32, prefill_bucket=32,
          retry_backoff_s=0.001)


@pytest.fixture(scope="module")
def cfg_params():
    cfg = tiny_cfg(vocab_size=131, hidden_size=48, intermediate_size=96,
                   num_heads=4, num_kv_heads=2, head_dim=12,
                   max_position_embeddings=512)
    return cfg, rand_params(cfg, qtype="bf16")


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


def _reference_text(cfg, params, prompt_ids, n_out=8, **req_kw) -> str:
    """What a healthy single replica streams for this request (greedy or
    seeded): the bit-identity oracle every failover path is judged
    against."""
    eng = ServingEngine(cfg, params, EngineConfig(**EC))
    r = Request(prompt_ids=list(prompt_ids), max_new_tokens=n_out, **req_kw)
    eng.submit(r)
    for _ in range(2000):
        eng._tick()
        if r.finish_reason is not None:
            break
    assert r.finish_reason is not None
    return _Tok().decode(list(stream_tokens(r, timeout=5)))


def _factory(cfg, params):
    def make():
        return ServingEngine(cfg, params, EngineConfig(**EC)).start()
    return make


async def _consume(stream: RouterStream):
    """Drain a RouterStream: returns (text_pieces, error_payload|None,
    saw_done)."""
    pieces, err, done = [], None, False
    async for ev in stream.events:
        for line in ev.decode().strip().split("\n"):
            if not line.startswith("data: "):
                continue
            d = line[6:]
            if d == "[DONE]":
                done = True
                continue
            j = json.loads(d)
            if "error" in j:
                err = j
            elif j.get("choices") and j["choices"][0].get("text"):
                pieces.append(j["choices"][0]["text"])
    return pieces, err, done


# ---------------------------------------------------------------------------
# scripted backend: drives the router core with no sockets and no engines


class FakeBackend(Backend):
    def __init__(self, name, queue_depth=0):
        self.target = name
        self.health_ok = True
        self.health_delay = 0.0
        self.kv = {"pages_total": 100, "pages_free": 90,
                   "prefix_evictions": 0}
        self.queue_depth = queue_depth
        self.json_calls: list[dict] = []
        self.sse_calls = 0
        # behaviour knobs: an async callable(body) -> (status, headers,
        # payload-bytes) for send_json; for open_sse, None = a normal
        # 3-event stream
        self.json_behavior = None
        self.sse_behavior = None

    async def probe(self, timeout=1.0) -> dict:
        if self.health_delay:
            await asyncio.sleep(self.health_delay)
        if not self.health_ok:
            raise BackendError("scripted /health failure")
        return {"status": "ok",
                "replica": {"replica_id": self.target, "uptime_s": 1.0,
                            "ticks": 1},
                "kv": dict(self.kv),
                "fault_domain": {"queue_depth": self.queue_depth}}

    async def fetch_metrics(self, timeout=1.0) -> dict:
        return {"replica_id": self.target,
                "metrics": {"requests": len(self.json_calls)}}

    async def get_json(self, path, timeout=10.0):
        return 200, b"{}"

    async def send_json(self, path, body, timeout):
        self.json_calls.append(body)
        if self.json_behavior is not None:
            return await self.json_behavior(body)
        return 200, {"Content-Type": "application/json"}, json.dumps(
            {"served_by": self.target}).encode()

    async def open_sse(self, path, body, stall_timeout_s,
                       first_event_timeout_s=None):
        self.sse_calls += 1
        if self.sse_behavior is not None:
            return await self.sse_behavior(body)

        async def events():
            for i in range(3):
                yield (b'data: {"choices": [{"text": "t%d "}]}\n\n'
                       % i)
            yield b"data: [DONE]\n\n"

        return SSEOpen(200, {}, events=events())


def _rc(**kw) -> RouterConfig:
    base = dict(probe_interval_s=0.01, probe_timeout_s=0.1,
                suspect_after=1, eject_after=2, probe_backoff_s=0.05,
                probe_backoff_max_s=0.2, reinstate_after=2,
                max_attempts=3, stall_timeout_s=1.0, shed_cooloff_s=0.3)
    base.update(kw)
    return RouterConfig(**base)


def test_state_machine_eject_probe_reinstate():
    """healthy → suspect → ejected via failed polls, exponential probe
    backoff while down, probing → reinstated once /health returns —
    with the transition log recording every hop."""
    async def scenario():
        b = FakeBackend("r0")
        router = Router([b], _rc())
        rep = router.replicas[0]

        await router.poll_once()
        assert rep.state == HEALTHY and rep.last_health is not None

        b.health_ok = False
        await asyncio.sleep(0.02)
        await router.poll_once()
        assert rep.state == SUSPECT
        await asyncio.sleep(0.02)
        await router.poll_once()
        assert rep.state == EJECTED
        assert not rep.routable(time.monotonic())
        assert router.counters["ejections"] == 1
        backoff0 = rep.backoff_s

        # failed probes double the backoff (bounded)
        await asyncio.sleep(rep.next_probe_t - time.monotonic() + 0.01)
        await router.poll_once()
        assert rep.state == EJECTED and rep.backoff_s == backoff0 * 2
        await asyncio.sleep(rep.next_probe_t - time.monotonic() + 0.01)
        await router.poll_once()
        assert rep.backoff_s == pytest.approx(0.2)   # capped

        # recovery: reinstate_after=2 successful probes required
        b.health_ok = True
        await asyncio.sleep(rep.next_probe_t - time.monotonic() + 0.01)
        await router.poll_once()
        assert rep.state == EJECTED and rep.probe_ok == 1
        await asyncio.sleep(rep.next_probe_t - time.monotonic() + 0.01)
        await router.poll_once()
        assert rep.state == HEALTHY
        assert router.counters["reinstated"] == 1

        hops = [(t["from"], t["to"]) for t in rep.transitions]
        assert (HEALTHY, SUSPECT) in hops
        assert (SUSPECT, EJECTED) in hops
        assert (EJECTED, PROBING) in hops
        assert (PROBING, HEALTHY) in hops

    asyncio.run(scenario())


def test_frozen_ticks_with_ok_health_ejects_wedged_replica():
    """The wedge shape a liveness-only check can't see: /health answers
    200-ok but the engine loop's `ticks` counter stays frozen while
    uptime advances — past wedge_timeout_s that is a FAILED poll, and
    the replica ejects like any other dead one."""
    async def scenario():
        b = FakeBackend("r0")   # probe always reports ticks=1 (frozen)
        router = Router([b], _rc(wedge_timeout_s=0.05, eject_after=2))
        rep = router.replicas[0]
        await router.poll_once()            # records the ticks baseline
        assert rep.state == HEALTHY
        await asyncio.sleep(0.07)           # past the wedge bound
        await router.poll_once()
        assert rep.state == SUSPECT
        await asyncio.sleep(0.02)
        await router.poll_once()
        assert rep.state == EJECTED
        assert any(t["reason"] == "wedged_ticks" for t in rep.transitions)
        # and the probe loop must not reinstate it while still frozen
        await asyncio.sleep(rep.next_probe_t - time.monotonic() + 0.01)
        await router.poll_once()
        assert rep.state == EJECTED

    asyncio.run(scenario())


def test_slow_loris_health_counts_as_failed_poll():
    """A /health slower than the probe budget is a FAILED poll (the
    wedged-replica shape): the replica loses traffic like a crashed one."""
    async def scenario():
        b = FakeBackend("r0")
        b.health_delay = 10.0    # way past probe_timeout_s=0.1
        router = Router([b], _rc(eject_after=1))
        await router.poll_once()
        assert router.replicas[0].state == EJECTED

    asyncio.run(scenario())


def test_least_loaded_and_backpressure_reroute():
    """Replica 429 feeds routing: the shedding replica goes into cooloff
    (Retry-After honored) and the request re-routes — invisible to the
    client; with EVERY replica shedding, the router sheds with 503 +
    Retry-After."""
    async def scenario():
        b0, b1 = FakeBackend("r0"), FakeBackend("r1", queue_depth=5)

        async def shed(body):
            return 429, {"Retry-After": "2"}, json.dumps(
                {"error": {"code": "queue_full"}}).encode()

        b0.json_behavior = shed
        router = Router([b0, b1], _rc())
        res = await router.dispatch_json(
            "/v1/completions", {"prompt": "x", "max_tokens": 4})
        # least-loaded picked b0 (queue_depth 0 vs 5), got 429, re-routed
        assert json.loads(res.payload)["served_by"] == "r1"
        assert router.counters["rerouted_backpressure"] == 1
        assert len(b0.json_calls) == 1
        now = time.monotonic()
        assert router.replicas[0].shed_until - now == pytest.approx(2.0,
                                                                    abs=0.3)
        # cooloff: the next request skips b0 without even asking it
        res = await router.dispatch_json(
            "/v1/completions", {"prompt": "y", "max_tokens": 4})
        assert json.loads(res.payload)["served_by"] == "r1"
        assert len(b0.json_calls) == 1

        # both shedding -> the router sheds honestly
        b1.json_behavior = shed
        router.replicas[1].shed_until = 0.0
        res = await router.dispatch_json(
            "/v1/completions", {"prompt": "z", "max_tokens": 4})
        assert res.status == 503
        assert json.loads(res.payload)["error"]["code"] == (
            "no_replica_available")
        assert int(res.headers["Retry-After"]) >= 1

    asyncio.run(scenario())


def test_router_inbox_bounded_sheds_429():
    async def scenario():
        router = Router([FakeBackend("r0")], _rc(max_inflight=1))
        router._inflight = 1   # a stream is holding the only slot
        res = await router.dispatch_json(
            "/v1/completions", {"prompt": "x"})
        assert res.status == 429
        assert json.loads(res.payload)["error"]["code"] == (
            "router_overloaded")
        assert int(res.headers["Retry-After"]) >= 1
        assert router.counters["shed"] == 1

    asyncio.run(scenario())


def test_bounded_failover_attempts():
    """Every replica connect-refusing must end in a bounded number of
    attempts and an honest 503 — not an infinite replay loop."""
    async def scenario():
        backends = [FakeBackend(f"r{i}") for i in range(5)]

        async def refuse(body):
            raise BackendError("connection refused", stage="connect")

        for b in backends:
            b.json_behavior = refuse
        router = Router(backends, _rc(max_attempts=3, eject_after=99))
        res = await router.dispatch_json("/v1/completions", {"prompt": "x"})
        assert res.status == 503
        assert json.loads(res.payload)["error"]["code"] == (
            "failover_exhausted")
        assert sum(len(b.json_calls) for b in backends) == 3

    asyncio.run(scenario())


def test_deadline_budget_spans_failover():
    """The per-request deadline is carried ACROSS attempts: a failover
    replay runs under the remaining budget (stamped into the forwarded
    body), and a budget consumed by a dying replica expires the request
    instead of granting the next replica a fresh allowance."""
    async def scenario():
        b0, b1 = FakeBackend("r0"), FakeBackend("r1", queue_depth=5)

        async def die_slowly(body):
            await asyncio.sleep(0.25)
            raise BackendError("reset mid-request", stage="read")

        b0.json_behavior = die_slowly
        router = Router([b0, b1], _rc())
        res = await router.dispatch_json(
            "/v1/completions", {"prompt": "x", "deadline_s": 1.0})
        assert json.loads(res.payload)["served_by"] == "r1"
        # b0 saw (about) the full budget, b1 only what b0 left behind
        assert b0.json_calls[0]["deadline_s"] == pytest.approx(1.0, abs=0.1)
        assert b1.json_calls[0]["deadline_s"] == pytest.approx(0.75,
                                                              abs=0.15)
        assert router.counters["failovers"] == 1

        # budget exhausted by the dying replica -> timeout error object,
        # no second attempt (fresh prompt: no affinity shortcut past b0)
        b1.json_calls.clear()
        res = await router.dispatch_json(
            "/v1/completions", {"prompt": "zz", "deadline_s": 0.2})
        assert res.status == 408
        assert json.loads(res.payload)["error"]["type"] == "timeout_error"
        assert b1.json_calls == []

    asyncio.run(scenario())


def test_deadline_expiry_is_not_a_replica_failure():
    """A request running out of its own budget mid-generation (the
    router's send timeout = the remaining deadline) is a CLIENT outcome:
    408, no health strike — short-deadline clients must not be able to
    eject healthy replicas."""
    async def scenario():
        b0 = FakeBackend("r0")

        async def too_slow(body):
            await asyncio.sleep(0.25)
            raise BackendError("response timed out", stage="stall")

        b0.json_behavior = too_slow
        router = Router([b0], _rc(eject_after=1))
        res = await router.dispatch_json(
            "/v1/completions", {"prompt": "x", "deadline_s": 0.2})
        assert res.status == 408
        assert router.replicas[0].state == HEALTHY
        assert router.replicas[0].fails == 0

    asyncio.run(scenario())


def test_affinity_repeat_prefix_and_spill():
    """Repeat-prefix traffic sticks to the replica that served the prefix
    (hit rate ~1 once warm) but degrades gracefully: prefix evictions or
    pool pressure reported in that replica's /health kv block — or the
    replica leaving rotation — spill the prefix to least-loaded."""
    async def scenario():
        # b1 is otherwise preferred (lower queue) — affinity must override
        b0, b1 = FakeBackend("r0", queue_depth=3), FakeBackend("r1")
        router = Router([b0, b1], _rc())
        await router.poll_once()   # learn kv blocks

        prompt = "A " * 40   # shared 64-char prefix window
        body = {"prompt": prompt + "tail0", "max_tokens": 4}
        res = await router.dispatch_json("/v1/completions", body)
        first = json.loads(res.payload)["served_by"]   # least-loaded: r1
        assert first == "r1"
        for i in range(6):
            res = await router.dispatch_json(
                "/v1/completions",
                {"prompt": prompt + f"tail{i}", "max_tokens": 4})
            assert json.loads(res.payload)["served_by"] == first
        assert router.counters["affinity_hits"] == 6

        # the owning replica evicted prefix pages since the mark: stale ->
        # spill to least-loaded and re-home
        b1.kv["prefix_evictions"] = 7
        router.replicas[1].last_health = await b1.probe()
        b1.queue_depth = 9
        router.replicas[1].last_health["fault_domain"]["queue_depth"] = 9
        res = await router.dispatch_json(
            "/v1/completions", {"prompt": prompt + "tail9",
                                "max_tokens": 4})
        assert json.loads(res.payload)["served_by"] == "r0"
        assert router.counters["affinity_spills"] == 1

        # ejection spills too: the re-homed owner (r0) leaving rotation
        # forgets the mapping instead of pinning traffic to a dead replica
        router.replicas[0].eject(time.monotonic(), "test")
        res = await router.dispatch_json(
            "/v1/completions", {"prompt": prompt + "tail10",
                                "max_tokens": 4})
        assert json.loads(res.payload)["served_by"] == "r1"
        assert router.counters["affinity_spills"] == 2

    asyncio.run(scenario())


def test_partial_trailing_block_is_a_read_death():
    """A FIN mid-event (replica died while writing a block) must NOT be
    forwarded as a clean end-of-stream: the unframed fragment is the
    silent-truncation shape, so the transport surfaces a read-stage
    BackendError (zero-delivery → failover; committed → terminal error
    event).  Clean EOF after complete frames stays a normal end."""
    from ipex_llm_tpu.serving.router import HTTPBackend

    class _Content:
        def __init__(self, chunks):
            self.chunks = list(chunks)

        async def readany(self):
            return self.chunks.pop(0) if self.chunks else b""

    class _Resp:
        def __init__(self, chunks):
            self.content = _Content(chunks)

        def release(self):
            pass

    async def scenario():
        b = HTTPBackend("http://unused")
        gen = b._events(_Resp([b'data: {"a": 1}\n\n', b'data: {"trunc']),
                        1.0)
        assert await gen.__anext__() == b'data: {"a": 1}\n\n'
        with pytest.raises(BackendError) as ei:
            async for _ in gen:
                pass
        assert ei.value.stage == "read"

        gen2 = b._events(_Resp([b"data: x\n\n"]), 1.0)
        assert [ev async for ev in gen2] == [b"data: x\n\n"]

    asyncio.run(scenario())


# ---------------------------------------------------------------------------
# real engines behind in-process replicas (loopback HTTP, the same
# transport as a multi-process fleet)


def test_zero_token_failover_bit_identity(cfg_params):
    """A replica that dies before delivering any token is invisible: the
    request replays on another replica and the client receives the EXACT
    stream — tokens and order — the healthy fleet would have produced
    (seeded AND greedy), with no error event and no duplicate."""
    cfg, params = cfg_params
    # distinct prompts: the second request must not ride the first one's
    # prefix-affinity entry (it would dodge the injected fault)
    ref_greedy = _reference_text(cfg, params, [1, 2, 3, 4, 5, 6])
    ref_seeded = _reference_text(cfg, params, [2, 3, 4, 5, 6, 7],
                                 temperature=0.8, seed=99)

    async def scenario():
        inj = FaultInjector().inject("replica-connect",
                                     ReplicaConnectRefused, times=2)
        b0 = InProcessBackend(_factory(cfg, params), _Tok(), "tiny",
                              injector=inj)
        b1 = InProcessBackend(_factory(cfg, params), _Tok(), "tiny")
        await b0.start()
        await b1.start()
        router = Router([b0, b1], _rc(eject_after=3))
        try:
            for body, ref in (
                ({"prompt": "1 2 3 4 5 6", "max_tokens": 8,
                  "temperature": 0.0, "stream": True}, ref_greedy),
                ({"prompt": "2 3 4 5 6 7", "max_tokens": 8,
                  "temperature": 0.8, "seed": 99, "stream": True},
                 ref_seeded),
            ):
                res = await router.dispatch_stream("/v1/completions", body)
                assert isinstance(res, RouterStream)
                pieces, err, done = await _consume(res)
                assert err is None and done
                assert "".join(pieces) == ref
            assert inj.fired == 2
            assert router.counters["failovers"] == 2
            assert router.counters["midstream_errors"] == 0
        finally:
            await router.close()

    asyncio.run(scenario())


def test_midstream_death_terminal_error_no_duplicate(cfg_params):
    """A replica dying mid-stream (wedge: the stream stalls past the
    router's bound) is NOT replayed: the client keeps every delivered
    token exactly once (a strict prefix of the reference stream) and the
    stream terminates with the standard error object + [DONE] — never a
    silent truncation, never a hang."""
    cfg, params = cfg_params
    # 24-token stream: token generation is slow relative to the client
    # read loop, so the 3rd-read hang lands mid-stream (some tokens
    # delivered, nowhere near all)
    ref = _reference_text(cfg, params, [1, 2, 3, 4, 5, 6], n_out=24)

    async def scenario():
        backends = []
        for _ in range(2):
            inj = FaultInjector().inject("replica-stream",
                                         ReplicaStreamHang, nth=3,
                                         times=1)
            b = InProcessBackend(_factory(cfg, params), _Tok(), "tiny",
                                 injector=inj)
            await b.start()
            backends.append(b)
        router = Router(backends, _rc(stall_timeout_s=0.5))
        try:
            res = await router.dispatch_stream(
                "/v1/completions",
                {"prompt": "1 2 3 4 5 6", "max_tokens": 24,
                 "temperature": 0.0, "stream": True})
            assert isinstance(res, RouterStream)
            t0 = time.monotonic()
            pieces, err, done = await _consume(res)
            # bounded: the stall timeout, not a client hang
            assert time.monotonic() - t0 < 5.0
            text = "".join(pieces)
            assert err is not None, "mid-stream death must surface"
            assert err["error"]["code"] == "replica_died_midstream"
            assert err["error"]["type"] == "server_error"
            assert done   # the OpenAI framing still terminates with [DONE]
            # at-most-once: delivered text is a non-empty strict prefix
            assert text and ref.startswith(text) and text != ref
            assert router.counters["midstream_errors"] == 1
        finally:
            await router.close()

    asyncio.run(scenario())


def test_drain_replica_under_load_then_reinstate(cfg_params):
    """Rolling-restart step: drain_replica finishes the in-flight stream
    (no truncation), routes new work to the surviving replica, and after
    restart the probe loop reinstates the drained one — every hop
    visible in the aggregated health view."""
    cfg, params = cfg_params

    async def scenario():
        b0 = InProcessBackend(_factory(cfg, params), _Tok(), "tiny")
        b1 = InProcessBackend(_factory(cfg, params), _Tok(), "tiny")
        await b0.start()
        await b1.start()
        router = Router([b0, b1], _rc(reinstate_after=1))
        try:
            ref = _reference_text(cfg, params, [1, 2, 3, 4, 5, 6],
                                  n_out=24)
            res = await router.dispatch_stream(
                "/v1/completions",
                {"prompt": "1 2 3 4 5 6", "max_tokens": 24,
                 "temperature": 0.0, "stream": True})
            assert isinstance(res, RouterStream)
            # ties route to idx 0: the stream lives on the replica being
            # drained
            assert router.replicas[0].inflight == 1
            consumer = asyncio.ensure_future(_consume(res))

            drainer = asyncio.ensure_future(
                router.drain_replica(0, timeout=60.0))
            # new work during the drain lands on the survivor
            await asyncio.sleep(0.05)
            res2 = await router.dispatch_json(
                "/v1/completions",
                {"prompt": "1 2 3 4 5 6", "max_tokens": 4,
                 "temperature": 0.0})
            assert res2.status == 200
            assert router.replicas[1].counters["requests"] >= 1

            pieces, err, done = await consumer
            assert err is None and done
            assert "".join(pieces) == ref   # drained, not truncated
            assert await drainer
            assert router.replicas[0].state == EJECTED

            assert await router.restart_replica(0, timeout=60.0)
            assert router.replicas[0].state == HEALTHY
            hops = [(t["from"], t["to"])
                    for t in router.replicas[0].transitions]
            assert ("healthy", "draining") in hops
            assert ("draining", "ejected") in hops
            assert ("ejected", "probing") in hops
            assert ("probing", "healthy") in hops
            # the restarted replica takes traffic again
            res3 = await router.dispatch_json(
                "/v1/completions",
                {"prompt": "1 2 3 4 5 6", "max_tokens": 4,
                 "temperature": 0.0})
            assert res3.status == 200
        finally:
            await router.close()

    asyncio.run(scenario())


def test_crash_replica_connect_refused_eject_restart(cfg_params):
    """InProcessBackend.crash() behaves like a SIGKILL: established
    connections abort, new requests fail at the transport, the replica
    ejects, and restart() + the probe loop bring it back."""
    cfg, params = cfg_params

    async def scenario():
        b0 = InProcessBackend(_factory(cfg, params), _Tok(), "tiny")
        await b0.start()
        router = Router([b0], _rc(eject_after=1, reinstate_after=1))
        try:
            res = await router.dispatch_json(
                "/v1/completions", {"prompt": "1 2 3", "max_tokens": 4,
                                    "temperature": 0.0})
            assert res.status == 200
            await b0.crash()
            res = await router.dispatch_json(
                "/v1/completions", {"prompt": "1 2 3", "max_tokens": 4,
                                    "temperature": 0.0})
            assert res.status == 503   # transport death, no replica left
            assert router.replicas[0].state == EJECTED
            # probes keep failing against the corpse
            router.replicas[0].next_probe_t = 0.0
            await router.poll_once()
            assert router.replicas[0].state == EJECTED

            assert await router.restart_replica(0, timeout=60.0)
            res = await router.dispatch_json(
                "/v1/completions", {"prompt": "1 2 3", "max_tokens": 4,
                                    "temperature": 0.0})
            assert res.status == 200
        finally:
            await router.close()

    asyncio.run(scenario())


def test_slow_loris_replica_fault_injected(cfg_params):
    """The ReplicaSlowHealth fault on a REAL backend: the probe outlives
    its budget, the poll counts as failed, and the replica ejects —
    deterministic chaos without killing anything."""
    cfg, params = cfg_params

    async def scenario():
        inj = FaultInjector().inject("replica-health", ReplicaSlowHealth,
                                     times=None)
        b0 = InProcessBackend(_factory(cfg, params), _Tok(), "tiny",
                              injector=inj)
        await b0.start()
        router = Router([b0], _rc(eject_after=1, probe_timeout_s=0.2))
        try:
            await router.poll_once()
            assert router.replicas[0].state == EJECTED
            assert inj.fired >= 1
        finally:
            await router.close()

    asyncio.run(scenario())


# ---------------------------------------------------------------------------
# disaggregated prefill/decode (the PR 11 transportable-KV handoff)


FP8_EC = dict(EC, kv_storage="fp8")


def _fp8_factory(cfg, params):
    def make():
        return ServingEngine(cfg, params, EngineConfig(**FP8_EC)).start()
    return make


def _reference_text_fp8(cfg, params, prompt_ids, n_out=8) -> str:
    eng = ServingEngine(cfg, params, EngineConfig(**FP8_EC))
    r = Request(prompt_ids=list(prompt_ids), max_new_tokens=n_out)
    eng.submit(r)
    for _ in range(2000):
        eng._tick()
        if r.finish_reason is not None:
            break
    assert r.finish_reason is not None
    return _Tok().decode(list(stream_tokens(r, timeout=5)))


_DISAGG_PROMPT = " ".join(str((7 * i) % 131 or 1) for i in range(48))


def test_role_preference_routes_traffic_to_decode_replicas():
    """In a role-split fleet, client traffic prefers decode-capable
    replicas and only degrades onto a prefill-role replica when nothing
    else is routable — roles are advisory, never a shed."""
    async def scenario():
        b_pre, b_dec = FakeBackend("pre"), FakeBackend("dec",
                                                       queue_depth=5)
        router = Router([b_pre, b_dec], _rc(),
                        roles=["prefill", "decode"])
        await router.poll_once()
        # decode replica wins despite its heavier load
        res = await router.dispatch_json(
            "/v1/completions", {"prompt": "x y z", "max_tokens": 4})
        assert json.loads(res.payload)["served_by"] == "dec"
        # decode replica gone: the prefill replica serves rather than
        # shedding on principle
        router.replicas[1].eject(time.monotonic(), "test")
        res = await router.dispatch_json(
            "/v1/completions", {"prompt": "a b c", "max_tokens": 4})
        assert json.loads(res.payload)["served_by"] == "pre"
        # bad role specs fail loudly
        with pytest.raises(ValueError, match="roles"):
            Router([FakeBackend("x")], _rc(), roles=[])
        with pytest.raises(ValueError, match="unknown replica roles"):
            Router([FakeBackend("x")], _rc(), roles=["chef"])

    asyncio.run(scenario())


def test_disagg_handoff_e2e_bit_identity(cfg_params):
    """The disaggregated path end to end over REAL replicas (fp8 pools:
    e5m2 wire codes ship natively, so the handoff is lossless): the
    prefill replica computes + exports the prompt's pages, the decode
    replica imports them, inherits the affinity, and streams a
    bit-identical continuation having prefilled only the uncovered
    tail."""
    cfg, params = cfg_params
    ids = [int(x) for x in _DISAGG_PROMPT.split()]
    ref = _reference_text_fp8(cfg, params, ids)

    async def scenario():
        b_pre = InProcessBackend(_fp8_factory(cfg, params), _Tok(), "tiny")
        b_dec = InProcessBackend(_fp8_factory(cfg, params), _Tok(), "tiny")
        await b_pre.start()
        await b_dec.start()
        router = Router([b_pre, b_dec],
                        _rc(disagg_prefill_chars=16, stall_timeout_s=30.0),
                        roles=["prefill", "decode"])
        try:
            await router.poll_once()
            res = await router.dispatch_stream(
                "/v1/completions",
                {"prompt": _DISAGG_PROMPT, "max_tokens": 8,
                 "temperature": 0.0, "stream": True})
            assert isinstance(res, RouterStream)
            pieces, err, done = await _consume(res)
            assert err is None and done
            assert "".join(pieces).strip() == ref
            assert router.counters["handoffs"] == 1
            assert router.counters["handoff_failures"] == 0
            assert router.counters["handoff_bytes"] > 0
            # the pages really moved: exported by the prefill engine,
            # imported by the decode engine, and the stream's admission
            # prefix-hit them (only the tail prefilled there)
            assert b_pre.engine.metrics.get("kv_pages_exported", 0) == 1
            assert b_dec.engine.metrics.get("kv_pages_imported", 0) == 1
            assert b_dec.engine.metrics.get("prefix_hits", 0) == 1
            # the role split held: the prefill replica never served the
            # client stream (its only request was the handoff leg)
            assert b_dec.engine.metrics["requests"] == 1
            # aggregated /health shows the roles
            view = router.health_view()
            assert [r["role"] for r in view["replicas"]] == \
                ["prefill", "decode"]
        finally:
            await router.close()

    asyncio.run(scenario())


def test_disagg_midhandoff_death_is_zero_delivery_failover(cfg_params):
    """A replica dying MID-HANDOFF (either leg) is invisible to the
    client: zero tokens were delivered, so the router notes the health
    strike, counts handoff_failures, and serves the stream through the
    monolithic path — bit-identical text, no error event, no hang, no
    duplicate."""
    cfg, params = cfg_params
    ids = [int(x) for x in _DISAGG_PROMPT.split()]
    ref = _reference_text_fp8(cfg, params, ids)

    async def scenario():
        for victim in ("prefill", "decode"):
            inj = FaultInjector().inject("replica-handoff",
                                         ReplicaConnectRefused, times=1)
            b_pre = InProcessBackend(
                _fp8_factory(cfg, params), _Tok(), "tiny",
                injector=inj if victim == "prefill" else None)
            b_dec = InProcessBackend(
                _fp8_factory(cfg, params), _Tok(), "tiny",
                injector=inj if victim == "decode" else None)
            await b_pre.start()
            await b_dec.start()
            router = Router([b_pre, b_dec],
                            _rc(disagg_prefill_chars=16, eject_after=3,
                                stall_timeout_s=30.0),
                            roles=["prefill", "decode"])
            try:
                await router.poll_once()
                res = await router.dispatch_stream(
                    "/v1/completions",
                    {"prompt": _DISAGG_PROMPT, "max_tokens": 8,
                     "temperature": 0.0, "stream": True})
                assert isinstance(res, RouterStream), res
                pieces, err, done = await _consume(res)
                assert err is None and done, (victim, err)
                assert "".join(pieces).strip() == ref
                assert inj.fired == 1
                assert router.counters["handoffs"] == 0
                assert router.counters["handoff_failures"] == 1
                # the strike registered on the victim's health machine
                # (the fallback stream may then succeed on the same
                # replica and clear `fails` — the lifetime counter is
                # the monotonic record)
                idx = 0 if victim == "prefill" else 1
                assert router.replicas[idx].counters["failures"] >= 1
                assert router.counters["midstream_errors"] == 0
            finally:
                await router.close()

    asyncio.run(scenario())


# ---------------------------------------------------------------------------
# the full HTTP surface: router app on a port, replicas behind it


def _spin_fleet(cfg, params, n=2, rc=None):
    """Run a whole fleet (backends + router + router app) on a dedicated
    event-loop thread; returns (handle, router_port)."""
    from aiohttp import web

    loop = asyncio.new_event_loop()
    started = threading.Event()
    holder = {}

    async def boot():
        backends = [InProcessBackend(_factory(cfg, params), _Tok(), "tiny")
                    for _ in range(n)]
        for b in backends:
            await b.start()
        router = Router(backends, rc or _rc())
        await router.start()       # poll loop on: the live deployment
        runner = web.AppRunner(router.build_app())
        await runner.setup()
        site = web.TCPSite(runner, "127.0.0.1", 0)
        await site.start()
        holder["router"] = router
        holder["backends"] = backends
        holder["runner"] = runner
        holder["port"] = site._server.sockets[0].getsockname()[1]

    def run():
        asyncio.set_event_loop(loop)
        loop.run_until_complete(boot())
        started.set()
        loop.run_forever()

    threading.Thread(target=run, daemon=True).start()
    assert started.wait(120)
    holder["loop"] = loop
    return holder


def _stop_fleet(holder):
    loop = holder["loop"]

    async def teardown():
        await holder["router"].close()
        await holder["runner"].cleanup()

    fut = asyncio.run_coroutine_threadsafe(teardown(), loop)
    fut.result(timeout=30)
    loop.call_soon_threadsafe(loop.stop)


def _post(port, path, body, timeout=120):
    data = json.dumps(body).encode()
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}{path}", data=data,
        headers={"Content-Type": "application/json"})
    return urllib.request.urlopen(req, timeout=timeout)


def _get_json(port, path, timeout=30):
    return json.loads(urllib.request.urlopen(
        f"http://127.0.0.1:{port}{path}", timeout=timeout).read())


def test_router_http_surface_e2e(cfg_params):
    """Clients see a single transparent endpoint: OpenAI non-stream +
    SSE and TGI through the router match the engine's own surface, the
    aggregated /health shows every replica's state machine, and /metrics
    exposes router counters plus per-replica series."""
    cfg, params = cfg_params
    fleet = _spin_fleet(cfg, params, n=2)
    port = fleet["port"]
    try:
        ref = _reference_text(cfg, params, [1, 2, 3, 4, 5, 6])
        body = json.loads(_post(port, "/v1/completions", {
            "prompt": "1 2 3 4 5 6", "max_tokens": 8, "temperature": 0.0,
        }).read())
        assert body["object"] == "text_completion"
        assert body["choices"][0]["text"] == ref

        resp = _post(port, "/v1/completions", {
            "prompt": "1 2 3 4 5 6", "max_tokens": 8, "temperature": 0.0,
            "stream": True})
        pieces, saw_done = [], False
        for line in resp:
            line = line.decode().strip()
            if line == "data: [DONE]":
                saw_done = True
            elif line.startswith("data: "):
                j = json.loads(line[6:])
                if j["choices"][0].get("text"):
                    pieces.append(j["choices"][0]["text"])
        assert saw_done and "".join(pieces) == ref

        tgi = json.loads(_post(port, "/generate", {
            "inputs": "1 2 3 4 5 6",
            "parameters": {"max_new_tokens": 8}}).read())
        assert tgi["generated_text"] == ref

        health = _get_json(port, "/health")
        assert health["status"] == "ok"
        assert health["router"]["replicas_total"] == 2
        assert len(health["replicas"]) == 2
        for rep in health["replicas"]:
            assert rep["state"] == "healthy"
            # the poll loop carried the replica satellites up
            assert rep["replica"]["replica_id"]
            assert rep["replica"]["uptime_s"] > 0
            assert rep["replica"]["ticks"] > 0

        text = urllib.request.urlopen(
            f"http://127.0.0.1:{port}/metrics", timeout=30).read().decode()
        assert "ipex_llm_tpu_router_requests" in text
        assert 'replica="0"' in text and 'replica="1"' in text
        assert "ipex_llm_tpu_fleet_requests" in text

        models = _get_json(port, "/v1/models")
        assert models["data"][0]["id"] == "tiny"
    finally:
        _stop_fleet(fleet)


def test_replica_health_metrics_and_retry_after_satellites(cfg_params):
    """Single-replica satellites: /health carries the replica identity
    block (stable replica_id, uptime_s, monotonic ticks), /metrics is
    Prometheus-style with a replica_id label (JSON via ?format=json),
    and 429/503 sheds carry a DERIVED Retry-After."""
    from ipex_llm_tpu.serving.api_server import OpenAIServer

    cfg, params = cfg_params
    eng = ServingEngine(
        cfg, params,
        EngineConfig(max_rows=1, max_seq_len=512, page_size=32,
                     pool_pages=12, prefill_bucket=32, max_queue=3,
                     retry_backoff_s=0.001)).start()
    srv = OpenAIServer(eng, _Tok(), "tiny")

    loop = asyncio.new_event_loop()
    started = threading.Event()
    holder = {}

    def run():
        from aiohttp import web

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
    port = holder["port"]
    try:
        h1 = _get_json(port, "/health")
        rep = h1["replica"]
        assert rep["replica_id"] == srv.replica_id
        assert rep["uptime_s"] >= 0
        time.sleep(0.2)   # the engine keeps ticking even when idle
        h2 = _get_json(port, "/health")
        assert h2["replica"]["ticks"] > rep["ticks"]
        assert h2["replica"]["uptime_s"] > rep["uptime_s"]

        text = urllib.request.urlopen(
            f"http://127.0.0.1:{port}/metrics", timeout=30).read().decode()
        assert f'ipex_llm_tpu_requests{{replica_id="{srv.replica_id}"}}' \
            in text
        assert "ipex_llm_tpu_kv_pages_total" in text
        mj = _get_json(port, "/metrics?format=json")
        assert mj["replica_id"] == srv.replica_id
        assert "ticks" in mj["metrics"]

        # queue-derived Retry-After on the 429 path: occupy the single
        # row, fill the queue, then get shed
        results = {}

        def slow(name, n):
            try:
                results[name] = _post(port, "/v1/completions",
                                      {"prompt": "1 2 3",
                                       "max_tokens": n})
            except urllib.error.HTTPError as e:
                results[name] = e

        t1 = threading.Thread(target=slow, args=("r1", 200))
        t1.start()
        for _ in range(3000):
            if eng.metrics["requests"] >= 1:
                break
            time.sleep(0.01)
        threads = [threading.Thread(target=slow, args=(f"q{i}", 4))
                   for i in range(3)]
        for t in threads:
            t.start()
        for _ in range(500):
            if eng.queue_depth >= 3:
                break
            time.sleep(0.01)
        with pytest.raises(urllib.error.HTTPError) as ei:
            _post(port, "/v1/completions", {"prompt": "9",
                                            "max_tokens": 2})
        assert ei.value.code == 429
        ra = int(ei.value.headers["Retry-After"])
        # depth 3 over a 1-row engine: ceil(3/1)=3 waves
        assert ra == 3

        assert eng.drain(timeout=60)
        t1.join(60)
        for t in threads:
            t.join(60)
        with pytest.raises(urllib.error.HTTPError) as ei:
            _post(port, "/v1/completions", {"prompt": "9",
                                            "max_tokens": 2})
        assert ei.value.code == 503
        # draining Retry-After = what is left of the drain window (the
        # window is spent: clamped to the 1s floor... plus restart grace)
        assert 1 <= int(ei.value.headers["Retry-After"]) <= 61
    finally:
        loop.call_soon_threadsafe(loop.stop)
        eng.stop()


def test_deadline_s_rides_the_http_body(cfg_params):
    """The deadline the router stamps into the forwarded body reaches
    Request.deadline_s — an attempt under a nearly-spent budget times
    out (408) instead of running open-ended."""
    from ipex_llm_tpu.serving.api_server import OpenAIServer

    cfg, params = cfg_params
    eng = ServingEngine(cfg, params, EngineConfig(**EC)).start()
    srv = OpenAIServer(eng, _Tok(), "tiny")
    loop = asyncio.new_event_loop()
    started = threading.Event()
    holder = {}

    def run():
        from aiohttp import web

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
    try:
        with pytest.raises(urllib.error.HTTPError) as ei:
            _post(holder["port"], "/v1/completions",
                  {"prompt": "1 2 3", "max_tokens": 64,
                   "deadline_s": 0.001})
        assert ei.value.code == 408
    finally:
        loop.call_soon_threadsafe(loop.stop)
        eng.stop()


# ---------------------------------------------------------------------------
# the chaos gate (process-kill tier; the deterministic in-process chaos
# rides the fast tests above)


@pytest.mark.slow
def test_chaos_gate_sigkill_one_of_three(tmp_path):
    """The acceptance gate: SIGKILL one of 3 replica PROCESSES mid-wave —
    every zero-token request completes via failover, every mid-stream
    casualty gets a terminal error object, zero hangs, zero duplicated
    tokens, and the restarted replica reinstates with the transitions
    visible in the router's aggregated health view."""
    from tests.replica_chaos import chaos_replicas

    row, passed = chaos_replicas(n_reqs=8, n_out=24)
    assert passed, row
    assert row["faults_injected"] == 1
    assert row["hangs"] == 0
    assert row["failovers"] >= 1
    assert row["reinstated"]
