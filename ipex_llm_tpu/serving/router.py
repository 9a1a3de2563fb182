"""Replica-fault-tolerant serving tier: a front router over N engine
replicas.

PR 3 made the REQUEST the unit of failure inside one engine; this tier
makes the REPLICA the next blast-radius boundary up (ROADMAP item 3 — the
millions-of-users shape).  The reference stack's FastChat controller +
worker tier load-balances but has no failover semantics: a dead worker
drops its streams.  Here, losing a replica mid-wave is an observable,
bounded, mostly-invisible event:

- **Health state machine** per replica (healthy → suspect → ejected →
  probing → reinstated), driven by periodic ``/health`` polls AND
  per-request transport outcomes, with exponential probe backoff — a
  circuit breaker: a crashed or wedged replica stops receiving traffic
  within one probe interval, and a restarted one reinstates itself via
  the probe loop without operator action.
- **Failover with a safe-replay contract**: a request that fails before
  any token was delivered replays on another replica under its REMAINING
  deadline budget (the deadline spans attempts; attempts are bounded); a
  mid-stream death surfaces the same terminal SSE/JSON error objects the
  engine tier defined (PR 3) — never a silent truncation, and never a
  duplicated token (at-most-once delivery: the router only replays
  streams that have delivered nothing).
- **Backpressure propagation**: replica 429/503 responses feed routing —
  a shedding replica is skipped for a cooloff (and its ``Retry-After``
  hint honored) instead of ejected; routing is least-loaded with
  prefix-affinity (prompt-prefix hash → the replica that last served the
  prefix, validated against its ``/health`` kv block: if the replica has
  since evicted prefix pages or its pool is under pressure, affinity
  gracefully spills to least-loaded — soft affinity, never a hard pin).
  The router's own inbox is bounded (``max_inflight``); beyond it the
  router sheds with 429 + ``Retry-After``.
- **Rolling drain orchestration**: ``drain_replica(i)`` stops routing to
  a replica, drains it, and (for in-process backends) ``restart_replica``
  rebuilds it — the probe loop reinstates it when its ``/health`` comes
  back, while the other replicas absorb the load.

Two backends behind one protocol: ``InProcessBackend`` (N engines in THIS
process, each behind its own ``OpenAIServer`` on a loopback port — one
weight upload serves the whole fleet, and tests/chaos can crash, drain,
and restart replicas deterministically) and ``HTTPBackend`` (remote
``api_server`` processes — the multi-process / multi-host deployment).
Both speak the existing OpenAI/TGI surface, so the router is transparent:
clients point at the router port and see the same endpoints, the same SSE
framing, and the same error objects as a single replica.

All host-side: no new jitted programs; the per-engine tick stays one
dispatch (JP106).

Run (in-process fleet):
    python -m ipex_llm_tpu.serving.router --model <ckpt> \
        --replicas 3 --router-port 8080
Run (fronting remote replicas):
    python -m ipex_llm_tpu.serving.router \
        --replicas http://h1:8000,http://h2:8000 --router-port 8080
"""

from __future__ import annotations

import argparse
import asyncio
import hashlib
import json
import time
import uuid
from collections import OrderedDict, deque
from dataclasses import dataclass, field
from typing import Any, AsyncIterator, Callable

try:
    import aiohttp
    from aiohttp import web
except ImportError as _e:  # pragma: no cover
    aiohttp = None
    web = None
    _AIOHTTP_ERR = _e

from ipex_llm_tpu.serving.faults import (FaultInjector, ReplicaConnectRefused,
                                         ReplicaFault, ReplicaSlowHealth,
                                         ReplicaStreamHang)
from ipex_llm_tpu.serving.observe import (LATENCY_BUCKETS_S, Histogram,
                                          Tracer, make_traceparent,
                                          new_trace_id, parse_traceparent,
                                          span)

__all__ = [
    "Backend",
    "BackendError",
    "HTTPBackend",
    "InProcessBackend",
    "Router",
    "RouterConfig",
    "RouterResponse",
    "RouterStream",
    "HEALTHY", "SUSPECT", "EJECTED", "PROBING", "DRAINING",
]

# Replica health states.  HEALTHY/SUSPECT are routable; EJECTED/PROBING/
# DRAINING receive no traffic.  SUSPECT is the one-strike warning state:
# still routable (a single transport blip must not halve a two-replica
# fleet), but one more failure ejects.
HEALTHY = "healthy"
SUSPECT = "suspect"
EJECTED = "ejected"
PROBING = "probing"
DRAINING = "draining"
ROUTABLE_STATES = (HEALTHY, SUSPECT)


class BackendError(RuntimeError):
    """Transport-level replica failure (connect refused/reset, mid-stream
    drop, stall past the router's silence budget) — the failures the
    ROUTER owns, as opposed to replica-AUTHORED error responses (408/500
    JSON bodies, in-stream error events), which are forwarded verbatim."""

    def __init__(self, message: str, stage: str = "connect"):
        super().__init__(message)
        self.stage = stage   # "connect" | "read" | "stall"


@dataclass
class SSEOpen:
    """Outcome of opening a streaming request against a replica: either a
    live SSE event iterator (``events``) or a complete non-SSE response
    the replica answered instead (shed/error — ``payload``)."""

    status: int
    headers: dict
    payload: bytes | None = None
    events: AsyncIterator[bytes] | None = None


@dataclass(frozen=True)
class RouterConfig:
    # health machinery
    probe_interval_s: float = 1.0    # /health poll period per routable replica
    probe_timeout_s: float = 2.0     # poll/probe budget (slow-loris guard)
    suspect_after: int = 1           # consecutive failures → suspect
    eject_after: int = 2             # consecutive failures → ejected
    probe_backoff_s: float = 0.5     # first re-probe delay after ejection
    probe_backoff_max_s: float = 8.0
    reinstate_after: int = 1         # consecutive probe successes → healthy
    wedge_timeout_s: float = 300.0   # a replica whose /health answers ok
    #                                  but whose `ticks` counter stays
    #                                  frozen this long (uptime advancing)
    #                                  counts as a FAILED poll: the engine
    #                                  loop ticks even when idle, so a
    #                                  frozen tick = a wedged engine with
    #                                  a live HTTP thread.  Generous by
    #                                  default because one tick can
    #                                  legitimately stall through a long
    #                                  jit compile.  0 disables.
    # failover
    max_attempts: int = 3            # replicas tried per request (transport)
    stall_timeout_s: float = 60.0    # max mid-stream silence before the
    #                                  stream counts as a replica death
    first_event_timeout_s: float = 300.0  # separate (larger) silence
    #                                  budget for the FIRST event: cold
    #                                  TTFT includes jit compilation, and
    #                                  a healthy-but-compiling replica
    #                                  must not read as a death
    request_timeout_s: float = 600.0  # non-streaming total budget when the
    #                                   request carries no deadline
    request_deadline_s: float = 0.0  # default end-to-end budget spanning
    #                                  ALL attempts (0 = none; per-request
    #                                  body["deadline_s"] overrides)
    # backpressure + routing
    max_inflight: int = 0            # router inbox bound (0 = unbounded)
    shed_cooloff_s: float = 0.25     # skip a 429/503 replica this long when
    #                                  it sent no Retry-After hint
    affinity_prefix_chars: int = 64  # prompt-prefix window the key hashes
    affinity_max_entries: int = 4096
    affinity_free_frac: float = 0.05  # kv pool pressure spill threshold:
    #                                   below this free-page fraction the
    #                                   prefix is likely evicted soon —
    #                                   spill to least-loaded (unless the
    #                                   replica's host spill tier covers
    #                                   the demoted pages; see
    #                                   _affinity_fresh)
    # disaggregated prefill/decode (requires per-replica roles): a
    # streaming request whose prompt text is at least this many chars
    # hands off — a prefill-role replica computes the pages
    # (/kv/prefill), a decode-role replica imports them (/kv/import) and
    # inherits the prompt's affinity, so the stream routed there joins
    # the fused tick with only the uncovered tail left to prefill.
    # Every handoff failure is a zero-delivery fallback to the
    # monolithic path.  0 disables handoff.
    disagg_prefill_chars: int = 0
    handoff_timeout_s: float = 120.0  # per-handoff-leg budget
    # request-lifecycle tracing (serving/observe.py): the router records
    # its OWN spans per request — route attempts, backpressure
    # re-routes, failover replays, both disagg handoff legs — keyed by
    # the W3C traceparent trace id it either receives from the client or
    # mints, and propagates the traceparent to the replica (carried in
    # the forwarded body; HTTPBackend promotes it to a real HTTP
    # header), so /trace/{id} assembles the request's whole life across
    # processes.  Pure host bookkeeping per attempt; False turns the
    # router tracer off entirely.
    tracing: bool = True
    trace_buffer: int = 512          # traces the router retains (LRU)
    # shared-token authn for the /kv/import handoff leg: forwarded as
    # the X-KV-Import-Token header so replicas started with
    # --kv-import-token accept the router's page sets while rejecting
    # unauthenticated callers (integrity != authn: a checksum-consistent
    # blob from anyone would otherwise poison the shared prefix cache).
    kv_import_token: str | None = None


class _Replica:
    """Router-side record of one backend: health state machine, load and
    backpressure signals, and the transition log the aggregated /health
    view exposes."""

    def __init__(self, idx: int, backend: "Backend", rc: RouterConfig,
                 role: str = "any"):
        self.idx = idx
        self.backend = backend
        self.rc = rc
        # disaggregation role: "any" serves everything (the default —
        # a monolithic fleet), "prefill" only takes /kv/prefill handoff
        # legs, "decode" only client streams/completions.  Advisory
        # under degradation: with no decode-capable replica routable,
        # a prefill replica still serves rather than shedding.
        self.role = role
        self.state = HEALTHY
        self.fails = 0             # consecutive poll/request failures
        self.probe_ok = 0          # consecutive successful probes (ejected)
        self.backoff_s = rc.probe_backoff_s
        self.next_probe_t = 0.0
        self.last_poll_t = -1e9
        self.polling = False       # a poll/probe coroutine is in flight
        self.inflight = 0          # requests the router routed here, live
        self.shed_until = 0.0      # backpressure memory (429/503 cooloff)
        self.last_health: dict | None = None
        # handoff capability memory: set when this replica proved unable
        # to import a page set (no binary transport, or a permanent
        # shape/format 400) — the handoff orchestration stops paying a
        # full prefill leg just to throw its blob at a replica that
        # cannot take it.  Cleared on reinstatement (a restart may fix
        # shape/version skew).
        self.handoff_broken = False
        self.transitions: "deque[dict]" = deque(maxlen=64)
        # wedge detection: the last distinct `ticks` value seen in a
        # healthy poll and when it changed (per replica_id incarnation)
        self.ticks_seen: tuple[str, int, float] | None = None
        self.counters = {"requests": 0, "failures": 0, "shed": 0,
                         "probes": 0}

    def routable(self, now: float) -> bool:
        return self.state in ROUTABLE_STATES and now >= self.shed_until

    def load(self) -> float:
        """Least-loaded signal: what the router routed here and hasn't
        seen finish, plus the replica's own reported admission backlog."""
        depth = 0
        if self.last_health:
            depth = self.last_health.get("fault_domain", {}).get(
                "queue_depth", 0)
        return self.inflight + depth

    def _move(self, to: str, reason: str):
        if to == self.state:
            return
        self.transitions.append({"t": round(time.time(), 3),
                                 "from": self.state, "to": to,
                                 "reason": reason})
        self.state = to

    # -- state machine inputs ------------------------------------------------

    def on_success(self, now: float, health: dict | None = None):
        self.fails = 0
        if health is not None:
            self.last_health = health
        if self.state == SUSPECT:
            self._move(HEALTHY, "recovered")

    def on_failure(self, now: float, reason: str):
        self.counters["failures"] += 1
        self.fails += 1
        if self.state in ROUTABLE_STATES:
            if self.fails >= self.rc.eject_after:
                self.eject(now, reason)
            elif self.state == HEALTHY and self.fails >= self.rc.suspect_after:
                self._move(SUSPECT, reason)

    def eject(self, now: float, reason: str):
        """Circuit open: no traffic until the probe loop reinstates."""
        self._move(EJECTED, reason)
        self.probe_ok = 0
        self.backoff_s = self.rc.probe_backoff_s
        self.next_probe_t = now + self.backoff_s

    def wedged(self, health: dict, now: float) -> bool:
        """True when this ok-answering replica's engine loop is frozen:
        `ticks` unchanged for ``wedge_timeout_s`` while the HTTP thread
        keeps serving /health — the wedge shape a liveness-only check
        can't see (the engine loop ticks even when idle, so a healthy
        replica's counter always moves)."""
        if self.rc.wedge_timeout_s <= 0:
            return False
        blk = health.get("replica") or {}
        rid, ticks = blk.get("replica_id"), blk.get("ticks")
        if rid is None or ticks is None:
            return False
        if (self.ticks_seen is None or self.ticks_seen[0] != rid
                or self.ticks_seen[1] != ticks):
            self.ticks_seen = (rid, ticks, now)
            return False
        return now - self.ticks_seen[2] > self.rc.wedge_timeout_s

    def on_probe_result(self, now: float, health: dict | None):
        """Ejected-replica probe outcome: success counts toward
        reinstatement, failure doubles the backoff (bounded)."""
        if health is not None:
            self.last_health = health
            self.probe_ok += 1
            if self.probe_ok >= self.rc.reinstate_after:
                self.fails = 0
                self.backoff_s = self.rc.probe_backoff_s
                self.handoff_broken = False   # a restart may have fixed it
                self._move(HEALTHY, "reinstated")
                return
            self._move(EJECTED, "probe_ok")   # more successes required
        else:
            self.probe_ok = 0
            self.backoff_s = min(self.backoff_s * 2,
                                 self.rc.probe_backoff_max_s)
            self._move(EJECTED, "probe_failed")
        self.next_probe_t = now + self.backoff_s

    def view(self, now: float) -> dict:
        """The aggregated-/health row for this replica."""
        out = {
            "idx": self.idx,
            "target": self.backend.target,
            "role": self.role,
            "state": self.state,
            "routable": self.routable(now),
            "inflight": self.inflight,
            "consecutive_failures": self.fails,
            "shed_cooloff": self.shed_until > now,
            "counters": dict(self.counters),
            "transitions": list(self.transitions),
        }
        if self.last_health is not None:
            out["replica"] = self.last_health.get("replica", {})
            out["status"] = self.last_health.get("status")
            out["kv"] = self.last_health.get("kv", {})
            out["fault_domain"] = self.last_health.get("fault_domain", {})
        return out


# ---------------------------------------------------------------------------
# Backends


class Backend:
    """Protocol one replica speaks to the router (duck-typed; subclass or
    imitate — the unit tests drive the router with scripted fakes):

    - ``target``: human-readable address for logs and /health
    - ``probe()``             -> parsed /health dict (raises on failure)
    - ``fetch_metrics()``     -> parsed /metrics?format=json dict
    - ``send_json(path, body, timeout)`` -> (status, headers, payload)
    - ``open_sse(path, body, stall_timeout_s, first_event_timeout_s)``
      -> SSEOpen (the first-event bound covers cold-compile TTFT)
    - ``get_json(path)``      -> (status, payload)  (GET passthrough)
    - ``drain(timeout)``      -> bool (best-effort; HTTP backends rely on
                                  the replica's own SIGTERM handler)
    - ``close()``

    Transport failures raise ``BackendError``; anything the replica
    ANSWERS (any HTTP status, any SSE event) is returned, not raised.
    Each backend may carry its own ``FaultInjector`` scoped to the
    replica-tier sites (``REPLICA_FAULT_SITES``) — deterministic chaos
    without killing processes."""

    target = "?"
    injector: FaultInjector | None = None
    # shared-token authn for /kv/import, set by the router from its
    # config: transports that speak real HTTP forward it as the
    # X-KV-Import-Token header
    kv_import_token: str | None = None

    def _fault(self, site: str):
        """Guarded replica-tier site: translate an injected ReplicaFault
        into the transport behaviour it models.  ``ReplicaStreamHang``
        and ``ReplicaSlowHealth`` are raised through to the call sites
        that know how to stall; connect faults become BackendError
        here."""
        if self.injector is None:
            return
        try:
            self.injector.hit(site, (self.target,))
        except ReplicaConnectRefused as e:
            raise BackendError(f"injected: {e}", stage="connect")

    async def send_bytes(self, path: str, data: bytes,
                         timeout: float) -> tuple[int, dict, bytes]:
        """Binary POST (the /kv/import handoff leg).  Backends that
        don't speak it surface an "unsupported"-stage BackendError: the
        handoff orchestration treats that as a capability gap (no
        health strike — the replica is healthy, just not
        binary-capable) and falls back to the monolithic path."""
        raise BackendError(f"{type(self).__name__} does not support "
                           "binary transport", stage="unsupported")

    async def drain(self, timeout: float = 30.0) -> bool:
        return False

    async def close(self):
        pass


class HTTPBackend(Backend):
    """A remote ``api_server`` replica reached over HTTP (the
    multi-process / multi-host deployment)."""

    def __init__(self, base_url: str,
                 injector: FaultInjector | None = None):
        if aiohttp is None:  # pragma: no cover
            raise ImportError(
                f"aiohttp is required for the router: {_AIOHTTP_ERR}")
        self.base_url = base_url.rstrip("/")
        self.target = self.base_url
        self.injector = injector
        self._session: aiohttp.ClientSession | None = None

    async def _sess(self) -> "aiohttp.ClientSession":
        # created lazily inside the running loop (a session binds to it)
        if self._session is None or self._session.closed:
            self._session = aiohttp.ClientSession()
        return self._session

    async def probe(self, timeout: float = 2.0) -> dict:
        try:
            self._fault("replica-health")
        except ReplicaSlowHealth:
            # slow-loris: the probe outlives any reasonable budget; the
            # router's wait_for() is what trips (sleep is cancellable)
            await asyncio.sleep(max(timeout, 1.0) * 10)
            raise BackendError("injected slow-loris /health", stage="stall")
        sess = await self._sess()
        try:
            async with sess.get(
                f"{self.base_url}/health",
                timeout=aiohttp.ClientTimeout(total=timeout),
            ) as resp:
                body = await resp.json()
                if resp.status != 200:
                    raise BackendError(
                        f"/health {resp.status}: {body}", stage="read")
                return body
        except (aiohttp.ClientError, asyncio.TimeoutError, OSError) as e:
            raise BackendError(f"/health: {type(e).__name__}: {e}",
                               stage="connect")

    async def fetch_metrics(self, timeout: float = 2.0) -> dict:
        sess = await self._sess()
        try:
            async with sess.get(
                f"{self.base_url}/metrics?format=json",
                timeout=aiohttp.ClientTimeout(total=timeout),
            ) as resp:
                return await resp.json()
        except (aiohttp.ClientError, asyncio.TimeoutError, OSError) as e:
            raise BackendError(f"/metrics: {type(e).__name__}: {e}",
                               stage="connect")

    async def get_json(self, path: str, timeout: float = 10.0):
        sess = await self._sess()
        try:
            async with sess.get(
                f"{self.base_url}{path}",
                timeout=aiohttp.ClientTimeout(total=timeout),
            ) as resp:
                return resp.status, await resp.read()
        except (aiohttp.ClientError, asyncio.TimeoutError, OSError) as e:
            raise BackendError(f"GET {path}: {type(e).__name__}: {e}",
                               stage="connect")

    @staticmethod
    def _tp_headers(body: dict) -> dict:
        """Promote a forwarded-body ``traceparent`` to the real W3C HTTP
        header (the Backend protocol stays body-shaped so scripted test
        backends need no transport knowledge; the wire speaks the
        standard header either way)."""
        tp = body.get("traceparent")
        return {"traceparent": str(tp)} if tp else {}

    async def send_json(self, path: str, body: dict,
                        timeout: float) -> tuple[int, dict, bytes]:
        """Non-streaming request: the whole response body is read before
        anything reaches the client, so the caller may always replay."""
        self._fault("replica-connect")
        sess = await self._sess()
        try:
            async with sess.post(
                f"{self.base_url}{path}", json=body,
                headers=self._tp_headers(body),
                timeout=aiohttp.ClientTimeout(total=timeout),
            ) as resp:
                payload = await resp.read()
                return resp.status, dict(resp.headers), payload
        except asyncio.TimeoutError:
            raise BackendError("response timed out", stage="stall")
        except (aiohttp.ClientError, OSError) as e:
            raise BackendError(f"{type(e).__name__}: {e}", stage="connect")

    async def send_bytes(self, path: str, data: bytes,
                         timeout: float) -> tuple[int, dict, bytes]:
        self._fault("replica-connect")
        sess = await self._sess()
        hdrs = {"Content-Type": "application/octet-stream"}
        if self.kv_import_token:
            hdrs["X-KV-Import-Token"] = self.kv_import_token
        try:
            async with sess.post(
                f"{self.base_url}{path}", data=data,
                headers=hdrs,
                timeout=aiohttp.ClientTimeout(total=timeout),
            ) as resp:
                payload = await resp.read()
                return resp.status, dict(resp.headers), payload
        except asyncio.TimeoutError:
            raise BackendError("response timed out", stage="stall")
        except (aiohttp.ClientError, OSError) as e:
            raise BackendError(f"{type(e).__name__}: {e}", stage="connect")

    async def open_sse(self, path: str, body: dict,
                       stall_timeout_s: float,
                       first_event_timeout_s: float | None = None) -> SSEOpen:
        self._fault("replica-connect")
        sess = await self._sess()
        try:
            # headers are bounded by the STALL budget, not the first-event
            # one: our replicas prepare the SSE response before any model
            # work, so headers not arriving means a wedged process (the
            # SIGSTOP shape), not a cold compile — and an unbounded wait
            # here would hold a router inflight slot forever
            resp = await asyncio.wait_for(
                sess.post(
                    f"{self.base_url}{path}", json=body,
                    headers=self._tp_headers(body),
                    # no total timeout: a stream lives as long as it
                    # emits; silence is bounded per-read below instead
                    timeout=aiohttp.ClientTimeout(
                        total=None, sock_connect=5.0),
                ),
                stall_timeout_s)
        except (aiohttp.ClientError, asyncio.TimeoutError, OSError) as e:
            raise BackendError(f"{type(e).__name__}: {e}", stage="connect")
        ctype = resp.headers.get("Content-Type", "")
        if resp.status != 200 or "text/event-stream" not in ctype:
            # the non-SSE body read is bounded and wrapped too: a replica
            # that sends shed/error headers then wedges (or dies, RST)
            # mid-body must surface as a replayable transport failure,
            # not an unbounded await or a naked aiohttp exception
            try:
                payload = await asyncio.wait_for(resp.read(),
                                                 stall_timeout_s)
            except (aiohttp.ClientError, asyncio.TimeoutError, OSError) as e:
                resp.release()
                raise BackendError(f"{type(e).__name__}: {e}",
                                   stage="read")
            resp.release()
            return SSEOpen(resp.status, dict(resp.headers), payload=payload)
        return SSEOpen(resp.status, dict(resp.headers),
                       events=self._events(resp, stall_timeout_s,
                                           first_event_timeout_s))

    async def _events(self, resp, stall_timeout_s: float,
                      first_event_timeout_s: float | None = None):
        """Yield raw SSE event blocks (``data: ...\\n\\n``) with a
        per-read silence bound: a replica that stops mid-stream (wedged
        process, dead socket) surfaces as a stall BackendError instead
        of a client hang.  The FIRST event gets its own (larger) bound —
        cold TTFT includes jit compiles, which must not read as death."""
        first_bound = max(first_event_timeout_s or 0.0, stall_timeout_s)
        buf = b""
        yielded = False
        try:
            while True:
                bound = stall_timeout_s if yielded else first_bound
                try:
                    self._fault("replica-stream")
                except ReplicaStreamHang:
                    # wedge emulation with the same latency as a real
                    # stall: silence for exactly the bound, then the
                    # same BackendError the wait_for below raises
                    await asyncio.sleep(bound)
                    raise BackendError("injected mid-stream hang",
                                       stage="stall")
                try:
                    chunk = await asyncio.wait_for(resp.content.readany(),
                                                   bound)
                except asyncio.TimeoutError:
                    raise BackendError(
                        f"stream stalled > {bound}s", stage="stall")
                except (aiohttp.ClientError, OSError,
                        ConnectionResetError) as e:
                    raise BackendError(f"{type(e).__name__}: {e}",
                                       stage="read")
                if not chunk:
                    if buf.strip():
                        # FIN mid-event: the replica died while writing a
                        # block.  Forwarding the fragment as a "clean end"
                        # would be exactly the silent truncation the
                        # failover contract forbids — surface it as a
                        # read-stage death instead (zero-delivery streams
                        # then fail over; committed ones get the terminal
                        # error event)
                        raise BackendError(
                            "connection closed mid-event "
                            f"({len(buf)} bytes of unframed trailing "
                            "data)", stage="read")
                    return
                buf += chunk
                while b"\n\n" in buf:
                    block, buf = buf.split(b"\n\n", 1)
                    yield block + b"\n\n"
                    yielded = True
        finally:
            resp.release()

    async def close(self):
        if self._session is not None and not self._session.closed:
            await self._session.close()


class InProcessBackend(HTTPBackend):
    """N engines in ONE process: each replica is a real ``OpenAIServer``
    on its own loopback port around an engine built by ``engine_factory``
    — one weight upload serves the whole fleet, and the router (or a
    test/chaos harness) can ``crash()``, ``drain()`` and ``restart()``
    replicas deterministically.  Transport is the same HTTP/SSE path as
    a remote replica, so behaviour matches the multi-process deployment
    byte-for-byte."""

    def __init__(self, engine_factory: Callable[[], Any], tokenizer,
                 model_name: str = "fleet",
                 injector: FaultInjector | None = None,
                 kv_import_token: str | None = None):
        super().__init__("http://127.0.0.1:0", injector=injector)
        self.engine_factory = engine_factory
        self.tokenizer = tokenizer
        self.model_name = model_name
        # token the replica's /kv/import REQUIRES (distinct from the
        # inherited kv_import_token attr the router sets for sending)
        self.require_kv_import_token = kv_import_token
        self.engine = None
        self.server = None
        self._runner = None
        self._site = None
        self.port = 0

    async def start(self):
        from ipex_llm_tpu.serving.api_server import OpenAIServer

        self.engine = self.engine_factory()
        self.server = OpenAIServer(self.engine, self.tokenizer,
                                   self.model_name,
                                   kv_import_token=self
                                   .require_kv_import_token)
        self._runner = web.AppRunner(self.server.app, shutdown_timeout=1.0)
        await self._runner.setup()
        self._site = web.TCPSite(self._runner, "127.0.0.1", self.port)
        await self._site.start()
        self.port = self._site._server.sockets[0].getsockname()[1]
        self.base_url = f"http://127.0.0.1:{self.port}"
        self.target = self.base_url
        return self

    async def crash(self):
        """SIGKILL emulation: stop accepting connections, ABORT every
        established connection (RST, the way a killed process drops
        them — closing only the listening socket would leave keep-alive
        clients talking to handlers with a dead engine), and kill the
        engine thread.  No drain, no goodbyes."""
        if self._site is not None and self._site._server is not None:
            self._site._server.close()
        if self.engine is not None:
            self.engine._stop.set()
        server = getattr(self._runner, "server", None)
        for conn in list(getattr(server, "connections", []) or []):
            transport = getattr(conn, "transport", None)
            if transport is not None:
                transport.abort()

    async def drain(self, timeout: float = 30.0) -> bool:
        """Graceful engine drain off the event loop (the engine's drain
        blocks); /health reports "draining" for the duration, so the
        poll loop sees the replica leaving."""
        if self.engine is None:
            return False
        loop = asyncio.get_running_loop()
        return await loop.run_in_executor(None, self.engine.drain, timeout)

    async def restart(self):
        """Tear down whatever is left (crashed or drained) and bring up a
        fresh engine + server on the SAME port, so the router's probe
        loop finds the replica where it left it."""
        if self.engine is not None:
            self.engine.stop()
        if self._runner is not None:
            try:
                await self._runner.cleanup()
            except Exception:
                pass   # a crashed site may already be half-closed
        await self.start()
        return self

    async def close(self):
        if self.engine is not None:
            self.engine._stop.set()
        if self._runner is not None:
            try:
                await self._runner.cleanup()
            except Exception:
                pass
        await super().close()


# ---------------------------------------------------------------------------
# Router


@dataclass
class RouterResponse:
    """A complete (non-streaming) outcome to relay to the client."""

    status: int
    payload: bytes
    headers: dict = field(default_factory=dict)


class RouterStream:
    """A live SSE stream to relay: ``events`` yields raw event blocks
    (the first one already acquired — failover is settled by the time a
    RouterStream exists).  ``close()`` abandons the stream and releases
    its router bookkeeping even if the relay never started (an unstarted
    async generator's ``finally`` does NOT run on ``aclose`` — the
    idempotent ``release`` closure is what guarantees the inflight slot
    comes back)."""

    def __init__(self, events: AsyncIterator[bytes], release=None,
                 upstream: AsyncIterator[bytes] | None = None):
        self.events = events
        self._release = release
        self._upstream = upstream

    async def close(self):
        await self.events.aclose()
        if self._upstream is not None:
            # the relay's finally closes the upstream too, but only if
            # the relay STARTED; closing an already-closed generator is a
            # no-op, so this covers the never-iterated case (client gone
            # before the first write) without double-close hazards —
            # releasing the replica's SSE response aborts its engine row
            await self._upstream.aclose()
        if self._release is not None:
            self._release()


def _surface(path: str) -> str:
    return "tgi" if path.startswith("/generate") else "openai"


def _error_payload(surface: str, message: str, code: str,
                   err_type: str) -> bytes:
    if surface == "tgi":
        return json.dumps({"error": message,
                           "error_type": code}).encode()
    return json.dumps({"error": {"message": message, "type": err_type,
                                 "code": code}}).encode()


# Replica series whose fleet-wide SUM is meaningful (true counters /
# occupancy).  Gauges and ratios (uptime_s, tokens_per_sync,
# accept rates, ttft percentiles...) are exported per-replica only —
# summing them across a fleet reads as nonsense on a dashboard.
_FLEET_SUMMABLE = frozenset({
    "requests", "tokens", "steps", "ticks", "retries", "rejected",
    "timeouts", "errors_isolated", "host_syncs", "mixed_steps",
    "mixed_fused_steps",
    "draft_proposed", "draft_accepted", "queue_depth",
    "kv_pages_in_use", "kv_pages_total", "kv_pool_bytes",
    "kv_prefix_evictions", "kv_alloc_fail_clamps",
    # spill tier + transport (the kv_ prefix is the replica's kv_stats
    # exposition; pages_imported/exported live in engine.metrics)
    "kv_spill_pages", "kv_spill_bytes", "kv_spills", "kv_swap_ins",
    "kv_swap_in_lookups", "kv_pages_imported", "kv_pages_exported",
    # device-time observatory (serving/perfwatch.py): the recompile-
    # sentinel series are true counters — a fleet sum of
    # perf_compiles_warm/out_of_grid > 0 is the one-glance "somebody is
    # recompiling mid-serving" signal; attributed ticks/compile seconds
    # sum the same way (MFU and per-family device_s are ratios/gauges,
    # per-replica only)
    "perf_compiles_total", "perf_compiles_warm",
    "perf_compiles_out_of_grid", "perf_compile_s_total",
    "perf_ticks_attributed", "perf_dispatch_mismatches",
})


class Router:
    """Front-tier async router: load-balances the OpenAI/TGI surface over
    N replicas with health-driven ejection, safe failover replay,
    backpressure propagation and prefix-affinity routing.  See the module
    docstring for the four robustness contracts."""

    def __init__(self, backends: list, rc: RouterConfig | None = None,
                 roles: list[str] | None = None):
        if not backends:
            raise ValueError("router needs at least one backend")
        self.rc = rc or RouterConfig()
        if roles is None:
            roles = ["any"] * len(backends)
        if len(roles) != len(backends):
            raise ValueError(
                f"{len(roles)} roles for {len(backends)} backends")
        bad = [r for r in roles if r not in ("any", "prefill", "decode")]
        if bad:
            raise ValueError(f"unknown replica roles {bad!r}: each must "
                             "be 'any', 'prefill', or 'decode'")
        self.replicas = [_Replica(i, b, self.rc, role=role)
                         for i, (b, role) in enumerate(zip(backends,
                                                           roles))]
        self.router_id = uuid.uuid4().hex
        # request-lifecycle tracing (observe.py): the router's own spans,
        # keyed by the traceparent trace id it receives or mints; the
        # /trace/{id} endpoint merges these with every replica's spans
        self.tracer = (Tracer(self.rc.trace_buffer)
                       if self.rc.tracing else None)
        # honest handoff-leg latency histograms (Prometheus
        # _bucket/_sum/_count on /metrics) — the two legs are the disagg
        # path's operational cost and had no distribution until now
        self.hists = {
            "handoff_prefill_s": Histogram(LATENCY_BUCKETS_S),
            "handoff_import_s": Histogram(LATENCY_BUCKETS_S),
        }
        for b in backends:
            # transports forward this as the X-KV-Import-Token header
            b.kv_import_token = self.rc.kv_import_token
        self._inflight = 0
        self._affinity: "OrderedDict[str, tuple[int, int]]" = OrderedDict()
        self._poll_task: asyncio.Task | None = None
        self._closed = False
        self.counters = {
            "requests": 0,          # requests accepted into the router
            "shed": 0,              # shed at the router (inbox/no replica)
            "failovers": 0,         # zero-token replays on another replica
            "rerouted_backpressure": 0,   # replica 429/503 -> other replica
            "midstream_errors": 0,  # terminal error events the router wrote
            "affinity_hits": 0,
            "affinity_spills": 0,   # stale/pressured affinity → least-loaded
            "probes": 0,
            "ejections": 0,
            "reinstated": 0,
            # disaggregated prefill/decode handoffs: completed page-set
            # moves, zero-delivery fallbacks to the monolithic path, and
            # the wire bytes shipped (the e5m2-halving story's meter)
            "handoffs": 0,
            "handoff_failures": 0,
            "handoff_bytes": 0,
        }

    # -- lifecycle -----------------------------------------------------------

    async def start(self):
        for rep in self.replicas:
            b = rep.backend
            if isinstance(b, InProcessBackend) and b.engine is None:
                await b.start()
        self._poll_task = asyncio.ensure_future(self._poll_loop())
        return self

    async def close(self):
        self._closed = True
        if self._poll_task is not None:
            self._poll_task.cancel()
            try:
                await self._poll_task
            except (asyncio.CancelledError, Exception):
                pass
        for rep in self.replicas:
            await rep.backend.close()

    # -- health machinery ----------------------------------------------------

    async def _probe_backend(self, rep: _Replica) -> dict | None:
        """One bounded health fetch; None = failed (timeout counts — the
        slow-loris /health shape must read as a failed poll)."""
        self.counters["probes"] += 1
        rep.counters["probes"] += 1
        try:
            return await asyncio.wait_for(
                rep.backend.probe(self.rc.probe_timeout_s),
                self.rc.probe_timeout_s)
        except (BackendError, asyncio.TimeoutError, Exception):
            return None

    async def poll_once(self, now: float | None = None):
        """One deterministic pass of the health loop: poll every routable
        replica whose last poll aged out, probe every ejected replica past
        its backoff.  Unit tests drive this directly; ``_poll_loop`` just
        repeats it."""
        now = time.monotonic() if now is None else now

        async def poll(rep: _Replica):
            rep.polling = True
            try:
                h = await self._probe_backend(rep)
                t = time.monotonic()
                if h is not None and rep.wedged(h, t):
                    # 200-ok with a frozen engine loop: the wedge shape —
                    # a failed poll, not a healthy one
                    h = None
                    reason = "wedged_ticks"
                else:
                    reason = "health_poll_failed"
                if h is None:
                    self._note_transport_failure(rep, reason)
                elif h.get("status") == "draining":
                    # a replica that reports "draining" is leaving on its
                    # own terms: stop routing, let the probe loop bring it
                    # back post-restart (the rolling-restart handshake).
                    # Checked BEFORE on_success so a SUSPECT replica's
                    # transition log never records a spurious "recovered"
                    # hop on its way out
                    rep.last_health = h
                    rep.eject(t, "replica_draining")
                    self.counters["ejections"] += 1
                else:
                    rep.on_success(t, health=h)
            finally:
                rep.polling = False

        async def probe(rep: _Replica):
            rep.polling = True
            rep._move(PROBING, "probe")
            try:
                h = await self._probe_backend(rep)
                t = time.monotonic()
                # a probed replica reporting "draining" is not back yet,
                # and neither is one whose engine loop is still frozen
                if h is not None and (h.get("status") == "draining"
                                      or rep.wedged(h, t)):
                    h = None
                rep.on_probe_result(t, h)
                if rep.state == HEALTHY:
                    self.counters["reinstated"] += 1
            finally:
                rep.polling = False

        tasks = []
        for rep in self.replicas:
            if rep.polling or rep.state == DRAINING:
                continue
            if rep.state in ROUTABLE_STATES:
                if now - rep.last_poll_t >= self.rc.probe_interval_s:
                    rep.last_poll_t = now
                    tasks.append(poll(rep))
            elif rep.state == EJECTED and now >= rep.next_probe_t:
                tasks.append(probe(rep))
        if tasks:
            await asyncio.gather(*tasks)

    async def _poll_loop(self):
        # tick at a quarter interval so "stops receiving traffic within
        # one probe interval" holds with poll scheduling jitter included
        tick = max(0.02, self.rc.probe_interval_s / 4)
        while not self._closed:
            try:
                await self.poll_once()
            except asyncio.CancelledError:
                raise
            except Exception:
                pass   # the poll loop must survive any backend weirdness
            await asyncio.sleep(tick)

    # -- routing -------------------------------------------------------------

    def _prompt_text(self, path: str, body: dict) -> str:
        """The request's prompt as one string — the affinity key's
        source and the disaggregation threshold's yardstick."""
        if "chat/completions" in path:
            return json.dumps(body.get("messages", []), sort_keys=True)
        if "completions" in path:
            p = body.get("prompt", "")
            return str(p[0] if isinstance(p, list) and p else p)
        return str(body.get("inputs", ""))

    def _prefix_key(self, path: str, body: dict) -> str | None:
        src = self._prompt_text(path, body)[
            : self.rc.affinity_prefix_chars]
        if not src:
            return None
        return hashlib.sha1(src.encode()).hexdigest()

    @staticmethod
    def _spill_covers(kv: dict) -> bool:
        """Does the replica's host spill tier plausibly cover pages the
        device pool let go?  With a spill tier, ``prefix_evictions``
        advancing means DEMOTION, not loss — the page swaps back on the
        next hit — so affinity should hold.  True only when the tier is
        enabled, actually holds pages (or has proven swap-ins), and is
        retaining what it is given: a tier whose own byte budget is
        dropping most of its demoted pages (``spill_lru_evictions``
        running at the spill rate) really IS losing prefixes, and
        affinity should degrade exactly as it would untiered.  (The
        swap-in hit RATE is deliberately not the signal here: every
        novel-prompt admission probes the store and counts a miss, so
        mixed traffic dilutes it without a single page being lost.)"""
        if not kv.get("spill_enabled"):
            return False
        if kv.get("spill_pages", 0) <= 0 and kv.get("swap_ins", 0) == 0:
            return False
        spills = kv.get("spills", 0)
        lost = kv.get("spill_lru_evictions", 0)
        return not (spills >= 8 and lost > spills * 0.5)

    def _affinity_fresh(self, rep: _Replica, evict_mark: int) -> bool:
        """Is the remembered prefix likely still SERVABLE there?  The
        replica's /health kv block is the signal: prefix evictions since
        the mark mean the cached pages may be gone, a nearly-dry pool
        means they soon will be — UNLESS the replica runs a spill tier
        whose /health block shows it holding up, in which case an
        eviction is a demotion the next hit swaps back.  Only a genuine
        loss degrades affinity to least-loaded."""
        h = rep.last_health
        if not h or "kv" not in h:
            return True   # no signal yet: assume resident
        kv = h["kv"]
        if (kv.get("prefix_evictions", 0) > evict_mark
                and not self._spill_covers(kv)):
            return False
        total = kv.get("pages_total", 0)
        if (total and kv.get("pages_free", 0) < total
                * self.rc.affinity_free_frac
                and not self._spill_covers(kv)):
            return False
        return True

    def _pick(self, key: str | None, exclude: set[int], now: float,
              role: str = "decode") -> _Replica | None:
        cands = [r for r in self.replicas
                 if r.routable(now) and r.idx not in exclude]
        # role preference (disaggregated fleets): client traffic goes to
        # decode-capable replicas, handoff prefills to prefill-capable
        # ones — advisory, so a degraded fleet serves from whatever is
        # left rather than shedding on principle
        preferred = [r for r in cands if r.role in (role, "any")]
        cands = preferred or cands
        if not cands:
            return None
        if key is not None and key in self._affinity:
            idx, mark = self._affinity[key]
            rep = self.replicas[idx]
            if rep in cands:
                if self._affinity_fresh(rep, mark):
                    self.counters["affinity_hits"] += 1
                    self._affinity.move_to_end(key)
                    return rep
                # stale: drop the entry and spill (graceful degradation)
                self.counters["affinity_spills"] += 1
                del self._affinity[key]
            elif rep.state not in ROUTABLE_STATES:
                # ejected/draining owner: spill AND forget, so the prefix
                # re-homes wherever least-loaded sends it next
                self.counters["affinity_spills"] += 1
                del self._affinity[key]
        return min(cands, key=lambda r: (r.load(), r.idx))

    def _record_affinity(self, key: str | None, rep: _Replica):
        if key is None:
            return
        mark = 0
        if rep.last_health and "kv" in rep.last_health:
            mark = rep.last_health["kv"].get("prefix_evictions", 0)
        self._affinity[key] = (rep.idx, mark)
        self._affinity.move_to_end(key)
        while len(self._affinity) > self.rc.affinity_max_entries:
            self._affinity.popitem(last=False)

    def _shed_retry_after(self, now: float) -> int:
        """Honest Retry-After when the router sheds: the soonest moment a
        replica might return to rotation (next probe / cooloff expiry),
        clamped to [1, 30]."""
        horizons = []
        for rep in self.replicas:
            if rep.state in (EJECTED, PROBING):
                horizons.append(rep.next_probe_t - now)
            elif rep.state in ROUTABLE_STATES and rep.shed_until > now:
                horizons.append(rep.shed_until - now)
            elif rep.state == DRAINING:
                horizons.append(self.rc.probe_backoff_s)
        soonest = min(horizons) if horizons else 1.0
        return max(1, min(30, int(soonest) + 1))

    def _replica_retry_after(self, headers: dict) -> float:
        try:
            return float(headers.get("Retry-After", ""))
        except (TypeError, ValueError):
            return self.rc.shed_cooloff_s

    # -- the attempt loop ----------------------------------------------------

    def _deadline(self, body: dict) -> float | None:
        budget = body.get("deadline_s") or self.rc.request_deadline_s
        try:
            budget = float(budget)
        except (TypeError, ValueError):
            budget = 0.0
        return (time.monotonic() + budget) if budget > 0 else None

    def _fwd_body(self, body: dict, deadline: float | None,
                  tid: str | None = None) -> dict:
        """Per-attempt forwarded body: the REMAINING deadline budget is
        stamped so a failover attempt runs under what is left, not a
        fresh allowance — and the traceparent rides along (HTTPBackend
        promotes it to the real W3C header) so the replica's spans key
        to the same trace the router's do."""
        fwd = dict(body)
        if deadline is not None:
            fwd["deadline_s"] = max(0.001,
                                    round(deadline - time.monotonic(), 3))
        else:
            fwd.pop("deadline_s", None)
        if tid is not None:
            fwd["traceparent"] = make_traceparent(tid)
        else:
            fwd.pop("traceparent", None)
        return fwd

    def _trace_tid(self, body: dict,
                   trace_id: str | None = None) -> str | None:
        """The request's trace id: caller-passed (the HTTP handlers parse
        the client's traceparent header), or the body's own traceparent,
        or freshly minted when the router traces — None only with
        tracing off and no inherited id (then nothing propagates)."""
        if trace_id:
            return trace_id
        parsed = parse_traceparent(body.get("traceparent"))
        if parsed is not None:
            return parsed[0]
        return new_trace_id() if self.tracer is not None else None

    def _rspan(self, tid: str | None, name: str, t0: float | None = None,
               t1: float | None = None, **attrs):
        if self.tracer is None or tid is None:
            return
        self.tracer.add(tid, span(name, time.time() if t0 is None else t0,
                                  t1, origin="router", **attrs))

    def _admit(self, surface: str) -> RouterResponse | None:
        """Bounded router inbox: beyond ``max_inflight`` the router sheds
        immediately with 429 + Retry-After instead of queueing."""
        if self.rc.max_inflight and self._inflight >= self.rc.max_inflight:
            self.counters["shed"] += 1
            ra = self._shed_retry_after(time.monotonic())
            return RouterResponse(
                429,
                _error_payload(surface,
                               "router overloaded "
                               f"({self._inflight} requests in flight)",
                               "router_overloaded", "overloaded_error"),
                {"Retry-After": str(ra)})
        return None

    def _give_up(self, surface: str, reason: str, code: str,
                 now: float) -> RouterResponse:
        self.counters["shed"] += 1
        return RouterResponse(
            503, _error_payload(surface, reason, code,
                                "overloaded_error"),
            {"Retry-After": str(self._shed_retry_after(now))})

    def _timed_out(self, surface: str) -> RouterResponse:
        return RouterResponse(
            408, _error_payload(
                surface,
                "request deadline exceeded (spanning failover attempts)",
                "timeout", "timeout_error"))

    def _next_replica(self, surface: str, key: str | None, tried: set[int],
                      attempts: int, deadline: float | None):
        """Shared per-attempt gate for both dispatch paths: returns
        ``(replica, None)`` to try, or ``(None, RouterResponse)`` when
        the request is over — deadline spent, no routable replica left,
        or the failover bound hit."""
        now = time.monotonic()
        if deadline is not None and now >= deadline:
            return None, self._timed_out(surface)
        rep = self._pick(key, tried, now)
        if rep is None:
            return None, self._give_up(
                surface, "no replica available (all ejected, draining, "
                "or shedding)", "no_replica_available", now)
        if attempts >= self.rc.max_attempts:
            return None, self._give_up(
                surface, f"failover attempts exhausted ({attempts})",
                "failover_exhausted", now)
        return rep, None

    def _note_shed(self, rep: _Replica, headers: dict, tried: set[int]):
        """Replica 429/503: backpressure, not death — cooloff (honoring
        its Retry-After hint) + re-route; the replica stays in rotation
        for later requests."""
        rep.counters["shed"] += 1
        rep.shed_until = time.monotonic() + self._replica_retry_after(
            headers)
        self.counters["rerouted_backpressure"] += 1
        tried.add(rep.idx)

    def _note_transport_failure(self, rep: _Replica, reason: str,
                                tried: set[int] | None = None):
        """Health-signal a transport-level failure; counts the ejection
        only when THIS failure caused it (an already-ejected replica's
        other dying streams must not double-count)."""
        was = rep.state
        rep.on_failure(time.monotonic(), reason)
        if rep.state == EJECTED and was != EJECTED:
            self.counters["ejections"] += 1
        if tried is not None:
            tried.add(rep.idx)

    @staticmethod
    def _fwd_headers(headers: dict) -> dict:
        return {k: v for k, v in headers.items()
                if k.lower() in ("content-type", "retry-after")}

    # -- disaggregated prefill/decode handoff --------------------------------

    def _disagg_eligible(self, path: str, body: dict) -> bool:
        return (self.rc.disagg_prefill_chars > 0
                and len(self._prompt_text(path, body))
                >= self.rc.disagg_prefill_chars)

    def _handoff_strike(self, rep: _Replica, e, deadline: float | None,
                        leg: str):
        """Health accounting for a failed handoff leg — with the PR 10
        no-strike-on-deadline rule restored for disagg: a leg whose
        budget was clamped to a nearly-spent CLIENT deadline and that
        timed out AT that deadline says nothing about the replica
        (short-deadline clients must not be able to eject healthy
        prefill/decode replicas), so it counts a handoff failure but no
        strike.  Anything else is a genuine transport death."""
        stage = getattr(e, "stage", "fault")
        if (deadline is not None and stage == "stall"
                and time.monotonic() >= deadline):
            return "deadline"
        self._note_transport_failure(rep, f"handoff_{stage}")
        return stage

    async def _handoff(self, path: str, body: dict, key: str | None,
                       deadline: float | None, tid: str | None = None):
        """Disaggregated prefill: compute the prompt's KV pages on a
        prefill-role replica (/kv/prefill), import them into a
        decode-role replica (/kv/import), and home the prompt's affinity
        there — the stream dispatched next lands on the importer and
        prefills only the uncovered tail.

        EVERY failure here is a zero-delivery failover by construction:
        nothing has reached the client yet, so a mid-handoff death just
        notes the health strike (the state machine ejects a dying
        replica exactly as it would for a failed request), counts
        ``handoff_failures``, and the caller falls back to the
        monolithic path — no lost, hung, or duplicated stream."""
        now = time.monotonic()
        # the prefill leg requires an EXPLICIT prefill-role replica —
        # _pick's advisory fallback would otherwise "hand off" between
        # two ordinary replicas, silently doubling prefill compute on a
        # monolithic fleet with disagg_prefill_chars set
        pre_cands = [r for r in self.replicas
                     if r.role == "prefill" and r.routable(now)]
        pre = (min(pre_cands, key=lambda r: (r.load(), r.idx))
               if pre_cands else None)
        # the decode replica is picked LEAST-LOADED, deliberately
        # ignoring affinity: transportable pages are what make the
        # affinity pin obsolete — the prefix moves to wherever capacity
        # is (the prefill replica's own prefix cache makes repeat
        # exports nearly free), so a shared hot prefix spreads across
        # decode replicas instead of hot-spotting its first home.
        # Import-incapable replicas (handoff_broken) are excluded UP
        # FRONT: discovering that only after paying the prefill leg
        # would tax every eligible request for a blob nobody can take.
        skip = {pre.idx} if pre is not None else set()
        skip |= {r.idx for r in self.replicas if r.handoff_broken}
        dec = self._pick(None, skip, now, role="decode")
        if pre is None or dec is None or pre.idx == dec.idx:
            return     # no split fleet to hand off across
        if key is not None and key in self._affinity:
            idx, mark = self._affinity[key]
            if idx == dec.idx and self._affinity_fresh(dec, mark):
                # the least-loaded decode pick ALREADY holds this
                # prefix (a prior handoff or admission homed it there):
                # re-shipping the blob would import zero pages — skip
                # the legs and let the dispatch loop route by affinity
                self._affinity.move_to_end(key)
                return
        budget = self.rc.handoff_timeout_s
        if deadline is not None:
            budget = min(budget, max(deadline - now, 0.001))
        pre.inflight += 1
        t_leg = time.time()
        try:
            pre.backend._fault("replica-handoff")
            status, headers, blob = await pre.backend.send_json(
                "/kv/prefill", self._fwd_body(body, deadline, tid), budget)
        except (BackendError, ReplicaFault) as e:
            # ReplicaFault covers injected shapes _fault does not
            # translate (e.g. a scripted stream-hang at this site): any
            # of them is still just a zero-delivery handoff death —
            # unless the leg merely ran out of the CLIENT's nearly-spent
            # deadline, which is no evidence against the replica
            outcome = self._handoff_strike(pre, e, deadline, "prefill")
            self.counters["handoff_failures"] += 1
            self._rspan(tid, "handoff_prefill", t0=t_leg, t1=time.time(),
                        replica=pre.idx, outcome=outcome)
            return
        finally:
            pre.inflight -= 1
        self.hists["handoff_prefill_s"].observe(time.time() - t_leg)
        self._rspan(tid, "handoff_prefill", t0=t_leg, t1=time.time(),
                    replica=pre.idx, status=status,
                    bytes=len(blob) if status == 200 else 0)
        if status != 200:
            # replica-authored refusal (shed / nothing-to-export): no
            # health strike, just no handoff this time
            if status in (429, 503):
                pre.shed_until = time.monotonic() + \
                    self._replica_retry_after(headers)
            self.counters["handoff_failures"] += 1
            return
        dec.inflight += 1
        t_leg = time.time()
        try:
            dec.backend._fault("replica-handoff")
            s2, _, _ = await dec.backend.send_bytes("/kv/import", blob,
                                                    budget)
        except (BackendError, ReplicaFault) as e:
            if getattr(e, "stage", None) == "unsupported":
                # a capability gap is not a death: no health strike,
                # but remember it so later handoffs skip this replica
                dec.handoff_broken = True
                outcome = "unsupported"
            else:
                # same no-strike-on-client-deadline rule as leg 1
                outcome = self._handoff_strike(dec, e, deadline, "import")
            self.counters["handoff_failures"] += 1
            self._rspan(tid, "handoff_import", t0=t_leg, t1=time.time(),
                        replica=dec.idx, outcome=outcome)
            return
        finally:
            dec.inflight -= 1
        self.hists["handoff_import_s"].observe(time.time() - t_leg)
        self._rspan(tid, "handoff_import", t0=t_leg, t1=time.time(),
                    replica=dec.idx, status=s2, bytes=len(blob))
        if s2 != 200:
            if s2 == 400:
                # the importer REJECTED the page set (shape/format skew
                # — permanent until the replica is rebuilt): stop
                # re-shipping blobs it will keep refusing
                dec.handoff_broken = True
            self.counters["handoff_failures"] += 1
            return
        self.counters["handoffs"] += 1
        self.counters["handoff_bytes"] += len(blob)
        # home the prompt on the importer: the dispatch loop's affinity
        # pick routes the stream (and future same-prefix requests) there
        self._record_affinity(key, dec)

    async def dispatch_json(self, path: str, body: dict,
                            trace_id: str | None = None) -> RouterResponse:
        """Non-streaming request through the fleet.  Nothing reaches the
        client until a replica's full response is in hand, so EVERY
        transport failure is safely replayable (bounded attempts, the
        deadline spanning them); replica-authored responses — including
        its own 408/500 error objects — are forwarded verbatim, and
        replica 429/503 re-routes with the shed replica in cooloff."""
        surface = _surface(path)
        shed = self._admit(surface)
        if shed is not None:
            return shed
        self.counters["requests"] += 1
        self._inflight += 1
        try:
            return await self._json_attempts(path, body, surface,
                                             self._trace_tid(body, trace_id))
        finally:
            self._inflight -= 1

    async def _json_attempts(self, path, body, surface,
                             tid=None) -> RouterResponse:
        deadline = self._deadline(body)
        key = self._prefix_key(path, body)
        tried: set[int] = set()
        attempts = 0
        replay_pending = False   # a transport failure happened: the NEXT
        #                          attempt is the failover (a backpressure
        #                          re-route in between is not one)
        while True:
            rep, done = self._next_replica(surface, key, tried, attempts,
                                           deadline)
            if rep is None:
                return done
            attempts += 1
            if replay_pending:
                self.counters["failovers"] += 1
                self._rspan(tid, "failover", attempt=attempts)
                replay_pending = False
            timeout = (deadline - time.monotonic() if deadline is not None
                       else self.rc.request_timeout_s)
            rep.counters["requests"] += 1
            rep.inflight += 1
            t_a = time.time()
            try:
                status, headers, payload = await rep.backend.send_json(
                    path, self._fwd_body(body, deadline, tid), timeout)
            except BackendError as e:
                self._rspan(tid, "route_attempt", t0=t_a, t1=time.time(),
                            replica=rep.idx, outcome=f"transport_{e.stage}")
                if (deadline is not None and e.stage == "stall"
                        and time.monotonic() >= deadline):
                    # the REQUEST ran out of budget mid-generation — that
                    # is a client deadline, not replica death: no health
                    # strike (short-deadline clients must not be able to
                    # eject healthy replicas); the stamped deadline_s
                    # expires the row server-side
                    return self._timed_out(surface)
                self._note_transport_failure(rep, f"request_{e.stage}",
                                             tried)
                replay_pending = True
                continue
            finally:
                rep.inflight -= 1
            if status in (429, 503):
                self._note_shed(rep, headers, tried)
                self._rspan(tid, "backpressure_reroute", t0=t_a,
                            t1=time.time(), replica=rep.idx, status=status)
                attempts -= 1   # backpressure re-route is not a failover
                continue
            rep.on_success(time.monotonic())
            self._record_affinity(key, rep)
            self._rspan(tid, "route_attempt", t0=t_a, t1=time.time(),
                        replica=rep.idx, status=status, outcome="ok")
            return RouterResponse(status, payload, self._fwd_headers(headers))

    async def dispatch_stream(self, path: str, body: dict,
                              trace_id: str | None = None,
                              ) -> RouterResponse | RouterStream:
        """Streaming request through the fleet.  Failover runs until the
        FIRST event is acquired from a replica (nothing delivered ⇒ replay
        is safe and invisible); from then on the stream is committed to
        that replica, and a mid-stream death becomes a terminal error
        event in the surface's own shape — never a silent truncation,
        never a replayed (duplicated) token."""
        surface = _surface(path)
        shed = self._admit(surface)
        if shed is not None:
            return shed
        self.counters["requests"] += 1
        self._inflight += 1
        deadline = self._deadline(body)
        key = self._prefix_key(path, body)
        tid = self._trace_tid(body, trace_id)
        tried: set[int] = set()
        attempts = 0
        committed = False   # a RouterStream owns the _inflight slot; every
        #                     other exit releases it in the finally below
        replay_pending = False
        try:
            if self._disagg_eligible(path, body):
                # the handoff is pure pre-work: success homes the
                # prompt's affinity on the importing decode replica,
                # any failure falls through to the ordinary loop below
                # with zero tokens delivered
                await self._handoff(path, body, key, deadline, tid)
            while True:
                rep, done = self._next_replica(surface, key, tried,
                                               attempts, deadline)
                if rep is None:
                    return done
                attempts += 1
                if replay_pending:
                    self.counters["failovers"] += 1
                    self._rspan(tid, "failover", attempt=attempts)
                    replay_pending = False
                rep.counters["requests"] += 1
                rep.inflight += 1
                t_a = time.time()
                try:
                    opened = await rep.backend.open_sse(
                        path, self._fwd_body(body, deadline, tid),
                        self.rc.stall_timeout_s,
                        self.rc.first_event_timeout_s)
                    if opened.events is None:
                        if opened.status in (429, 503):
                            self._note_shed(rep, opened.headers, tried)
                            self._rspan(tid, "backpressure_reroute",
                                        t0=t_a, t1=time.time(),
                                        replica=rep.idx,
                                        status=opened.status)
                            attempts -= 1
                            continue
                        # replica-authored pre-stream outcome (408/500/
                        # 400...): forwarded verbatim, like one replica
                        rep.on_success(time.monotonic())
                        self._rspan(tid, "route_attempt", t0=t_a,
                                    t1=time.time(), replica=rep.idx,
                                    status=opened.status, outcome="ok")
                        return RouterResponse(
                            opened.status, opened.payload or b"",
                            self._fwd_headers(opened.headers))
                    # acquire the first event BEFORE committing: a replica
                    # that dies between accept and first token is still a
                    # zero-delivery failover
                    gen = opened.events
                    try:
                        first = await gen.__anext__()
                    except StopAsyncIteration:
                        raise BackendError("stream closed with no events",
                                           stage="read")
                    rep.on_success(time.monotonic())
                    self._record_affinity(key, rep)
                    self._rspan(tid, "route_attempt", t0=t_a,
                                t1=time.time(), replica=rep.idx,
                                outcome="stream_committed")
                    committed = True
                    release = self._release_once(rep)
                    return RouterStream(
                        self._relay(rep, gen, first, surface, release,
                                    tid=tid),
                        release, upstream=gen)
                except BackendError as e:
                    self._rspan(tid, "route_attempt", t0=t_a,
                                t1=time.time(), replica=rep.idx,
                                outcome=f"transport_{e.stage}")
                    self._note_transport_failure(rep, f"stream_{e.stage}",
                                                 tried)
                    replay_pending = True
                    continue
                finally:
                    if not committed:
                        rep.inflight -= 1
        finally:
            # a committed stream's slot is released by the RouterStream's
            # release closure (via _relay's finally, or close() if the
            # relay never starts); every other exit releases it here
            if not committed:
                self._inflight -= 1

    def _release_once(self, rep: _Replica):
        """Idempotent release of a committed stream's inflight slots —
        callable from _relay's finally AND RouterStream.close() without
        double-decrement."""
        released = [False]

        def release():
            if not released[0]:
                released[0] = True
                rep.inflight -= 1
                self._inflight -= 1

        return release

    async def _relay(self, rep: _Replica, gen, first: bytes, surface: str,
                     release, tid: str | None = None):
        """Forward events from the committed replica; on mid-stream death
        append the surface's terminal error object (+ [DONE] on the
        OpenAI framing) so the client always sees a terminal event."""
        delivered = 0
        try:
            yield first
            delivered += 1
            async for ev in gen:
                yield ev
                delivered += 1
            rep.on_success(time.monotonic())
        except BackendError as e:
            self._note_transport_failure(rep, f"midstream_{e.stage}")
            self.counters["midstream_errors"] += 1
            self._rspan(tid, "midstream_error", replica=rep.idx,
                        delivered=delivered, stage=e.stage)
            err = _error_payload(
                surface,
                f"replica died mid-stream after {delivered} events "
                f"({e})", "replica_died_midstream", "server_error")
            yield b"data: " + err + b"\n\n"
            if surface == "openai":
                yield b"data: [DONE]\n\n"
        finally:
            release()
            await gen.aclose()

    # -- drain / restart orchestration --------------------------------------

    async def drain_replica(self, idx: int, timeout: float = 30.0) -> bool:
        """Rolling-restart step: stop routing to replica ``idx``, drain
        it gracefully (in-flight requests finish inside ``timeout``),
        and leave it EJECTED with an imminent probe — ``restart_replica``
        (or the process supervisor) brings it back and the probe loop
        reinstates it while the other replicas absorb the load."""
        rep = self.replicas[idx]
        rep._move(DRAINING, "drain_replica")
        ok = await rep.backend.drain(timeout)
        deadline = time.monotonic() + timeout
        while rep.inflight > 0 and time.monotonic() < deadline:
            await asyncio.sleep(0.02)
        rep.eject(time.monotonic(), "drained")
        self.counters["ejections"] += 1
        return ok and rep.inflight == 0

    async def restart_replica(self, idx: int, timeout: float = 60.0) -> bool:
        """Restart an (in-process) replica and wait for the probe loop to
        reinstate it.  HTTP backends have no restart lever — the process
        supervisor restarts them and this just waits for reinstatement."""
        rep = self.replicas[idx]
        if hasattr(rep.backend, "restart"):
            await rep.backend.restart()
        rep.next_probe_t = 0.0   # probe immediately
        deadline = time.monotonic() + timeout
        while rep.state != HEALTHY and time.monotonic() < deadline:
            await self.poll_once()
            await asyncio.sleep(0.02)
        return rep.state == HEALTHY

    async def rolling_restart(self, timeout_per_replica: float = 60.0):
        """Drain → restart → reinstate each replica in turn; the fleet
        keeps serving throughout (the runbook's one-liner)."""
        results = []
        for idx in range(len(self.replicas)):
            ok = await self.drain_replica(idx, timeout_per_replica)
            ok = await self.restart_replica(idx, timeout_per_replica) and ok
            results.append(ok)
        return results

    # -- aggregated observability -------------------------------------------

    async def assemble_trace(self, trace_id: str) -> dict | None:
        """One end-to-end trace: the router's own spans merged with every
        replica's ``/trace/{id}`` spans (re-tagged ``replicaN:engine``),
        sorted on the shared wall-clock timeline — the cross-process
        assembly the propagated traceparent exists for.  None when no
        process holds the trace."""
        own = self.tracer.get(trace_id) if self.tracer is not None else None
        spans = list(own["spans"]) if own else []
        dropped = own["spans_dropped"] if own else 0

        # concurrent fan-out under the probe budget (metrics_text's
        # pattern): one wedged replica must not stall the postmortem
        # surface for its whole 10 s default — traces are fetched
        # exactly when a replica is sick
        async def fetch(rep: _Replica):
            try:
                return await rep.backend.get_json(
                    f"/trace/{trace_id}", timeout=self.rc.probe_timeout_s)
            except BackendError:
                return None   # an unreachable replica costs spans, not a 500

        got = await asyncio.gather(*(fetch(r) for r in self.replicas))
        for rep, res in zip(self.replicas, got):
            if res is None or res[0] != 200:
                continue
            try:
                data = json.loads(res[1])
            except ValueError:
                continue
            for s in data.get("spans", []):
                s = dict(s)
                s["origin"] = (f"replica{rep.idx}:"
                               f"{s.get('origin') or 'engine'}")
                spans.append(s)
            dropped += data.get("spans_dropped", 0)
        if not spans:
            return None
        spans.sort(key=lambda s: (s["t0"], s["name"]))
        return {"trace_id": trace_id, "spans": spans,
                "spans_dropped": dropped}

    def health_view(self) -> dict:
        now = time.monotonic()
        routable = sum(1 for r in self.replicas if r.routable(now))
        status = ("ok" if routable == len(self.replicas)
                  else "degraded" if routable else "unavailable")
        return {
            "status": status,
            "router": {
                "router_id": self.router_id,
                "inflight": self._inflight,
                "replicas_total": len(self.replicas),
                "replicas_routable": routable,
                "affinity_entries": len(self._affinity),
                **self.counters,
            },
            "replicas": [r.view(now) for r in self.replicas],
        }

    async def metrics_text(self) -> str:
        """Prometheus-style aggregation: the router's own counters plus
        every reachable replica's counters re-labelled per replica,
        fleet-wide sums — and real histogram series: the router's
        handoff-leg histograms, plus fleet-SUMMED latency histograms
        (bucket counts are true counters, so summing them across
        replicas is the one honest fleet aggregation; the old rolling
        p95 scalars could not be combined at all)."""
        lines = []
        view = self.health_view()["router"]
        for name in sorted(view):
            v = view[name]
            if isinstance(v, (int, float)) and not isinstance(v, bool):
                lines.append(f"ipex_llm_tpu_router_{name} {v}")
        for name in sorted(self.hists):
            lines.extend(self.hists[name].prometheus_lines(
                f"ipex_llm_tpu_router_{name}"))

        async def fetch(rep: _Replica):
            try:
                return rep, await rep.backend.fetch_metrics(
                    self.rc.probe_timeout_s)
            except Exception:
                return rep, None

        got = await asyncio.gather(*(fetch(r) for r in self.replicas))
        sums: dict[str, float] = {}
        hist_sums: dict[str, Histogram] = {}
        for rep, res in got:
            if not res:
                continue
            rid = res.get("replica_id", "?")
            vals = res.get("metrics", {})
            for name in sorted(vals):
                v = vals[name]
                if not isinstance(v, (int, float)) or isinstance(v, bool):
                    continue
                lines.append(
                    f'ipex_llm_tpu_{name}{{replica="{rep.idx}",'
                    f'replica_id="{rid}"}} {v}')
                if name in _FLEET_SUMMABLE:
                    sums[name] = sums.get(name, 0) + v
            for hname, hd in sorted((res.get("histograms") or {}).items()):
                agg = hist_sums.get(hname)
                if agg is None:
                    try:
                        agg = hist_sums[hname] = Histogram(
                            hd.get("bounds") or LATENCY_BUCKETS_S)
                    except ValueError:
                        continue
                agg.merge(hd)   # refuses (skips) mismatched buckets
        for name in sorted(sums):
            lines.append(f"ipex_llm_tpu_fleet_{name} "
                         f"{round(sums[name], 6)}")
        for hname in sorted(hist_sums):
            lines.extend(hist_sums[hname].prometheus_lines(
                f"ipex_llm_tpu_fleet_{hname}"))
        return "\n".join(lines) + "\n"

    # -- aiohttp surface ------------------------------------------------------

    def build_app(self) -> "web.Application":
        if web is None:  # pragma: no cover
            raise ImportError(
                f"aiohttp is required for the router: {_AIOHTTP_ERR}")
        app = web.Application()
        app.router.add_post("/v1/chat/completions", self._h_openai)
        app.router.add_post("/v1/completions", self._h_openai)
        app.router.add_post("/generate", self._h_tgi)
        app.router.add_post("/generate_stream", self._h_tgi_stream)
        app.router.add_get("/v1/models", self._h_models)
        app.router.add_get("/health", self._h_health)
        app.router.add_get("/metrics", self._h_metrics)
        app.router.add_get("/trace/{trace_id}", self._h_trace)
        app.router.add_get("/debug/traces", self._h_traces)
        app.router.add_get("/debug/flight", self._h_flight)
        return app

    @staticmethod
    def _respond(res: RouterResponse) -> "web.Response":
        headers = dict(res.headers)
        ctype = headers.pop("Content-Type", "application/json")
        return web.Response(status=res.status, body=res.payload,
                            content_type=ctype.split(";")[0],
                            headers=headers)

    def _req_trace_id(self, request) -> str | None:
        """Trace id for one HTTP request: the client's traceparent header
        when present (so callers control/correlate their own traces),
        else freshly minted when the router traces.  Echoed back as
        X-Trace-Id so a client that did NOT send a traceparent can still
        fetch /trace/{id}."""
        parsed = parse_traceparent(request.headers.get("traceparent"))
        if parsed is not None:
            return parsed[0]
        return new_trace_id() if self.tracer is not None else None

    async def _stream_out(self, request, res: RouterStream,
                          trace_id: str | None = None):
        headers = {
            "Content-Type": "text/event-stream",
            "Cache-Control": "no-cache",
        }
        if trace_id:
            headers["X-Trace-Id"] = trace_id
        resp = web.StreamResponse(headers=headers)
        # prepare() is inside the guarded region: a client that
        # disconnects before (or while) headers go out must still close
        # the committed upstream and release its inflight slots
        try:
            await resp.prepare(request)
            async for ev in res.events:
                await resp.write(ev)
            await resp.write_eof()
        except (ConnectionResetError, asyncio.CancelledError):
            # client went away: close the upstream so the replica's
            # engine aborts the row instead of decoding into the void
            await res.close()
            raise
        return resp

    @staticmethod
    def _with_trace_header(resp: "web.Response",
                           trace_id: str | None) -> "web.Response":
        if trace_id:
            resp.headers["X-Trace-Id"] = trace_id
        return resp

    async def _h_openai(self, request):
        body = await request.json()
        tid = self._req_trace_id(request)
        if body.get("stream"):
            res = await self.dispatch_stream(request.path, body,
                                             trace_id=tid)
            if isinstance(res, RouterStream):
                return await self._stream_out(request, res, trace_id=tid)
            return self._with_trace_header(self._respond(res), tid)
        return self._with_trace_header(
            self._respond(await self.dispatch_json(request.path, body,
                                                   trace_id=tid)), tid)

    async def _h_tgi(self, request):
        tid = self._req_trace_id(request)
        return self._with_trace_header(
            self._respond(await self.dispatch_json(
                "/generate", await request.json(), trace_id=tid)), tid)

    async def _h_tgi_stream(self, request):
        tid = self._req_trace_id(request)
        res = await self.dispatch_stream("/generate_stream",
                                         await request.json(),
                                         trace_id=tid)
        if isinstance(res, RouterStream):
            return await self._stream_out(request, res, trace_id=tid)
        return self._with_trace_header(self._respond(res), tid)

    async def _h_models(self, request):
        now = time.monotonic()
        for rep in self.replicas:
            if rep.routable(now):
                try:
                    status, payload = await rep.backend.get_json(
                        "/v1/models")
                    return web.Response(status=status, body=payload,
                                        content_type="application/json")
                except BackendError:
                    continue
        return self._respond(self._give_up(
            "openai", "no replica available", "no_replica_available", now))

    async def _h_health(self, request):
        view = self.health_view()
        status = 200 if view["status"] != "unavailable" else 503
        return web.json_response(view, status=status)

    async def _h_metrics(self, request):
        return web.Response(text=await self.metrics_text(),
                            content_type="text/plain")

    async def _h_trace(self, request):
        """One assembled end-to-end trace; ``?format=chrome`` renders it
        as Chrome trace-event JSON (chrome://tracing / Perfetto)."""
        tid = request.match_info["trace_id"]
        tr = await self.assemble_trace(tid)
        if tr is None:
            return web.json_response(
                {"error": {"message": f"unknown trace {tid!r} (tracing "
                                      "off, or aged out of the LRU)",
                           "type": "invalid_request_error",
                           "code": "unknown_trace"}}, status=404)
        if request.query.get("format") == "chrome":
            return web.json_response(Tracer.chrome_events([tr]))
        return web.json_response(tr)

    async def _h_traces(self, request):
        """Whole-window export of the router's own spans (per-request
        assembly across replicas rides /trace/{id}); ``?format=chrome``
        for the Perfetto shape."""
        if self.tracer is None:
            return web.json_response(
                {"error": {"message": "router tracing is disabled",
                           "type": "invalid_request_error",
                           "code": "tracing_disabled"}}, status=404)
        if request.query.get("format") == "chrome":
            return web.json_response(self.tracer.export_chrome())
        return web.json_response({"trace_ids": self.tracer.trace_ids()})

    async def _h_flight(self, request):
        """Every reachable replica's tick flight recorder, keyed by
        replica index — the fleet-wide postmortem fetch."""
        async def fetch(rep: _Replica):
            try:
                return await rep.backend.get_json(
                    "/debug/flight", timeout=self.rc.probe_timeout_s)
            except BackendError:
                return None

        got = await asyncio.gather(*(fetch(r) for r in self.replicas))
        out = {}
        for rep, res in zip(self.replicas, got):
            if res is None or res[0] != 200:
                continue
            try:
                out[str(rep.idx)] = json.loads(res[1])
            except ValueError:
                continue
        return web.json_response({"replicas": out})


# ---------------------------------------------------------------------------
# CLI


def build_inprocess_fleet(model_path: str, n_replicas: int,
                          low_bit: str = "sym_int4",
                          engine_config=None,
                          kv_import_token: str | None = None) -> list:
    """N in-process engine replicas over ONE loaded copy of the weights
    (params are read-only device arrays — every engine shares them; each
    replica has its own KV pool, queue, and fault domain).
    ``kv_import_token`` makes every replica's loopback /kv/import
    REQUIRE the shared token — the in-process replicas listen on real
    TCP ports, so the poisoning exposure is the same as the
    multi-process deployment's."""
    from transformers import AutoTokenizer

    from ipex_llm_tpu.serving.engine import ServingEngine
    from ipex_llm_tpu.transformers import AutoModelForCausalLM

    import os
    if os.path.exists(f"{model_path}/bigdl_config.json"):
        model = AutoModelForCausalLM.load_low_bit(model_path)
    else:
        model = AutoModelForCausalLM.from_pretrained(
            model_path, load_in_low_bit=low_bit)
    tok = AutoTokenizer.from_pretrained(model_path, trust_remote_code=True)
    eos = model.generation_config.eos_token_id

    def factory():
        return ServingEngine(model.config, model.params, engine_config,
                             default_eos=eos).start()

    return [InProcessBackend(factory, tok, model_name=model_path,
                             kv_import_token=kv_import_token)
            for _ in range(n_replicas)]


def main(argv=None):
    ap = argparse.ArgumentParser(
        "ipex-llm-tpu replica router (OpenAI/TGI-transparent)")
    ap.add_argument("--replicas", required=True,
                    help="fleet spec: an integer N (spawn N in-process "
                         "engine replicas over --model) or a comma-"
                         "separated list of replica base URLs "
                         "(http://host:port) to front")
    ap.add_argument("--model", default=None,
                    help="checkpoint for the in-process fleet form")
    ap.add_argument("--low-bit", default="sym_int4")
    ap.add_argument("--host", default="0.0.0.0")
    ap.add_argument("--router-port", type=int, default=8080)
    ap.add_argument("--probe-interval", type=float, default=1.0,
                    metavar="S", help="per-replica /health poll period — "
                    "also the bound on how long a dead replica keeps "
                    "receiving traffic")
    ap.add_argument("--probe-timeout", type=float, default=2.0, metavar="S",
                    help="health poll budget; a slower /health (slow-"
                         "loris) counts as a failed poll")
    ap.add_argument("--eject-after", type=int, default=2, metavar="N",
                    help="consecutive failures before a replica is "
                         "ejected (1 = eject on first failure)")
    ap.add_argument("--probe-backoff", type=float, default=0.5, metavar="S",
                    help="first re-probe delay after ejection; doubles "
                         "per failed probe up to --probe-backoff-max")
    ap.add_argument("--probe-backoff-max", type=float, default=8.0,
                    metavar="S")
    ap.add_argument("--max-attempts", type=int, default=3,
                    help="bounded failover: replicas tried per request")
    ap.add_argument("--stall-timeout", type=float, default=60.0,
                    metavar="S", help="max mid-stream silence before a "
                    "stream counts as a replica death")
    ap.add_argument("--first-event-timeout", type=float, default=300.0,
                    metavar="S", help="separate silence budget for a "
                    "stream's FIRST event (cold TTFT includes jit "
                    "compiles — a compiling replica is not a dead one)")
    ap.add_argument("--max-inflight", type=int, default=0,
                    help="router inbox bound: beyond this many in-flight "
                         "requests the router sheds with 429 + "
                         "Retry-After (0 = unbounded)")
    ap.add_argument("--request-deadline", type=float, default=0.0,
                    metavar="S", help="default end-to-end deadline "
                    "spanning ALL failover attempts (0 = none)")
    ap.add_argument("--roles", default=None,
                    help="comma-separated per-replica roles "
                         "(any|prefill|decode), one per replica — the "
                         "disaggregated-fleet shape, e.g. "
                         "'prefill,decode,decode'")
    ap.add_argument("--disagg-prefill-chars", type=int, default=0,
                    metavar="N",
                    help="disaggregated prefill/decode: streaming "
                         "prompts of at least N characters hand off — "
                         "a prefill-role replica computes the KV pages, "
                         "a decode-role replica imports them and serves "
                         "the stream (0 = off; requires --roles)")
    ap.add_argument("--kv-import-token", default=None, metavar="TOKEN",
                    help="shared token forwarded on the /kv/import "
                         "handoff leg (X-KV-Import-Token); replicas "
                         "started with the same --kv-import-token "
                         "reject unauthenticated page-set imports")
    ap.add_argument("--no-trace", action="store_true",
                    help="disable router-side request-lifecycle tracing "
                         "(spans, /trace/{id} assembly, traceparent "
                         "minting; client-supplied traceparents still "
                         "propagate)")
    args = ap.parse_args(argv)

    rc = RouterConfig(
        probe_interval_s=args.probe_interval,
        probe_timeout_s=args.probe_timeout,
        eject_after=args.eject_after,
        probe_backoff_s=args.probe_backoff,
        probe_backoff_max_s=args.probe_backoff_max,
        max_attempts=args.max_attempts,
        stall_timeout_s=args.stall_timeout,
        first_event_timeout_s=args.first_event_timeout,
        max_inflight=args.max_inflight,
        request_deadline_s=args.request_deadline,
        disagg_prefill_chars=args.disagg_prefill_chars,
        kv_import_token=args.kv_import_token,
        tracing=not args.no_trace,
    )
    if args.replicas.isdigit():
        if not args.model:
            ap.error("--model is required for the in-process fleet form")
        backends = build_inprocess_fleet(
            args.model, int(args.replicas), args.low_bit,
            kv_import_token=args.kv_import_token)
    else:
        backends = [HTTPBackend(u.strip())
                    for u in args.replicas.split(",") if u.strip()]
    roles = ([r.strip() for r in args.roles.split(",")]
             if args.roles else None)
    router = Router(backends, rc, roles=roles)

    async def on_startup(app):
        await router.start()   # starts any un-started in-process backend

    async def on_shutdown(app):
        await router.close()

    app = router.build_app()
    app.on_startup.append(on_startup)
    app.on_shutdown.append(on_shutdown)
    web.run_app(app, host=args.host, port=args.router_port)


if __name__ == "__main__":
    main()
