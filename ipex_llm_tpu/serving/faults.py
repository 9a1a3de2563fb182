"""Fault classes + deterministic fault injection for the serving engine.

The engine's unit of failure is a REQUEST, not the engine (the blast-radius
contract tests/test_serving_faults.py enforces).  Two failure classes drive
the recovery policy in ``ServingEngine._recover``:

- **transient** — the device call would succeed if repeated: a preempted
  device, ``RESOURCE_EXHAUSTED``/``UNAVAILABLE`` from the runtime, a
  dropped connection.  Recovery: roll host bookkeeping back to the last
  committed tick, re-upload device state (``_dirty = True`` — the same
  epoch mechanism admission uses), back off exponentially, and re-run the
  step.  The re-run recomputes the identical tick (same key chain, same
  fold_in(seed, step) streams), so retried output is bit-identical.
- **deterministic** — the same inputs fail every time: a poisoned prompt
  hitting a model/kernel edge, a per-request resource bug.  Retrying is
  useless; instead the engine BISECTS the faulted tick's row set
  (re-running the step with suspect rows masked, emissions muted and all
  bookkeeping rolled back between probes) until one culprit row remains,
  quarantines only that row with ``finish_reason="error"``, and replays
  the tick for the survivors — whose tokens and logprobs stay bit-
  identical to an unfaulted run.  ``_fail_all`` remains only as the
  engine-level backstop for when bisection itself cannot localize the
  fault (the fault fires even with every suspect masked — a device-level,
  not request-level, failure).  A fault that VANISHES during bisection
  (does not reproduce on re-run, or stops firing before the culprit is
  confirmed) is treated as transient-resolved: the engine carries on from
  the committed state rather than punishing anyone.  NOTE for scripted
  deterministic faults: use ``times=None`` on a request-scoped spec (a
  poisoned request fails every time it participates); a one-shot
  deterministic spec is indistinguishable from a transient blip and will
  be classified as vanished.

Classification: exception TYPE first (the marker classes below, used by
tests and by code that knows its failure mode), then RUNTIME MESSAGE
markers — the gRPC-style status names JAX runtimes embed in
``XlaRuntimeError`` text (``RESOURCE_EXHAUSTED: ...``), plus OS-level
connection failures.

``FaultInjector`` is the deterministic test harness for all of the above:
it raises scripted exceptions at named SITES inside the engine step
(page-alloc, mixed-step, decode-dispatch, sample) on the
Nth hit of the site, optionally only when a given request participates in
the step — which is exactly the shape of a poisoned-request fault, and
what makes bisection observable.  Sites fire BEFORE the device call they
guard, so an injected fault never leaves a half-donated cache behind (the
recovery contract assumes KV writes beyond the committed row_lens are
scratch, which holds for host-side raises).

One tier up, the REPLICA is the unit of failure (serving/router.py): a
whole engine process can crash, wedge mid-stream, or go slow-loris on its
health endpoint.  The ``ReplicaFault`` family models those transport-level
failures, and the router's backends guard their own sites
(``REPLICA_FAULT_SITES``) with the same ``FaultInjector`` — each backend
carries its OWN injector, so chaos is scripted per-replica and a router
chaos run is deterministic and unit-testable, not only process-kill:

- ``replica-connect`` (``ReplicaConnectRefused``) — fires before a request
  is sent to the replica: the connect-refused shape a SIGKILLed process
  produces.  The router must fail over (the request never reached a row).
- ``replica-stream``  (``ReplicaStreamHang``) — fires before an SSE event
  read: the backend then stalls past the router's stall timeout, the
  mid-stream-wedge shape.  Zero delivered tokens → safe replay; delivered
  tokens → a terminal error event, never a silent truncation.
- ``replica-health``  (``ReplicaSlowHealth``) — fires on a health probe:
  the probe hangs past its budget, the slow-loris shape that must count
  as a failed poll (a wedged replica stops receiving traffic within one
  probe interval).
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any

__all__ = [
    "TransientFault",
    "DeterministicFault",
    "EngineOverloaded",
    "ReplicaFault",
    "ReplicaConnectRefused",
    "ReplicaStreamHang",
    "ReplicaSlowHealth",
    "FaultInjector",
    "FAULT_SITES",
    "REPLICA_FAULT_SITES",
    "is_transient",
]


class TransientFault(RuntimeError):
    """A step failure expected to succeed on retry (device preemption,
    pool pressure in the runtime, a dropped connection)."""


class DeterministicFault(RuntimeError):
    """A step failure that will recur on identical inputs (poisoned
    request); retry is useless, isolation is the remedy."""


class EngineOverloaded(RuntimeError):
    """Raised by ``ServingEngine.submit`` when the bounded inbox is full
    or the engine is draining — the load-shedding signal the HTTP
    surfaces map to 429/503."""

    def __init__(self, message: str, queue_depth: int = 0,
                 draining: bool = False):
        super().__init__(message)
        self.queue_depth = queue_depth
        self.draining = draining


class ReplicaFault(RuntimeError):
    """Base of the replica-tier fault family: transport-level failures of
    a whole engine replica, injected into the ROUTER's backends (not the
    engine step) via ``REPLICA_FAULT_SITES``."""


class ReplicaConnectRefused(ReplicaFault):
    """The replica refuses connections (crashed / SIGKILLed process); the
    router backend translates it into its connect-failure path."""


class ReplicaStreamHang(ReplicaFault):
    """The replica stops producing SSE events mid-stream (wedged engine
    thread, dead peer with the socket still open); the backend stalls
    until the router's stall timeout trips."""


class ReplicaSlowHealth(ReplicaFault):
    """The replica's /health answers slower than the probe budget
    (slow-loris): the probe must count as a failed poll."""


# Status markers JAX device runtimes embed in XlaRuntimeError messages
# (absl::Status names), plus transport failures: all are
# retry-worthy.  Deliberately NOT here: INVALID_ARGUMENT, INTERNAL,
# FAILED_PRECONDITION — those recur on identical inputs.
_TRANSIENT_MARKERS = (
    "RESOURCE_EXHAUSTED",
    "UNAVAILABLE",
    "DEADLINE_EXCEEDED",
    "ABORTED",
    "CANCELLED",
    "connection reset",
    "connection refused",
    "broken pipe",
)


def is_transient(exc: BaseException) -> bool:
    """Classify a step exception: True = bounded retry, False = isolate."""
    if isinstance(exc, TransientFault):
        return True
    if isinstance(exc, DeterministicFault):
        return False
    if isinstance(exc, (ConnectionError, TimeoutError, OSError)):
        return True
    msg = str(exc)
    return any(m.lower() in msg.lower() for m in _TRANSIENT_MARKERS)


# The named sites ``ServingEngine`` guards with ``_fault_point``.  Each
# fires before the operation it names, with the request ids participating
# in that operation.
FAULT_SITES = (
    "page-alloc",        # PageAllocator growth for a row / admission
    "mixed-step",        # batched ragged prefill dispatch (admission wave)
    "decode-dispatch",   # the tick's decode stage (plain or speculative)
    "sample",            # first-token sampling / blocking result fetch
    # spill-tier / transport sites (host-RAM page store + kv_transport):
    "spill-store",       # page demotion to the host store (pre-gather)
    "swap-in",           # page promotion back into the pool (pre-scatter)
    "kv-export",         # prefix page-set serialization (pre-gather)
    "kv-import",         # page-set import into the pool (pre-scatter)
)

# Replica-tier sites, guarded by the router's backends (one injector per
# backend = per-replica scoping).  Each fires BEFORE the transport
# operation it names, mirroring the engine-site contract.
REPLICA_FAULT_SITES = (
    "replica-connect",   # request send to the replica (connect refused)
    "replica-stream",    # one SSE event read (mid-stream hang)
    "replica-health",    # health probe (slow-loris /health)
    # disaggregated prefill/decode: fires before each handoff leg
    # (/kv/prefill on the prefill replica, /kv/import on the decode
    # replica) — a mid-handoff death is a zero-delivery failover: the
    # client saw nothing, the router falls back to the monolithic path
    "replica-handoff",
)


@dataclass
class _FaultSpec:
    site: str
    exc_factory: "type[BaseException] | Any"
    nth: int = 1              # fire starting at the Nth matching hit
    times: int | None = 1     # how many firings (None = every time)
    request_id: str | None = None  # only when this request participates
    period: int = 0           # >0: re-fire every `period` hits after nth
    hits: int = 0             # matching hits seen so far
    fired: int = 0            # faults actually raised

    def due(self) -> bool:
        if self.hits < self.nth:
            return False
        if self.times is not None and self.fired >= self.times:
            return False
        if self.period > 0:
            return (self.hits - self.nth) % self.period == 0
        # one-shot window: fire on hits nth..nth+times-1 (times=None: all)
        return self.times is None or self.hits < self.nth + self.times


@dataclass
class FaultInjector:
    """Deterministic scripted fault source.

    >>> inj = FaultInjector()
    >>> inj.inject("decode-dispatch", TransientFault, nth=3)
    >>> inj.inject("mixed-step", DeterministicFault, request_id=rid,
    ...            times=None)     # poisoned request: fires every time

    The engine calls ``hit(site, request_ids)`` at each guarded site; the
    first matching spec that is due raises.  A ``request_id``-scoped spec
    only counts hits where that request participates, so quarantining the
    request silences the fault — the property the isolation tests lean on.
    """

    specs: list[_FaultSpec] = field(default_factory=list)
    site_hits: dict = field(default_factory=dict)

    def inject(self, site: str, exc=TransientFault, *, nth: int = 1,
               times: int | None = 1, request_id: str | None = None,
               period: int = 0):
        if site not in FAULT_SITES + REPLICA_FAULT_SITES:
            raise ValueError(f"unknown fault site {site!r}; "
                             f"one of {FAULT_SITES + REPLICA_FAULT_SITES}")
        self.specs.append(_FaultSpec(site=site, exc_factory=exc, nth=nth,
                                     times=times, request_id=request_id,
                                     period=period))
        return self

    def hit(self, site: str, request_ids=()):
        """Called by the engine at a guarded site; raises if a spec is due.

        MUST be called before the device/allocator operation it guards so
        a raise leaves no half-committed device state behind.
        """
        self.site_hits[site] = self.site_hits.get(site, 0) + 1
        for spec in self.specs:
            if spec.site != site:
                continue
            if (spec.request_id is not None
                    and spec.request_id not in request_ids):
                continue
            spec.hits += 1
            if not spec.due():
                continue
            spec.fired += 1
            exc = spec.exc_factory
            if isinstance(exc, type):
                exc = exc(f"injected {spec.site} fault"
                          + (f" (request {spec.request_id})"
                             if spec.request_id else ""))
            raise exc

    @property
    def fired(self) -> int:
        return sum(s.fired for s in self.specs)


def rate_injector(site: str, every: int, exc=TransientFault,
                  limit: int | None = None) -> FaultInjector:
    """Chaos-mode helper: fire ``exc`` on every ``every``-th hit of
    ``site``, up to ``limit`` total — deterministic, so a chaos run is
    reproducible."""
    return FaultInjector().inject(site, exc, nth=every, period=every,
                                  times=limit)
