"""Device-time observatory for the serving tier (the PR 15 tentpole).

Three joined capabilities, all pure host bookkeeping threaded through the
points the transactional tick already visits (no new device programs, no
new syncs — JP106's one-dispatch tick is untouched):

- **Per-program device-time attribution**: every committed working tick's
  wall clock is classified into four buckets that PARTITION it exactly —

  * ``dispatch``   — host time inside the jitted call(s): trace/compile
    lookup + argument upload + async enqueue;
  * ``device``     — the window between the last dispatch return and the
    tick's completion barrier *starting*: the device is executing while
    the host runs overlapped bookkeeping (host work here is off the
    critical path, which is why it attributes to the device);
  * ``sync``       — host BLOCKED on the per-tick device->host
    materialization (the device is still executing: ``device + sync`` is
    the host's best view of device-busy time without a profiler);
  * ``bookkeep``   — everything else (admission, page allocation, drain
    walks, emission staging) = ``wall - dispatch - device - sync``.

  Buckets accumulate into rollback-covered :class:`observe.Histogram`
  objects keyed ``perf_<family>_<bucket>_s`` per program family
  (``tick.steady`` / ``tick.admission`` / ``tick.spec`` for the
  ``_ragged_tick`` forms, plus ``swap_in`` and ``handoff`` epoch
  windows), ride the engine's committed
  /metrics exposition (the router fleet-sums them), and stamp per-tick
  fields into the flight-recorder record.

- **Runtime recompile sentinel** — JP104's runtime twin: a
  ``jax.monitoring.register_event_duration_secs_listener`` hook counts
  backend-compile events and seconds, attributes them to the program
  family whose dispatch window they fired inside (compiles happen
  synchronously inside the jitted call on the dispatching thread), and
  classifies each against the manifest-locked grid in
  ``analysis/programs.lock.json``:

  * first compile of a grid point = **cold** (the budgeted warm-up
    compile the static audit priced);
  * a compile for a point ALREADY compiled in this engine =
    ``compiles_warm`` (the jit cache should have hit — a shape/semantic
    retrace is eating seconds mid-serving; chip_smoke.py holds it at 0
    after warm-up);
  * a compile whose point is NOT in the locked grid =
    ``compiles_out_of_grid``, flagged loudly (warn log + /health ``perf``
    block + monotonic /metrics counter + flight-ring field): the engine
    is paying for a program the static recompile-surface audit (JP104)
    never saw.

- **MFU / roofline accounting**: measured per-tick device time (the
  backend-honest ``dispatch - compile + device + sync`` view — see
  ``_device_view``) joins the manifest's ``cost_analysis`` flops /
  bytes-accessed for the dispatched grid point.  The manifest records the
  AUDIT model's cost, so the join scales by the analytic per-token flops
  ratio between the serving model and the audit model (decode cost is
  weight-matmul dominated, so one ratio serves flops and bytes; XLA's
  cost analysis counts a while-loop body ONCE, so the decode-horizon
  estimate multiplies by the tick's executed iteration count — which the
  engine already syncs as ``n_exec``).  Reported per tick class:
  achieved flops/s, achieved bytes/s, and MFU = achieved / peak, where
  peak comes from the ``device_kind``-keyed ``DEVICE_PEAKS`` table (an
  unknown TPU raises; off the TPU platform MFU is not measured).

- **Host phase spans**: ``phase(name, **attrs)`` brackets one phase of
  the engine thread's tick (``PHASES``).  Each phase is a
  ``jax.profiler.TraceAnnotation`` named ``engine/<phase>`` — so inside a
  profiler capture (the benchmark's, or an operator's ``/debug/profile``)
  it lies on the host plane of the same trace as the device events, on
  one clock — and a monotonic counter ``perf_phase_<phase>_s`` of its
  ``perf_counter`` SELF time (nested phases subtracted, so the counters
  of the phases inside ``tick`` partition ``perf_phase_tick_s``, the
  tick's whole wall clock).  Beside it, by the same rules from the same
  entry, for the phases of ``CPU_PHASES`` (``tick`` and those that call
  into the runtime and so release the interpreter),
  ``perf_phase_<phase>_cpu_s``: the engine thread's CPU time
  (``time.thread_time``) in the phase.  ``wall - cpu`` is the time the
  thread neither computed nor held the interpreter: it waited for the
  interpreter's lock, or was blocked inside a runtime call that had
  released it.  Counters fold in at ``tick_finish`` under the
  attribution rule above: committed working ticks only.  What closes
  after ``tick_finish`` has closed the books (the rest of
  ``engine/record`` and of ``engine/tick``) is counted into the NEXT
  committed tick.  With a program in flight behind the running one (the
  engine's ``_queue_next``) a tick may dispatch the NEXT tick's program
  (``dispatch(ahead=True)``: a span without ``variant``), reads one an
  earlier tick dispatched (``awaited``: the tick's family and point are
  that program's) and marks where the queued one starts to run
  (``running``): each committed working tick is still attributed once.

Engines whose grid point the manifest does not cover (bigger row counts,
wider buckets than the audit sampled) still get full attribution and
sentinel compile counting — only the MFU join reports None, and
out-of-grid compiles flag, which is the message: extend the audit grid
(``scripts/jaxprcheck --update``) to cover the config you serve.
"""

from __future__ import annotations

import logging
import threading
import time
from contextlib import contextmanager

from jax.profiler import TraceAnnotation

from ipex_llm_tpu.serving.observe import FAST_LATENCY_BUCKETS_S, Histogram

__all__ = [
    "PerfWatch",
    "BUCKETS",
    "PHASES",
    "CPU_PHASES",
    "SLOW_SYNC_WAIT_S",
    "PLAN_ERROR_BUCKETS",
    "model_flops_per_token",
    "parse_point_key",
    "locked_points",
    "point_in_grid",
    "outside_axes",
    "resolve_peaks",
    "DEVICE_PEAKS",
]

log = logging.getLogger("ipex_llm_tpu.perfwatch")

BUCKETS = ("dispatch", "device", "sync", "bookkeep")

# the engine thread's tick, as host phase spans (``engine/<phase>`` on the
# profiler's timeline) and ``perf_phase_<phase>_s`` / ``_cpu_s`` counters:
# ``tick`` is the parent, the rest partition it in the order a tick visits
# them.  ``sync`` is the tick's device->host window; inside it
# ``sync_wait`` is the ONE fetch of the tick's packed results, which
# blocks until the program ends (the device is busy), and ``sync_fetch``
# the taking apart of that buffer on the host (no transfer since the
# results come home packed), so ``sync`` itself is the remainder
PHASES = ("tick", "plan", "checkpoint", "admit", "prepare", "dispatch",
          "sync", "sync_wait", "sync_fetch", "drain", "commit", "record")

# the phases whose thread-CPU time is read beside their wall time: the
# whole tick, and the phases that call into the runtime and so RELEASE the
# interpreter (a snapshot's copies, the upload, the jitted call, the
# fetches, the drain around them).  Not all of them: ``time.thread_time``
# is a system call that costs 6.2 us on the chip's host (0.13 us for
# ``perf_counter``; PERF.md section 6, PR 42), and 25 of them a tick were
# 1 % of the shortest tick.  A phase that is not read books no CPU time
# and passes what its children read up to its parent, so ``drain``'s holds
# the remainder of ``sync`` (a few statements)
CPU_PHASES = frozenset(("tick", "checkpoint", "prepare", "dispatch",
                        "sync_wait", "sync_fetch", "drain"))

# a committed tick whose ``sync_wait`` exceeds this has lost seconds to a
# program that did not start (PERF.md section 7, the intermittent stall;
# the slowest tick of any benchmark cell is 0.16 s): the engine freezes
# the flight ring around it (dump reason ``slow_tick``)
SLOW_SYNC_WAIT_S = 1.0

# the ``variant`` attribute of an ``engine/dispatch`` span, by what the
# dispatched tick program holds: a decode stage alone, prefill chunks with
# a decode stage, prefill chunks alone, or the speculative loop
_VARIANT = {"tick.steady": "steady", "tick.admission": "mixed",
            "tick.spec": "spec"}

# planner prediction error, |actual - predicted| / predicted: RATIO
# buckets, not seconds — a 10ms tick mispredicted by 5ms and a 1s tick
# mispredicted by 500ms are the same 0.5 model miss.  Fleet-summable
# like every other histogram here.
PLAN_ERROR_BUCKETS = (0.01, 0.02, 0.05, 0.1, 0.2, 0.5, 1.0, 2.0, 5.0)

# jax.monitoring event names: one backend_compile per
# compiled program — THE unit the sentinel counts — while the trace/
# lowering events fire per (possibly nested) jaxpr and would overcount.
_COMPILE_COUNT_EVENT = "/jax/core/compile/backend_compile_duration"
_COMPILE_TIME_PREFIX = "/jax/core/compile/"

# magnitude axes of the ragged-tick grid: the audit samples power-of-two
# representatives (rows 4/8, width 8/128, horizon 1/8), and the engine's
# budget clamping only ever generates power-of-two values on them — so
# membership admits any pow2 value up to the locked maximum of the
# structurally-matching group.  Every other axis (kv, wq, tp, cq, wd,
# spec) is structural: it must match a locked point exactly (spec: any
# value up to the locked max, since per-request clamps keep it bounded).
_MAG_AXES = ("rows", "width", "horizon")

# retrace-driving shape axes the engine keys its warm/cold compile dedup
# on but the audit grid does NOT lock (its builders fix them: batch pad
# p=2, table-width bucket, eos pad width 2) — they ride the sentinel's
# point identity so a fresh pow2 batch pad is a COLD compile, not a
# false warm flag, and the membership check ignores them.
_UNLOCKED_AXES = ("pb", "maxp", "ew")

# Published per-chip peaks, keyed by the ``device_kind`` jax reports:
# (bf16 FLOP/s, HBM bytes/s).  The one table MFU and roofline numbers are
# taken against; a TPU that is not in it is an error, not a default.
DEVICE_PEAKS = {
    # Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 819 GB/s
    "TPU v5 lite": (197e12, 819e9),
}


def resolve_peaks(device=None) -> tuple[float, float] | None:
    """(peak_flops_per_s, peak_bytes_per_s) of ``device`` (default: the
    first device jax reports).  None off the TPU platform: utilization of
    a CPU running the XLA:CPU backend or the Pallas interpreter is not a
    number this system reports, so MFU is "not measured" there.  A TPU
    whose ``device_kind`` has no row raises."""
    if device is None:
        import jax

        device = jax.devices()[0]
    if device.platform != "tpu":
        return None
    try:
        return DEVICE_PEAKS[device.device_kind]
    except KeyError:
        raise ValueError(
            f"no published peaks for TPU device_kind "
            f"{device.device_kind!r}: add a row, with its source, to "
            "serving/perfwatch.py::DEVICE_PEAKS") from None


def model_flops_per_token(cfg) -> float:
    """Analytic dense-matmul flops for ONE decode token through the model
    (2 flops per MAC: qkv/o projections, gate+up+down MLP, lm head) — the
    MFU scale basis.  Attention score/value math and norms are omitted on
    both sides of the ratio (they are the same small fraction at decode
    shapes), so the audit-model / serving-model ratio stays honest."""
    h = cfg.hidden_size
    q = cfg.num_heads * cfg.head_dim
    kv = cfg.num_kv_heads * cfg.head_dim
    per_layer = h * (q + 2 * kv) + q * h + 3 * h * cfg.intermediate_size
    return 2.0 * (cfg.num_layers * per_layer + h * cfg.vocab_size)


# ---------------------------------------------------------------------------
# manifest grid membership


def parse_point_key(key: str) -> dict:
    """``"horizon=8,kv=fp8,rows=4"`` -> typed axis dict (ints where the
    value parses, ``False`` for the ``wd=False`` axis)."""
    out: dict = {}
    for part in key.split(","):
        if not part:
            continue
        k, _, v = part.partition("=")
        if v == "False":
            out[k] = False
        elif v == "True":
            out[k] = True
        else:
            try:
                out[k] = int(v)
            except ValueError:
                out[k] = v
    return out


def locked_points(manifest: dict | None,
                  program: str = "serving.ragged_tick") -> list[dict] | None:
    """The locked grid for one program as typed point dicts; None when
    the manifest (or the program's entries) is unavailable — membership
    checks are then disabled rather than false-flagging everything."""
    if not manifest:
        return None
    entries = (manifest.get("programs", {}).get(program, {})
               .get("entries"))
    if not entries:
        return None
    return [parse_point_key(k) for k in entries]


def _structure(point: dict) -> tuple:
    """The structural identity of a grid point: every non-magnitude axis
    verbatim, plus whether the width axis is the steady (0) or the
    admission (>0) form — magnitude values are range-checked per group
    instead of matched exactly (the audit samples pow2 representatives,
    the engine generates the whole pow2 family)."""
    keys = sorted(k for k in point if k not in _MAG_AXES
                  and k != "spec" and k not in _UNLOCKED_AXES)
    return (tuple((k, point[k]) for k in keys),
            int(point.get("width", 0) or 0) > 0,
            "spec" in point and bool(point.get("spec")))


def _pow2(v: int) -> bool:
    return v > 0 and (v & (v - 1)) == 0


def _mag_group(point: dict) -> tuple:
    """The magnitude-bounds grouping: non-magnitude axes MINUS the
    program-form splits (wd, wq, width=0 vs >0).  The audit samples each
    form at representative widths/rows, but the pow2 family the engine's
    budget clamping generates is shared across the forms — a wd=False
    pure-chunk tick at width 16, or an int4 admission wave at width 32,
    is bounded by the widest width the STRUCTURALLY adjacent forms
    sampled (the bf16 admission rows' 128), not by the single width
    that form happened to lower at (the wq form keeps width=8 only
    because wider chunks shape-collide with the widened int4 audit
    model's weight stacks — see the registry grid comment).  Structural
    existence is still exact: a (wq, kv) form with no locked row at all
    flags."""
    keys = sorted(k for k in point if k not in _MAG_AXES
                  and k not in ("spec", "wd", "wq")
                  and k not in _UNLOCKED_AXES)
    return (tuple((k, point[k]) for k in keys),
            "spec" in point and bool(point.get("spec")))


def point_in_grid(point: dict, locked: list[dict] | None) -> bool:
    """Whether a dispatched grid point falls inside the manifest-locked
    recompile surface: its exact structural form (kv/wq/tp/cq/wd/
    steady-vs-admission/spec) must be locked, and each magnitude axis
    (rows/width/horizon) must be a power of two no larger than the
    maximum the audit sampled for the structural family.  ``locked=None``
    (no manifest) admits everything — the sentinel still counts, it just
    cannot classify."""
    if locked is None:
        return True
    if not any(_structure(p) == _structure(point) for p in locked):
        return False
    group = [p for p in locked if _mag_group(p) == _mag_group(point)]
    for ax in _MAG_AXES:
        v = int(point.get(ax, 0) or 0)
        if ax == "width" and v == 0:
            continue            # steady form: width matched structurally
        if not (_pow2(v) and v <= max(int(p.get(ax, 0) or 0)
                                      for p in group)):
            return False
    sp = int(point.get("spec", 0) or 0)
    if sp and sp > max(int(p.get("spec", 0) or 0) for p in group):
        return False
    return True


def outside_axes(point: dict, locked: list[dict] | None) -> str:
    """Which axis puts ``point`` outside the locked grid, for the
    sentinel's warning: the magnitude axes (and ``spec``) that pass the
    largest value the structurally matching entries were audited at or
    are no power of two, else the structural form itself."""
    if not locked or not any(_structure(p) == _structure(point)
                             for p in locked):
        return "its structural form (" + ",".join(
            f"{k}={v}" for k, v in _structure(point)[0]) + ")"
    group = [p for p in locked if _mag_group(p) == _mag_group(point)]
    out = []
    for ax in (*_MAG_AXES, "spec"):
        v = int(point.get(ax, 0) or 0)
        top = max(int(p.get(ax, 0) or 0) for p in group)
        if v > top or (ax != "spec" and v and not _pow2(v)):
            out.append(f"{ax}={v} (locked up to {top})")
    return ", ".join(out) or "no single axis"


# ---------------------------------------------------------------------------
# the jax.monitoring listener (module-global, installed once)

_tls = threading.local()           # .watch — the PerfWatch whose dispatch
#                                    window is open on this thread
_install_lock = threading.Lock()
_installed = False


def _on_event(event, duration=0.0, **_kw):
    w = getattr(_tls, "watch", None)
    if w is not None and isinstance(event, str) \
            and event.startswith(_COMPILE_TIME_PREFIX):
        w._compile_event(event, float(duration))


def _install_listener():
    """Register the module's single jax.monitoring listener: one global
    hook fans out to the thread-local active watch, so engines on
    different threads — in-process fleets included — attribute their own
    compiles, and the listener list does not grow with every engine a
    process builds."""
    global _installed
    with _install_lock:
        if _installed:
            return
        try:
            import jax.monitoring as _mon

            _mon.register_event_duration_secs_listener(_on_event)
            _installed = True
        except Exception:       # stripped install: sentinel degrades to 0s
            log.warning("jax.monitoring unavailable: the recompile "
                        "sentinel will not observe compile events")


# ---------------------------------------------------------------------------
# PerfWatch


def _fam_key(family: str) -> str:
    return family.replace(".", "_").replace("-", "_")


class PerfWatch:
    """The engine-facing observatory facade.

    Lifecycle (engine thread): ``tick_begin()`` opens the tick scratch;
    ``dispatch(family, point)`` wraps every jitted call (timing window +
    compile attribution + the tick-dispatch count the JP106 cross-check
    compares against the engine's hand-maintained counter);
    ``note_sync(seconds)`` marks the blocking materializations;
    ``tick_finish(...)`` (called ONLY for committed working ticks, from
    the flight recorder) classifies the buckets, joins MFU, and returns
    the per-tick flight fields; ``tick_abort()`` discards the scratch of
    a rolled-back tick — attribution residue cannot survive a rollback
    because nothing is accumulated before ``tick_finish``.

    Sentinel counters (``compiles_*``) are monotonic and deliberately
    NOT rollback-covered: a compile really happened even if the tick it
    fired in rolled back (same rule as the ``rejected`` counter).

    ``hists`` is the dict the histograms register into — the engine
    passes its own ``self.hists`` so checkpoint/rollback/commit and the
    /metrics exposition cover them with zero extra plumbing.
    """

    def __init__(self, hists: dict | None = None, manifest: dict = None,
                 flops_scales: dict | None = None,
                 peak_flops: float | None = None,
                 peak_bytes_s: float | None = None,
                 program: str = "serving.ragged_tick"):
        self.hists = hists if hists is not None else {}
        self.grid = locked_points(manifest, program)
        self._cost: dict[str, tuple[int, int]] = {}
        self._cost_points: list[tuple[dict, int, int]] = []
        if manifest:
            entries = (manifest.get("programs", {}).get(program, {})
                       .get("entries", {}))
            for k, e in entries.items():
                rec = (int(e.get("flops", 0) or 0),
                       int(e.get("bytes_accessed", 0) or 0))
                self._cost[k] = rec
                self._cost_points.append((parse_point_key(k), *rec))
        # per-variant serving-model/audit-model flops ratio, keyed like
        # the audit model choice: "bf16" (the default audit model),
        # "sym_int4" (the widened int4 audit model), "tp" (the tp audit
        # model).  Missing key -> 1.0 (the caller IS the audit model).
        self.flops_scales = dict(flops_scales or {})
        # None = not on a TPU: achieved flops/bytes are still joined, MFU
        # and roofline shares are not measured
        pf, pb = ((peak_flops, peak_bytes_s) if peak_flops and peak_bytes_s
                  else resolve_peaks() or (None, None))
        self.peak_flops = float(pf) if pf else None
        self.peak_bytes_s = float(pb) if pb else None
        self._lock = threading.Lock()
        # sentinel state: points already compiled (the warm/cold line),
        # monotonic counters, the last out-of-grid evidence for /health
        self._compiled_points: set[str] = set()
        self.compiles = {"compiles_total": 0, "compiles_warm": 0,
                         "compiles_out_of_grid": 0,
                         "compile_s_total": 0.0}
        self._per_family_compiles: dict[str, dict] = {}
        self.out_of_grid_points: list[str] = []
        # per-family committed aggregates (the MFU join's denominators)
        self._fam: dict[str, dict] = {}
        self.ticks_attributed = 0
        self.dispatch_mismatches = 0
        self._tick = None               # open tick scratch
        self._windows: list[dict] = []  # open window stack (tick + epoch)
        # host phase spans: committed counters (wall and thread CPU), the
        # open tick's phases as {name: (wall, cpu)}, what closed after the
        # last commit (folded into the next one), where a closing phase
        # adds (None: nowhere), the open phases as [name, wall clock, cpu
        # clock (None: not read), wall nested, cpu nested], the open
        # phase_to() phase
        self.phase_s = dict.fromkeys(PHASES, 0.0)
        self.phase_cpu_s = dict.fromkeys(
            (p for p in PHASES if p in CPU_PHASES), 0.0)
        self._phase_open: dict = {}
        self._phase_tail: dict = {}
        self._phase_sink: dict | None = None
        self._phase_stack: list[list] = []
        self._phase_linear = None
        _install_listener()

    # -- window / tick lifecycle (engine thread) ----------------------------

    def tick_begin(self):
        self._tick = {"t0": time.perf_counter(), "dispatch": [],
                      "sync": [], "families": [], "points": [],
                      "tick_dispatches": 0, "compiles": 0,
                      "compiles_warm": 0, "out_of_grid": 0,
                      "compile_s": 0.0, "executed": 1}
        self._windows = [self._tick]

    def tick_abort(self):
        """Discard the rolled-back tick's scratch: nothing it measured
        was committed, so nothing it measured is kept (sentinel compile
        counters already landed — compiles are real either way)."""
        self.phase_to(None)
        self._tick = None
        self._windows = []
        self._phase_sink = None     # its phases, and the probes', count nowhere

    @contextmanager
    def phase(self, name: str, **attrs):
        """One host phase of the tick (``PHASES``): a profiler span
        ``engine/<name>`` carrying ``attrs`` (outside a capture a
        TraceAnnotation is a flag test) and its SELF time on two clocks,
        ``perf_counter`` and (``CPU_PHASES``) this thread's
        ``thread_time`` — nested phases subtracted; ``tick``, the
        parent, whole — towards ``perf_phase_<name>_s`` and
        ``perf_phase_<name>_cpu_s``.  The
        time counts when ``tick_finish`` commits the tick; a phase that
        closes after it (``record``, ``tick``) counts into the next
        committed tick."""
        if name == "tick":
            self._phase_open = self._phase_sink = {}
        with TraceAnnotation("engine/" + name, **attrs):
            entry = [name, time.perf_counter(),
                     time.thread_time() if name in CPU_PHASES else None,
                     0.0, 0.0]
            self._phase_stack.append(entry)
            try:
                yield
            finally:
                wall = time.perf_counter() - entry[1]
                # a phase that is not read passes its children's on
                cpu = (entry[4] if entry[2] is None
                       else time.thread_time() - entry[2])
                self._phase_stack.pop()
                if self._phase_stack:
                    self._phase_stack[-1][3] += wall
                    self._phase_stack[-1][4] += cpu
                self._phase_book(name, wall, cpu, entry[3], entry[4])
                if name == "tick":
                    self._phase_sink = None   # between ticks counts nowhere

    def _phase_book(self, name: str, wall: float, cpu: float,
                    wall_inside: float, cpu_inside: float):
        """``wall`` seconds and ``cpu`` thread-CPU seconds of phase
        ``name``, the ``_inside`` part of each in the phases nested in
        it, to where a closing phase counts now."""
        sink = self._phase_sink
        if sink is not None:
            if name != "tick":
                wall, cpu = wall - wall_inside, cpu - cpu_inside
            w, c = sink.get(name, (0.0, 0.0))
            sink[name] = (w + wall, c + cpu)

    def phase_to(self, name: str | None):
        """``phase`` for straight-line code too long to indent: close the
        phase the last ``phase_to`` opened and open ``name`` at the same
        point of the code (None: only close; the open one again: stay)."""
        if self._phase_linear is not None:
            if self._phase_linear[0] == name:
                return
            self._phase_linear[1].__exit__(None, None, None)
            self._phase_linear = None
        if name is not None:
            cm = self.phase(name)
            cm.__enter__()
            self._phase_linear = (name, cm)

    @staticmethod
    def span_attrs(family: str, point: dict | None = None, **attrs) -> dict:
        """What an ``engine/dispatch`` span says of the program it stands
        for: which tick variant, at which grid point (plus ``attrs``)."""
        pt = point or {}
        variant = ("chunk" if pt.get("wd") is False
                   else _VARIANT.get(family, family))
        return dict(variant=variant, family=family, p_b=pt.get("pb", 0),
                    width=pt.get("width", 0), maxp=pt.get("maxp", 0),
                    **attrs)

    @contextmanager
    def dispatch(self, family: str, point: dict | None = None,
                 tick: bool = True, ahead: bool = False, **attrs):
        """Timing window around ONE jitted call.  Runs the sentinel on
        any compile events that fire inside (they fire synchronously on
        this thread), stamps the family/point on the open tick scratch,
        and counts toward the tick-dispatch cross-check when ``tick``.
        The window is the ``dispatch`` phase: its span says which tick
        variant was dispatched, at which grid point (plus ``attrs``).

        ``ahead``: the program is queued BEHIND the one this tick is
        about to read and belongs, by family and point, to the tick that
        will read it (``awaited``).  Its span carries the variant as
        ``ahead`` and no ``variant``: the device runs another program
        while it is open, so no reader may take the span for the window of
        an execution (``running`` marks that window)."""
        span = self.span_attrs(family, point, **attrs)
        if ahead:
            span["ahead"] = span.pop("variant")
        with self.phase("dispatch", **span):
            prev = getattr(_tls, "watch", None)
            _tls.watch = self
            n0 = self.compiles["compiles_total"]
            s0 = self.compiles["compile_s_total"]
            self._window_point = point
            t0 = time.perf_counter()
            try:
                yield
            finally:
                t1 = time.perf_counter()
                _tls.watch = prev
                self._window_point = None
                for w in self._windows:
                    w["dispatch"].append((t0, t1))
                    if not ahead:
                        w["families"].append(family)
                        if point is not None:
                            w["points"].append(point)
                    if tick and w is self._tick:
                        w["tick_dispatches"] += 1
                    w["compiles"] += self.compiles["compiles_total"] - n0
                    w["compile_s"] += self.compiles["compile_s_total"] - s0
                with self._lock:
                    fc = self._per_family_compiles.setdefault(
                        family, {"compiles": 0, "compile_s": 0.0,
                                 "dispatches": 0})
                    fc["dispatches"] += 1
                    dn = self.compiles["compiles_total"] - n0
                    if dn:
                        fc["compiles"] += dn
                        fc["compile_s"] += round(
                            self.compiles["compile_s_total"] - s0, 6)

    def awaited(self, family: str, point: dict | None):
        """The open tick reads a program that an EARLIER tick dispatched
        (``dispatch(ahead=True)``): its family and point are this tick's.
        A tick that dispatches nothing itself opens an empty
        ``engine/dispatch`` span (stat ``queued``, no ``variant``), so
        that a reader counting working ticks by that span counts it."""
        t = self._tick
        if t is None:
            return
        t["families"].append(family)
        if point is not None:
            t["points"].append(point)
        if not t["dispatch"]:
            with self.phase("dispatch", queued=1):
                pass

    @staticmethod
    def running(attrs: dict):
        """Right after a tick's sync, with a program queued behind the
        one just read: from here to the end of ITS sync the device runs
        that program.  An ``engine/dispatch`` span of no length carrying
        what its dispatch would have (``span_attrs``) and ``queued``: no
        sync follows it inside this tick, so a reader's window for it
        runs to the next such span, one execution long."""
        with TraceAnnotation("engine/dispatch", queued=1, **attrs):
            pass

    def note_sync(self, seconds: float):
        """One blocking device->host materialization ending NOW (the
        caller just measured it, inside its ``sync`` phase) — recorded as
        a [start, end] window on every open scratch."""
        t1 = time.perf_counter()
        for w in self._windows:
            w["sync"].append((t1 - seconds, t1))

    def note_executed(self, n: int):
        """The tick's executed horizon-iteration count (``n_exec``): the
        multiplier for the manifest's once-counted loop-body flops."""
        if self._tick is not None:
            self._tick["executed"] = max(int(n), 1)

    @contextmanager
    def epoch_window(self, family: str):
        """Attribution window for epoch-boundary work (swap-in, handoff
        export/import): its own wall span classified with the same bucket
        math, nested inside a tick or free-standing between ticks.  The
        aggregate updates at close — epoch work either happens entirely
        before a fault point (handoff host ops run between ticks) or is
        re-done wholesale by the retried tick (swap-in), so per-window
        accounting stays honest without checkpoint plumbing."""
        w = {"t0": time.perf_counter(), "dispatch": [], "sync": [],
             "families": [], "points": [], "tick_dispatches": 0,
             "compiles": 0, "compiles_warm": 0, "out_of_grid": 0,
             "compile_s": 0.0, "executed": 1}
        self._windows.append(w)
        try:
            yield
        except BaseException:
            # an aborted window (injected fault mid-swap-in, transport
            # error) measures nothing: the retried tick re-runs it whole
            self._windows.remove(w)
            raise
        else:
            self._windows.remove(w)
            buckets, wall = self._classify(w, time.perf_counter())
            # histogram observations are rollback-covered by the engine
            # checkpoint (they live in engine.hists); the un-checkpointed
            # family aggregates defer to tick commit when a tick is open,
            # so a rolled-back tick's swap-in leaves no residue there
            for b, v in buckets.items():
                self._hist(family, b).observe(v)
            dev_s = self._device_view(buckets, w["compile_s"])
            if self._tick is not None:
                self._tick.setdefault("epoch", []).append(
                    (family, buckets, wall, dev_s))
            else:
                self._fam_update(family, buckets, wall, device_s=dev_s)

    # -- the sentinel (listener thread side = dispatching thread) -----------

    def _compile_event(self, event: str, seconds: float):
        with self._lock:
            self.compiles["compile_s_total"] = round(
                self.compiles["compile_s_total"] + seconds, 6)
            if event != _COMPILE_COUNT_EVENT:
                return
            self.compiles["compiles_total"] += 1
            point = getattr(self, "_window_point", None)
            if point is None:
                return
            key = ",".join(f"{k}={point[k]}" for k in sorted(point))
            warm = key in self._compiled_points
            self._compiled_points.add(key)
            in_grid = point_in_grid(point, self.grid)
            if warm:
                self.compiles["compiles_warm"] += 1
                if self._tick is not None:
                    self._tick["compiles_warm"] += 1
                log.warning(
                    "warm-path recompile of grid point %s (%d warm "
                    "compiles total): the jit cache should have hit — "
                    "a retrace is eating compile seconds mid-serving",
                    key, self.compiles["compiles_warm"])
            if not in_grid:
                self.compiles["compiles_out_of_grid"] += 1
                if self._tick is not None:
                    self._tick["out_of_grid"] += 1
                if key not in self.out_of_grid_points:
                    self.out_of_grid_points.append(key)
                    del self.out_of_grid_points[:-16]
                if self.compiles["compiles_out_of_grid"] == 1:
                    log.warning(
                        "compile for grid point %s OUTSIDE the manifest-"
                        "locked recompile surface (analysis/programs.lock."
                        "json) on %s: the JP104 static audit never priced "
                        "this program — extend the registry grid and rerun "
                        "`scripts/jaxprcheck --update`, or this engine "
                        "pays unbudgeted compiles (further out-of-grid "
                        "compiles of this engine log at debug level; "
                        "/health perf.sentinel counts them)",
                        key, outside_axes(point, self.grid))
                else:
                    log.debug("compile for grid point %s outside the "
                              "locked grid on %s", key,
                              outside_axes(point, self.grid))

    # -- bucket math ---------------------------------------------------------

    @staticmethod
    def _classify(scratch: dict, t1: float) -> tuple[dict, float]:
        """Partition ``[scratch.t0, t1]`` into the four buckets.  By
        construction ``sum(buckets) == wall`` exactly: ``device`` is the
        host-idle/overlapped measure between the first dispatch start
        and the last device-activity end, minus the dispatch/sync
        windows themselves; ``bookkeep`` is the remainder."""
        t0 = scratch["t0"]
        wall = max(t1 - t0, 0.0)
        disp = sorted(scratch["dispatch"])
        sync = sorted(scratch["sync"])
        d_s = sum(b - a for a, b in disp)
        s_s = sum(b - a for a, b in sync)
        dev = 0.0
        if disp:
            span0 = disp[0][0]
            span1 = max([b for _, b in disp] + [b for _, b in sync])
            busy = sorted(disp + sync)
            merged: list[list[float]] = []
            for a, b in busy:
                if merged and a <= merged[-1][1]:
                    merged[-1][1] = max(merged[-1][1], b)
                else:
                    merged.append([a, b])
            covered = sum(min(b, span1) - max(a, span0)
                          for a, b in merged
                          if b > span0 and a < span1)
            dev = max((span1 - span0) - covered, 0.0)
        book = max(wall - d_s - s_s - dev, 0.0)
        return ({"dispatch": d_s, "device": dev, "sync": s_s,
                 "bookkeep": book}, wall)

    @staticmethod
    def _device_view(buckets: dict, compile_s: float = 0.0) -> float:
        """The host's best view of device-busy seconds, backend-honest:
        ``device + sync`` (the dispatch-to-barrier window) PLUS the
        dispatch window minus any compile seconds that fired inside it —
        on an async backend dispatch is an enqueue (microseconds, no
        skew), while XLA:CPU executes much of the program synchronously
        inside the call, which would otherwise vanish from the MFU
        denominator entirely."""
        return (max(buckets["dispatch"] - compile_s, 0.0)
                + buckets["device"] + buckets["sync"])

    def _hist(self, family: str, bucket: str) -> Histogram:
        name = f"perf_{_fam_key(family)}_{bucket}_s"
        h = self.hists.get(name)
        if h is None:
            h = self.hists[name] = Histogram(FAST_LATENCY_BUCKETS_S)
        return h

    def note_plan_error(self, predicted_s: float, actual_s: float) -> float:
        """Planner plan-vs-actual: observe the relative prediction error
        into the ``perf_plan_error`` histogram (lazily registered into
        the engine's checkpointed hists dict, so rollback covers it like
        every attribution histogram) and return the rounded error for
        the flight record."""
        err = abs(actual_s - predicted_s) / max(predicted_s, 1e-9)
        h = self.hists.get("perf_plan_error")
        if h is None:
            h = self.hists["perf_plan_error"] = Histogram(PLAN_ERROR_BUCKETS)
        h.observe(err)
        return round(err, 4)

    def _fam_update(self, family: str, buckets: dict, wall: float,
                    flops: float = 0.0, byts: float = 0.0,
                    device_s: float | None = None):
        if device_s is None:
            device_s = self._device_view(buckets)
        with self._lock:
            f = self._fam.setdefault(
                family, {"ticks": 0, "wall_s": 0.0, "device_s": 0.0,
                         "flops": 0.0, "bytes": 0.0, "joined": 0})
            f["ticks"] += 1
            f["wall_s"] = round(f["wall_s"] + wall, 6)
            f["device_s"] = round(f["device_s"] + device_s, 6)
            if flops:
                f["flops"] += flops
                f["bytes"] += byts
                f["joined"] += 1

    # -- cost join -----------------------------------------------------------

    def _scale_for(self, point: dict) -> float:
        if "tp" in point:
            return float(self.flops_scales.get("tp", 1.0))
        if point.get("wq"):
            return float(self.flops_scales.get(str(point["wq"]), 1.0))
        return float(self.flops_scales.get("bf16", 1.0))

    def cost_for(self, point: dict, executed: int = 1
                 ) -> tuple[float, float] | None:
        """(flops, bytes) estimate for ONE tick dispatching ``point`` —
        the manifest's audit-model cost_analysis scaled to the serving
        model, times the executed loop iterations (XLA counts the
        while-loop body once).

        Exact grid points use their entry verbatim.  A point the audit
        sampled AROUND (a bigger pow2 row count, a chunk width between
        the sampled 8 and 128) falls back to the nearest structurally-
        matching entry scaled LINEARLY in rows and width — the manifest
        itself shows both axes linear (rows=8 costs 2.006x rows=4) —
        with the provenance still the locked cost_analysis.  None when
        no structurally-matching entry exists at all (MFU reports None;
        attribution and the sentinel keep working)."""
        clean = {k: v for k, v in point.items() if k not in _UNLOCKED_AXES}
        key = ",".join(f"{k}={clean[k]}" for k in sorted(clean))
        rec = self._cost.get(key)
        scale = self._scale_for(point)
        ex = max(int(executed), 1)
        if rec is not None:
            return rec[0] * scale * ex, rec[1] * scale * ex
        want = _structure(clean)
        rows = int(clean.get("rows", 0) or 0)
        width = int(clean.get("width", 0) or 0)
        hz = int(clean.get("horizon", 1) or 1)
        best = None
        for p, fl, by in self._cost_points:
            if _structure(p) != want:
                continue
            pr = int(p.get("rows", 0) or 0)
            pw = int(p.get("width", 0) or 0)
            dist = (abs(rows - pr) + abs(width - pw)
                    + (0 if int(p.get("horizon", 1) or 1) == hz else 1))
            if best is None or dist < best[0]:
                best = (dist, p, fl, by)
        if best is None:
            return None
        _, p, fl, by = best
        r = 1.0
        if rows and p.get("rows"):
            r *= rows / int(p["rows"])
        if width and p.get("width"):
            r *= width / int(p["width"])
        return fl * scale * r * ex, by * scale * r * ex

    # -- tick close ----------------------------------------------------------

    def tick_finish(self, manual_dispatches: int, working: bool) -> dict:
        """Close the committed tick: classify buckets, cross-check the
        dispatch count, join MFU, fold into the per-family aggregates,
        and return the flight-record fields.  ``working=False`` (idle
        tick) discards the scratch and returns {}.  Raises
        AssertionError (debug builds) on a dispatch-count divergence —
        the runtime enforcement of JP106's hand-maintained bookkeeping.
        """
        scratch, self._tick = self._tick, None
        self._windows = []
        if scratch is None or not working:
            self._phase_sink = None     # an idle tick's phases count nowhere
            return {}
        t1, c1 = time.perf_counter(), time.thread_time()
        # book the phases still open (``record``, ``tick``) up to HERE,
        # innermost first, and restart their clocks: what is booked for
        # this tick partitions its wall clock (and its CPU time) up to
        # now.  This tick's phases, plus what closed after the last
        # committed tick's books did, are what it commits; from here on a
        # closing phase adds to the tail the next committed tick folds in
        wall_in = cpu_in = 0.0
        for entry in reversed(self._phase_stack):
            read = entry[2] is not None
            wall = t1 - entry[1]
            cpu = c1 - entry[2] if read else entry[4] + cpu_in
            self._phase_book(entry[0], wall, cpu, entry[3] + wall_in,
                             entry[4] + cpu_in)
            entry[1:] = t1, c1 if read else None, 0.0, 0.0
            wall_in, cpu_in = wall, cpu
        phases, tail = self._phase_open, self._phase_tail
        self._phase_open = {}
        self._phase_sink = self._phase_tail = {}
        for name, (w, c) in tail.items():
            w0, c0 = phases.get(name, (0.0, 0.0))
            phases[name] = (w0 + w, c0 + c)
        buckets, wall = self._classify(scratch, t1)
        fams = scratch["families"]
        if "tick.spec" in fams:
            family = "tick.spec"
        elif "tick.admission" in fams:
            family = "tick.admission"
        elif fams:
            family = fams[-1]
        else:
            family = "tick.host"
        observed = scratch["tick_dispatches"]
        mismatch = observed != manual_dispatches
        out = {
            "perf_family": family,
            "attrib": {b: round(buckets[b], 6) for b in BUCKETS},
            "wall_s": round(wall, 6),
        }
        # MFU join over the tick's dispatched points (one per tick)
        flops = byts = 0.0
        joined = False
        for point in scratch["points"]:
            cost = self.cost_for(point, scratch["executed"])
            if cost is not None:
                flops += cost[0]
                byts += cost[1]
                joined = True
        dev_s = self._device_view(buckets, scratch["compile_s"])
        if joined and dev_s > 0:
            if self.peak_flops:
                out["mfu"] = round(flops / dev_s / self.peak_flops, 6)
            out["bytes_per_s"] = round(byts / dev_s, 1)
        if scratch["compiles"]:
            out["compiles"] = scratch["compiles"]
            out["compile_s"] = round(scratch["compile_s"], 6)
        if scratch["compiles_warm"]:
            out["compiles_warm"] = scratch["compiles_warm"]
        if scratch["out_of_grid"]:
            out["compiles_out_of_grid"] = scratch["out_of_grid"]
        if scratch["points"]:
            p = scratch["points"][-1]
            out["grid_point"] = ",".join(
                f"{k}={p[k]}" for k in sorted(p))
        if phases:
            out["phases"] = {k: round(w, 6) for k, (w, _) in phases.items()}
            out["phases_cpu"] = {k: round(c, 6)
                                 for k, (_, c) in phases.items()
                                 if k in CPU_PHASES}
        if mismatch:
            self.dispatch_mismatches += 1
            out["dispatch_mismatch"] = {"observed": observed,
                                        "manual": manual_dispatches}
            log.warning(
                "tick dispatch-count divergence: perfwatch observed %d "
                "tick-program dispatch windows but the engine's "
                "hand-maintained _tick_dispatches says %d — one of the "
                "`+= 1` call sites in serving/engine.py drifted from "
                "its dispatch", observed, manual_dispatches)
        for b, v in buckets.items():
            self._hist(family, b).observe(v)
        self._fam_update(family, buckets, wall, flops=flops, byts=byts,
                         device_s=dev_s)
        for e_fam, e_buckets, e_wall, e_dev in scratch.get("epoch", ()):
            # swap-ins committed with this tick (their histograms landed
            # live — the engine checkpoint covers those)
            self._fam_update(e_fam, e_buckets, e_wall, device_s=e_dev)
        with self._lock:
            self.ticks_attributed += 1
            for name, (w, c) in phases.items():
                self.phase_s[name] = self.phase_s.get(name, 0.0) + w
                if name in CPU_PHASES:
                    self.phase_cpu_s[name] += c
        # the debug ASSERT lives in the engine, AFTER the flight ring has
        # recorded this dict — the mismatch evidence must survive the
        # raise (and survive `-O` builds, where only the field remains)
        return out

    # -- views ---------------------------------------------------------------

    def sentinel_view(self) -> dict:
        with self._lock:
            out = dict(self.compiles)
            out["grid_locked"] = (len(self.grid)
                                  if self.grid is not None else None)
            out["grid_points_compiled"] = len(self._compiled_points)
            if self.out_of_grid_points:
                out["out_of_grid_points"] = list(self.out_of_grid_points)
            out["per_family"] = {k: dict(v) for k, v
                                 in self._per_family_compiles.items()}
        return out

    def view(self) -> dict:
        """The /health ``perf`` block: per-family attribution + MFU, the
        sentinel counters, the roofline denominators."""
        fams = {}
        with self._lock:
            fam_snapshot = {k: dict(v) for k, v in self._fam.items()}
        for name, f in fam_snapshot.items():
            row = {"ticks": f["ticks"],
                   "wall_s": round(f["wall_s"], 4),
                   "device_s": round(f["device_s"], 4)}
            if f["joined"] and f["device_s"] > 0:
                row["flops_per_s"] = round(f["flops"] / f["device_s"], 1)
                row["bytes_per_s"] = round(f["bytes"] / f["device_s"], 1)
                if self.peak_flops:
                    row["mfu"] = round(
                        f["flops"] / f["device_s"] / self.peak_flops, 6)
            fams[name] = row
        return {
            "families": fams,
            "ticks_attributed": self.ticks_attributed,
            "dispatch_mismatches": self.dispatch_mismatches,
            "sentinel": self.sentinel_view(),
            "roofline": {"peak_flops": self.peak_flops,
                         "peak_bytes_per_s": self.peak_bytes_s,
                         "flops_scales": dict(self.flops_scales)},
        }

    def mfu(self, family: str | None = None) -> float | None:
        """Aggregate MFU over committed ticks — ``family=None`` joins
        every family with a cost entry; None when nothing joined."""
        with self._lock:
            fams = ([self._fam.get(family)] if family
                    else list(self._fam.values()))
        flops = sum(f["flops"] for f in fams if f)
        dev = sum(f["device_s"] for f in fams if f and f["joined"])
        if not flops or dev <= 0 or not self.peak_flops:
            return None
        return round(flops / dev / self.peak_flops, 6)

    def metrics_numeric(self) -> dict:
        """Flat counters for the /metrics exposition (``perf_`` prefix
        added by the caller); every value is fleet-summable or a
        per-replica gauge the router leaves unsummed."""
        with self._lock:
            out = {k: v for k, v in self.compiles.items()}
            out["ticks_attributed"] = self.ticks_attributed
            out["dispatch_mismatches"] = self.dispatch_mismatches
            for name, f in self._fam.items():
                out[f"{_fam_key(name)}_ticks"] = f["ticks"]
                out[f"{_fam_key(name)}_device_s"] = round(f["device_s"], 6)
            for name, v in self.phase_s.items():
                out[f"phase_{name}_s"] = round(v, 6)
            for name, v in self.phase_cpu_s.items():
                out[f"phase_{name}_cpu_s"] = round(v, 6)
        m = self.mfu()
        if m is not None:
            out["mfu"] = m
        return out

    def dump_fields(self) -> dict:
        """Compact sentinel evidence for a flight-recorder dump
        (_fail_all / quarantine / chaos-gate failure rows)."""
        c = self.compiles
        out = {"perf_compiles_total": c["compiles_total"],
               "perf_compiles_warm": c["compiles_warm"],
               "perf_compiles_out_of_grid": c["compiles_out_of_grid"]}
        if self.out_of_grid_points:
            out["perf_out_of_grid_points"] = list(self.out_of_grid_points)
        return out
