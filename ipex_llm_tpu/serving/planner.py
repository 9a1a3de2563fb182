"""Model-predictive tick planner: spend the cost model on goodput.

PR 15 built the observe half of the loop — manifest ``cost_analysis``
flops/bytes per locked grid point, perfwatch's per-family wall-clock
attribution, the recompile sentinel — while every engine control knob
stayed static config or an ad-hoc heuristic scattered through
``engine.py``.  This module closes the loop: ONE host-side decision
function runs once per tick (pure bookkeeping, zero new device programs,
JP106's one-dispatch tick untouched) and picks the tick's whole shape —
prefill chunk budget, decode horizon H, per-row speculative draft caps,
and admission count — to maximize predicted goodput (completed-under-
deadline tok/s) subject to per-request deadlines.

The predictor joins three sources:

- the manifest's ``cost_analysis`` for each candidate grid point
  (``PerfWatch.cost_for`` — the analytic roofline seconds), so a cold
  engine plans sensibly before it has measured anything;
- perfwatch's measured per-family tick history, folded into per-step /
  per-prefill-token EWMA rates (``observe`` — called from the flight
  recorder on committed working ticks only), so the plan tracks the real
  machine, not the analytic model;
- the rolling speculative accept-rate window, which prices draft
  economics: a verify round costs about one weight pass either way, so
  speculation pays iff the measured acceptance buys more than the spec
  program's measured per-round premium.

Candidates are drawn ONLY from shapes the engine's own config already
bounds (pow2 horizons up to ``decode_horizon``, pow2 chunk widths up to
``prefill_bucket``, spec widths up to ``spec_k``) and, when a manifest is
loaded, filtered to the locked grid (``point_in_grid``) — the planner
SELECTS among existing lowerings, it never creates one, which is why the
recompile sentinel stays structurally quiet under it and the manifest
``--update`` check is a byte-identical no-op.

Two planners share the interface:

- :class:`StaticPlanner` (``EngineConfig.planner="static"``) reproduces
  the pre-planner engine's decisions exactly — the fixed
  ``step_token_budget`` chunk budget, the admission-wave H-clamp
  (streams joining ⇒ H=1), static per-request spec widths, unbounded
  admission — as ONE plan object, so the escape hatch is bit-identical
  to the PR 15 engine by construction.
- :class:`MPCPlanner` (the default) deviates from those decisions only
  on evidence: deadline slack caps the horizon of the tick a
  latency-sensitive row rides (batch rows keep H×(k+1)); a measured
  accept-rate window that prices drafts underwater masks speculation off
  (re-probing periodically so the window never goes stale); admission is
  deferred for a tick when the wave would blow a critical row's
  deadline; the TTFT budget escalates the chunk share of deadline-bound
  joiners.  With no deadlines and no adverse spec evidence it makes the
  static choices, which is what keeps the equivalence suites green with
  the planner on by default.

Plan timing and fault replay: the engine computes the plan at the top of
``_tick`` BEFORE the checkpoint, snapshots it with the tick state, and
reuses it verbatim across transient-retry re-runs and bisection probes —
a rolled-back tick replays the SAME plan (``tests/test_serving_faults``
pins this).  Decision counters here are sentinel-style monotonic (a
rolled-back tick's planning really happened), mirroring perfwatch's
compile counters.

The plan's horizon is a PRE-TICK decision from pre-tick queue state; the
allocation walk in ``_horizon_step`` remains as the mid-tick safety
clamp (page-pool reality outranks any prediction) and records a
``plan_clamped`` flight-ring field when it cuts a planned horizon.
"""

from __future__ import annotations

import time
from dataclasses import dataclass

__all__ = ["TickPlan", "StaticPlanner", "MPCPlanner", "make_planner"]

# EWMA smoothing for the measured per-family rates: light enough to track
# a regime change inside a few ticks, heavy enough that one noisy tick
# (a GC pause, a cold page fault) does not whipsaw the plan
_EWMA_ALPHA = 0.25

# spec economics: don't judge draft acceptance before the window holds
# this many proposals (a handful of unlucky rounds must not mask spec
# off), and once masked off, re-probe every N planned decode ticks so
# the accept window tracks the workload instead of fossilizing
_SPEC_MIN_PROPOSALS = 64
_SPEC_REPROBE_TICKS = 64
# speculation stays on while measured tokens-per-round beats the spec
# program's measured cost premium by this margin
_SPEC_MARGIN = 1.05


@dataclass(frozen=True)
class TickPlan:
    """One tick's decided shape — immutable, so the checkpoint can hold
    a reference and a rolled-back tick replays it verbatim.

    ``spec_ks`` are per-row CAPS composed with the per-request knobs at
    use time (``min(_row_spec_k(req), cap)``), never replacements — a
    row admitted after planning takes ``spec_cap``.  ``admit_max=None``
    is unbounded (the static engine's behaviour); 0 defers the whole
    wave to a later tick."""
    horizon: int                     # decode-horizon target (pow2, >= 1)
    chunk_budget: int                # mixed-step prefill token budget
    spec_ks: tuple[int, ...]         # per-row draft-width caps [R]
    spec_cap: int                    # cap for rows admitted after planning
    admit_max: int | None = None     # admissions allowed this tick
    predicted_s: float = 0.0         # predicted tick wall seconds (0 = n/a)
    predicted_tok_s: float = 0.0     # predicted aggregate tok/s (0 = n/a)
    clamped: bool = False            # desired point cut to the locked grid
    reason: str = "static"           # decision tag (/health + flight ring)

    @property
    def spec_on(self) -> bool:
        """Whether this tick's fused program carries the spec stage at
        all — the per-tick form of ``EngineConfig.spec_k > 0``."""
        return self.spec_cap > 0 or any(self.spec_ks)

    def flight_fields(self) -> dict:
        """Compact plan stamp for the flight-recorder record."""
        out = {"h": self.horizon, "cb": self.chunk_budget,
               "sk": max(self.spec_ks) if self.spec_ks else 0,
               "why": self.reason}
        if self.admit_max is not None:
            out["admit"] = self.admit_max
        return out

    def view(self) -> dict:
        """The /health ``planner.last`` block."""
        out = {"horizon": self.horizon, "chunk_budget": self.chunk_budget,
               "spec_cap": self.spec_cap, "reason": self.reason,
               "clamped": self.clamped}
        if self.admit_max is not None:
            out["admit_max"] = self.admit_max
        if self.predicted_s:
            out["predicted_s"] = round(self.predicted_s, 6)
        if self.predicted_tok_s:
            out["predicted_tok_s"] = round(self.predicted_tok_s, 2)
        return out


class _PlannerBase:
    """Shared bookkeeping: decision counters (monotonic, sentinel-style
    — a rolled-back tick's plan really was computed) and the measured
    per-family EWMA rates the flight recorder feeds after every
    committed working tick."""

    mode = "base"

    def __init__(self, ec):
        self.ec = ec
        self.decisions: dict[str, int] = {}
        self.last_plan: TickPlan | None = None
        # measured rates, EWMA-smoothed: "step" / "step_spec" are wall
        # seconds per executed decode iteration (plain / spec program),
        # "prefill_tok" is wall seconds per prefill token through the
        # admission wave
        self._rates: dict[str, float] = {}
        self.plans = 0

    # -- engine-facing lifecycle -------------------------------------------

    def plan(self, eng) -> TickPlan:
        raise NotImplementedError

    def observe(self, family: str | None, wall_s: float, executed: int,
                prefill_tokens: int):
        """Fold one committed working tick's measured wall clock into the
        EWMA rates (called from ``_flight_record`` — committed ticks
        only, so a rolled-back tick leaves no rate residue)."""
        if not wall_s or wall_s <= 0:
            return
        if prefill_tokens > 0:
            self._ewma("prefill_tok", wall_s / prefill_tokens)
        elif executed > 0:
            key = ("step_spec" if family == "tick.spec" else "step")
            self._ewma(key, wall_s / executed)

    def _ewma(self, key: str, value: float):
        old = self._rates.get(key)
        self._rates[key] = (value if old is None
                            else old + _EWMA_ALPHA * (value - old))

    def _record(self, plan: TickPlan) -> TickPlan:
        self.plans += 1
        self.decisions[plan.reason] = self.decisions.get(plan.reason, 0) + 1
        if plan.clamped:
            self.decisions["grid_clamped"] = (
                self.decisions.get("grid_clamped", 0) + 1)
        self.last_plan = plan
        return plan

    def view(self) -> dict:
        """The /health ``planner`` block body (the engine adds the
        deadline-miss rate from its own metrics)."""
        out = {"mode": self.mode, "plans": self.plans,
               "decisions": dict(self.decisions)}
        if self.last_plan is not None:
            out["last"] = self.last_plan.view()
        if self._rates:
            out["rates"] = {k: round(v, 6) for k, v in self._rates.items()}
        return out

    # -- shared decision inputs --------------------------------------------

    @staticmethod
    def _streams_joining(eng) -> bool:
        """The admission-wave condition, evaluated over PRE-TICK state:
        rows mid-prefill, or queued work (pending FIFO / inbox) a free
        row could take.  This is the pre-planner ``_horizon_step``
        clamp's exact predicate, moved to plan time — the one visible
        difference is an arrival racing into the inbox AFTER planning
        waits out at most one already-planned horizon."""
        if eng._prefilling:
            return True
        return ((bool(eng._pending) or not eng._inbox.empty())
                and eng._free_row() is not None)


class StaticPlanner(_PlannerBase):
    """The escape hatch: today's decisions, verbatim, as one plan.

    Horizon folds the admission-wave clamp (streams joining ⇒ 1, else
    ``decode_horizon``); the chunk budget is the resolved
    ``step_token_budget``; spec caps are the no-op ``spec_k`` everywhere
    (per-request opt-outs stay where they always were, in
    ``_row_spec_k``); admission is unbounded.  No prediction, no grid
    filtering, no deviation — bit-identical to the PR 15 engine."""

    mode = "static"

    def plan(self, eng) -> TickPlan:
        ec = self.ec
        h = ec.decode_horizon
        if h > 1 and self._streams_joining(eng):
            h = 1
        return self._record(TickPlan(
            horizon=max(h, 1),
            chunk_budget=eng._step_budget,
            spec_ks=(ec.spec_k,) * ec.max_rows,
            spec_cap=ec.spec_k,
            admit_max=None,
            reason="static"))


class MPCPlanner(_PlannerBase):
    """Goodput-maximizing planner: model-predictive over one tick.

    The decision order matters — admission first (a deferred wave
    removes the joiners from the horizon condition), then speculation
    (its cost model feeds the per-step rate), then the horizon over the
    grid-filtered candidate ladder under the tightest deadline slack."""

    mode = "mpc"

    def __init__(self, ec):
        super().__init__(ec)
        # spec hysteresis: ticks planned since speculation was masked
        # off (drives the periodic re-probe that keeps the accept
        # window live)
        self._spec_off_ticks = 0

    # -- measured / analytic cost -------------------------------------------

    def _step_rate(self, eng, spec_on: bool, horizon: int) -> float:
        """Predicted wall seconds per decode iteration: the measured
        EWMA when the family has history, else the manifest's analytic
        roofline for the candidate point (EWMA-corrected only in the
        sense that measurement replaces it as soon as one tick lands),
        else 0.0 = unknown (deadline capping disabled rather than
        guessed)."""
        measured = self._rates.get("step_spec" if spec_on else "step")
        if measured:
            return measured
        perf = eng.perf
        if perf is None:
            return 0.0
        point = eng._perf_point(horizon, width=0, spec=spec_on)
        cost = perf.cost_for(point, max(horizon, 1))
        if cost is None or not perf.peak_flops:
            return 0.0      # no cost entry, or no published peaks (off-TPU)
        flops, byts = cost
        sec = max(flops / perf.peak_flops, byts / perf.peak_bytes_s)
        return sec / max(horizon, 1)

    # -- sub-decisions -------------------------------------------------------

    def _deadline_slacks(self, eng, now: float) -> list[float]:
        """Wall-clock slack of every in-flight decode row with a
        deadline (queued requests gate admission, not the horizon)."""
        out = []
        for r in eng.rows:
            if r is None:
                continue
            d = eng._deadline_of(r)
            if d is not None:
                out.append(d - (now - r.submitted_s))
        return out

    def _spec_decision(self, eng) -> tuple[int, str | None]:
        """Draft economics from the rolling accept window: speculation
        stays at full width until the window holds enough proposals to
        judge; then tokens-per-round (1 free token + measured accepted
        drafts) must beat the spec program's measured per-round cost
        premium, or the caps mask to 0 (the program drops back to the
        plain steady form — a locked point, not a new one).  Masked-off
        spec re-probes periodically so the window keeps tracking the
        workload."""
        k = self.ec.spec_k
        if k <= 0:
            return k, None
        window = list(eng._spec_window)
        prop = sum(p for p, _ in window)
        acc = sum(a for _, a in window)
        if prop < _SPEC_MIN_PROPOSALS:
            return k, None
        rounds = max(len(window), 1)
        tokens_per_round = 1.0 + acc / rounds
        s_spec = self._rates.get("step_spec")
        s_plain = self._rates.get("step")
        premium = (s_spec / s_plain if s_spec and s_plain else 1.0)
        if tokens_per_round >= premium * _SPEC_MARGIN:
            self._spec_off_ticks = 0
            return k, None
        self._spec_off_ticks += 1
        if self._spec_off_ticks >= _SPEC_REPROBE_TICKS:
            self._spec_off_ticks = 0
            return k, "spec_probe"
        return 0, "spec_off"

    def _grid_horizons(self, eng, cands: list[int], spec_on: bool
                       ) -> tuple[list[int], bool]:
        """Filter horizon candidates to the manifest-locked grid.  A
        candidate set the grid covers not at all keeps every candidate
        (degraded mode: the sentinel still flags, exactly as the static
        engine would) — the planner must never brick serving over a
        missing lock entry."""
        perf = eng.perf
        if perf is None or perf.grid is None:
            return cands, False
        from ipex_llm_tpu.serving.perfwatch import point_in_grid

        kept = [h for h in cands
                if point_in_grid(eng._perf_point(h, width=0, spec=spec_on),
                                 perf.grid)]
        if not kept:
            return cands, False
        return kept, max(kept) < max(cands)

    # -- the decision function ----------------------------------------------

    def plan(self, eng) -> TickPlan:
        ec = self.ec
        now = time.perf_counter()
        reason = "steady"
        slacks = self._deadline_slacks(eng, now)
        min_slack = max(min(slacks), 0.0) if slacks else None

        # speculation first: its verdict picks which program family's
        # measured rate prices the rest of the tick
        spec_cap, spec_reason = self._spec_decision(eng)
        if spec_reason:
            reason = spec_reason
        s_step = self._step_rate(eng, spec_cap > 0, ec.decode_horizon)

        # admission: normally unbounded (rows are the real limit), but a
        # wave that would turn the next ticks into H=1 mixed ticks is
        # DEFERRED while an in-flight row's deadline cannot absorb even
        # two plain ticks — finish the critical row first, admit next
        # tick (the queued request's own deadline is still enforced at
        # admission by _expire_deadlines)
        admit_max = None
        queued = bool(eng._pending) or not eng._inbox.empty()
        if (queued and min_slack is not None and s_step > 0
                and eng._free_row() is not None
                and min_slack < 2.0 * s_step * max(ec.decode_horizon, 1)):
            admit_max = 0
            reason = "admit_deferred"

        # the admission-wave condition over pre-tick state — a deferred
        # wave is excluded from it on purpose (that IS the deferral)
        joining = (bool(eng._prefilling) if admit_max == 0
                   else self._streams_joining(eng))

        if joining:
            cands = [1]
            if reason == "steady":
                reason = "joining"
        else:
            top = max(ec.decode_horizon, 1)
            cands = sorted({1 << i for i in range(top.bit_length())
                            if (1 << i) <= top} | {top})
        cands, clamped = self._grid_horizons(eng, cands, spec_cap > 0)

        # deadline slack caps the horizon of the tick a latency-bound
        # row rides: the tick must END before the tightest deadline, so
        # its finish/timeout epoch lands in time (batch rows on the same
        # tick simply ride the shorter horizon)
        if min_slack is not None and s_step > 0 and len(cands) > 1:
            cap = max(int(min_slack / s_step), 1)
            feasible = [c for c in cands if c <= cap]
            if feasible and max(feasible) < max(cands):
                reason = "deadline_h_cap"
            cands = feasible or [min(cands)]
        horizon = max(cands)

        # chunk budget: static share unless a deadline-bound joiner is
        # mid-prefill with more prompt left than its share advances per
        # tick — then every joining row gets the full bucket (TTFT
        # escalation; widths stay pow2 <= prefill_bucket, so no new
        # program shapes)
        budget = eng._step_budget
        if eng._prefilling:
            n_join = len(eng._prefilling)
            share = max(1, budget // max(n_join, 1))
            tight = False
            for row, rem in eng._prefilling.items():
                req = eng.rows[row]
                if req is None or len(rem) <= share:
                    continue
                d = eng._deadline_of(req)
                if d is not None and (d - (now - req.submitted_s)
                                      < d * 0.5):
                    tight = True
                    break
            if tight:
                budget = min(ec.prefill_bucket * n_join,
                             ec.prefill_bucket * ec.max_rows)
                reason = "ttft_escalate"

        # predicted economics for the chosen shape (flight ring + the
        # perf_plan_error histogram measure the model against reality)
        n_active = sum(1 for i, r in enumerate(eng.rows)
                       if r is not None and i not in eng._prefilling)
        predicted_s = horizon * s_step if s_step else 0.0
        predicted_tok = float(horizon * max(n_active, 0))
        predicted_tok_s = (predicted_tok / predicted_s
                           if predicted_s and predicted_tok else 0.0)

        return self._record(TickPlan(
            horizon=max(horizon, 1),
            chunk_budget=budget,
            spec_ks=(spec_cap,) * ec.max_rows,
            spec_cap=spec_cap,
            admit_max=admit_max,
            predicted_s=predicted_s,
            predicted_tok_s=predicted_tok_s,
            clamped=clamped,
            reason=reason))


def make_planner(ec) -> _PlannerBase:
    """Resolve ``EngineConfig.planner`` — "mpc" (the default) or the
    "static" escape hatch."""
    mode = getattr(ec, "planner", "mpc") or "mpc"
    if mode == "mpc":
        return MPCPlanner(ec)
    if mode == "static":
        return StaticPlanner(ec)
    raise ValueError(
        f"unknown EngineConfig.planner {mode!r}: expected 'mpc' or "
        "'static'")
