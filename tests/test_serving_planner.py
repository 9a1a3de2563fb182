"""Model-predictive tick planner (serving/planner.py).

The contracts under test (PR 16):

- the "mpc" default deviates from static decisions only on evidence, so
  with no deadlines and no adverse spec signal its streams (tokens AND
  logprobs, greedy + seeded) are bit-identical to the "static" escape
  hatch — and the escape hatch itself reproduces the pre-planner
  engine's clamp decisions (admission-wave H=1, steady H) exactly;
- the plan is computed pre-checkpoint and snapshotted, so a transient
  rollback replays the SAME plan object (the faults suite pins the
  rollback half; here the retried-step identity is pinned directly);
- a plan that would exceed the manifest-locked grid is clamped to the
  largest in-grid candidate, counted under ``grid_clamped``, and stamped
  ``plan_clamped`` in the flight ring;
- draft economics: a rolling accept window pricing drafts underwater
  masks speculation off (the tick dispatches the plain steady program —
  a locked point) and re-probes periodically so the window never
  fossilizes, with the emitted stream still bit-identical;
- an ``admit_max=0`` plan defers the whole admission wave to a later
  tick;
- deadline slack caps the horizon of the tick a latency-bound row rides
  (``deadline_h_cap``), priced from the measured per-step EWMA rate;
- ``planner_view()`` is the /health ``planner`` block: mode, plan
  counts, per-reason decisions, deadline-miss rate;
- plan-vs-actual lands in the ``perf_plan_error`` histogram and the
  flight ring carries the compact plan stamp.

Engines are driven synchronously through ``_tick`` where decision
timing matters, exactly like the faults suite.
"""

import time

import numpy as np
import pytest

from ipex_llm_tpu.serving.engine import (
    EngineConfig,
    Request,
    ServingEngine,
    stream_tokens,
)
from ipex_llm_tpu.serving.planner import (
    MPCPlanner,
    StaticPlanner,
    TickPlan,
    make_planner,
)
from tests.test_decoder import rand_params, tiny_cfg

EC = dict(max_rows=4, max_seq_len=256, page_size=32, prefill_bucket=32)

RNG = np.random.default_rng(61)


@pytest.fixture(scope="module")
def cfg_params():
    cfg = tiny_cfg(vocab_size=131, hidden_size=48, intermediate_size=96,
                   num_heads=4, num_kv_heads=2, head_dim=12,
                   max_position_embeddings=512)
    return cfg, rand_params(cfg, qtype="bf16")


def _wave(cfg, seed=7):
    rng = np.random.default_rng(seed)
    spec = [(40, {}), (70, {"temperature": 0.8, "seed": 99}),
            (24, {}), (50, {})]
    return [Request(prompt_ids=list(rng.integers(0, cfg.vocab_size, n)),
                    max_new_tokens=8, **kw) for n, kw in spec]


def _drive(eng, reqs, max_ticks=3000):
    for r in reqs:
        eng.submit(r)
    for _ in range(max_ticks):
        eng._tick()
        if all(r.finish_reason is not None for r in reqs):
            break
    assert all(r.finish_reason is not None for r in reqs)
    return [list(stream_tokens(r, timeout=10)) for r in reqs]


def _run(cfg, params, **ec_over):
    ec = dict(EC)
    ec.update(ec_over)
    eng = ServingEngine(cfg, params, EngineConfig(**ec))
    reqs = _wave(cfg)
    streams = _drive(eng, reqs)
    return eng, reqs, streams


# -- escape-hatch equivalence ------------------------------------------------

def test_mpc_matches_static_bit_identical(cfg_params):
    """No deadlines, no adverse spec evidence: the default planner makes
    the static choices — greedy + seeded streams, logprobs, finish
    reasons, AND the horizon decision metrics are identical."""
    cfg, params = cfg_params
    es, rs, ss = _run(cfg, params, planner="static", decode_horizon=8)
    em, rm, sm = _run(cfg, params, planner="mpc", decode_horizon=8)
    assert ss == sm
    assert [r.finish_reason for r in rs] == [r.finish_reason for r in rm]
    for a, b in zip(rs, rm):
        np.testing.assert_array_equal(
            np.asarray(a.logprobs, np.float32),
            np.asarray(b.logprobs, np.float32))
    # decision pins, not just stream equality: same effective horizon,
    # same clamp count (the old heuristics' observable decisions)
    for k in ("decode_horizon_effective", "horizon_clamped"):
        assert es.metrics.get(k, 0) == em.metrics.get(k, 0), k


@pytest.mark.parametrize("mode", ["static", "mpc"])
def test_wave_clamp_decision_reproduced(cfg_params, mode):
    """The pre-planner admission-wave clamp, now a plan: a request
    joining an H=8 engine mid-decode rides an H=1 tick (streaming
    granularity for the joiner), then steady ticks return to H=8.  The
    regression pins the DECISION for both planners — the static hatch
    reproduces the deleted heuristic bit-identically, and mpc makes the
    same call absent deadlines."""
    cfg, params = cfg_params
    eng = ServingEngine(cfg, params, EngineConfig(
        decode_horizon=8, planner=mode, **EC))
    a = Request(prompt_ids=list(RNG.integers(0, cfg.vocab_size, 40)),
                max_new_tokens=24)
    eng.submit(a)
    for _ in range(200):
        eng._tick()
        if len(a.output_ids) >= 1:
            break
    eng._tick()      # first pure-decode tick after the admission wave
    assert eng.metrics["decode_horizon_effective"] == 8  # steady
    b = Request(prompt_ids=list(RNG.integers(0, cfg.vocab_size, 40)),
                max_new_tokens=4)
    eng.submit(b)
    eng._tick()     # the wave tick: b admitted, horizon dropped
    assert eng.metrics["decode_horizon_effective"] == 1, (
        f"planner={mode} did not reproduce the admission-wave H-clamp")
    assert eng._plan.horizon == 1
    for _ in range(400):
        eng._tick()
        if b.finish_reason is not None:
            break
    assert b.finish_reason == "length"
    assert eng.metrics["decode_horizon_effective"] == 8  # steady again


def test_static_planner_plan_shape(cfg_params):
    cfg, params = cfg_params
    eng = ServingEngine(cfg, params, EngineConfig(
        planner="static", decode_horizon=4, spec_k=0, **EC))
    p = eng.planner.plan(eng)
    assert isinstance(eng.planner, StaticPlanner)
    assert p.reason == "static" and p.admit_max is None
    assert p.horizon == 4 and p.chunk_budget == eng._step_budget
    assert not p.spec_on


# -- plan lifecycle under faults ---------------------------------------------

def test_plan_checkpointed_and_restored(cfg_params):
    cfg, params = cfg_params
    eng = ServingEngine(cfg, params, EngineConfig(**EC))
    held = eng._plan
    assert held is not None
    snap = eng._checkpoint()
    assert snap["plan"] is held
    eng._plan = None
    eng._rollback(snap)
    assert eng._plan is held


def test_transient_retry_replays_same_plan(cfg_params):
    """A retried tick must re-run under the plan object the aborted tick
    planned — no replanning between rollback and retry (replanning would
    let a mid-fault queue change alter the replay)."""
    from ipex_llm_tpu.serving.faults import FaultInjector, TransientFault

    cfg, params = cfg_params
    inj = FaultInjector().inject("decode-dispatch", TransientFault, nth=3)
    eng = ServingEngine(cfg, params, EngineConfig(
        retry_backoff_s=0.001, decode_horizon=4, **EC),
        fault_injector=inj)
    seen = []
    orig = eng._step_once

    def recording():
        seen.append(eng._plan)
        return orig()

    eng._step_once = recording
    reqs = _wave(cfg)
    _drive(eng, reqs)
    assert inj.fired == 1 and eng.metrics["retries"] == 1
    # the aborted attempt and its retry are consecutive _step_once calls
    # holding the IDENTICAL plan object
    assert any(a is b for a, b in zip(seen, seen[1:])), (
        "retry did not replay the checkpointed plan")
    # planning happened once per logical tick: rolled-back ticks kept
    # their plan, so plan count trails step-entry count by the retries
    assert eng.planner.plans < len(seen) + 10  # sanity: counters coupled


# -- grid membership ---------------------------------------------------------

def test_out_of_grid_plan_clamped(cfg_params):
    """A locked grid whose steady family tops out at H=2 clamps an H=8
    engine's plan to 2: counted under ``grid_clamped``, stamped
    ``plan_clamped`` in the flight ring, and the tick actually runs at
    the clamped horizon."""
    cfg, params = cfg_params
    eng = ServingEngine(cfg, params, EngineConfig(
        decode_horizon=8, planner="mpc", **EC))
    assert eng.perf is not None
    # toy manifest: the steady decode family locked only up to H=2
    eng.perf.grid = [eng._perf_point(2, width=0, spec=False)]
    a = Request(prompt_ids=list(RNG.integers(0, cfg.vocab_size, 40)),
                max_new_tokens=16)
    _drive(eng, [a])
    assert eng.planner.decisions.get("grid_clamped", 0) >= 1
    assert eng.metrics["decode_horizon_effective"] == 2
    ring = eng.flight.view()["ring"]
    plans = [r for r in ring if "plan" in r]
    assert plans, "flight ring carries no plan stamps"
    assert any(r.get("plan_clamped") for r in ring)
    assert all(r["plan"]["h"] <= 2 for r in plans)


def test_empty_grid_keeps_candidates(cfg_params):
    """A grid that covers the steady family not at all must NOT brick
    serving: every candidate is kept (degraded mode — the sentinel still
    flags out-of-grid compiles; the planner never invents a clamp)."""
    cfg, params = cfg_params
    eng = ServingEngine(cfg, params, EngineConfig(
        decode_horizon=4, planner="mpc", **EC))
    eng.perf.grid = [{"form": "nothing-like-the-engine"}]
    cands, clamped = eng.planner._grid_horizons(eng, [1, 2, 4], False)
    assert cands == [1, 2, 4] and clamped is False


# -- draft economics ---------------------------------------------------------

def test_spec_masked_off_then_reprobed(cfg_params):
    """An accept window pricing drafts underwater masks speculation off
    (plain steady ticks — spec_ticks stops advancing), the decision is
    counted, and the periodic re-probe turns the spec program back on
    for one tick; the stream stays bit-identical to a spec_k=0 run."""
    from ipex_llm_tpu.serving import planner as planner_mod

    cfg, params = cfg_params
    prompt = list(RNG.integers(0, cfg.vocab_size, 40))

    eng0 = ServingEngine(cfg, params, EngineConfig(
        decode_horizon=4, spec_k=0, **EC))
    r0 = Request(prompt_ids=list(prompt), max_new_tokens=48)
    (oracle,) = _drive(eng0, [r0])

    eng = ServingEngine(cfg, params, EngineConfig(
        decode_horizon=4, spec_k=2, **EC))
    r = Request(prompt_ids=list(prompt), max_new_tokens=48)
    eng.submit(r)
    for _ in range(200):      # admit + reach steady decode
        eng._tick()
        if len(r.output_ids) >= 4:
            break
    # poison the window: plenty of proposals, zero accepted
    eng._spec_window.clear()
    eng._spec_window.extend([(8, 0)] * 16)
    before = eng.metrics.get("spec_ticks", 0)
    for _ in range(3):
        eng._tick()
    assert eng.planner.decisions.get("spec_off", 0) >= 1
    assert eng.metrics.get("spec_ticks", 0) == before, (
        "masked-off spec still dispatched the spec program")
    assert eng._plan.spec_cap == 0 and not eng._plan.spec_on
    # re-probe: the hysteresis counter reaching the period turns the
    # spec program back on for one tick even with the window unchanged
    eng.planner._spec_off_ticks = planner_mod._SPEC_REPROBE_TICKS - 1
    eng._tick()
    assert eng._plan.reason == "spec_probe"
    assert eng.metrics.get("spec_ticks", 0) > before
    while r.finish_reason is None:
        eng._tick()
    assert list(stream_tokens(r, timeout=10)) == oracle, (
        "spec mask-off/re-probe diverged from the plain greedy stream")


def test_spec_stays_on_while_window_small_or_accepting(cfg_params):
    """Below the minimum-proposal threshold, and with healthy
    acceptance, the caps stay at full width (no premature mask-off)."""
    cfg, params = cfg_params
    eng = ServingEngine(cfg, params, EngineConfig(
        decode_horizon=4, spec_k=2, **EC))
    eng._spec_window.clear()
    eng._spec_window.extend([(4, 0)] * 4)      # 16 proposals < threshold
    k, why = eng.planner._spec_decision(eng)
    assert k == 2 and why is None
    eng._spec_window.clear()
    eng._spec_window.extend([(8, 6)] * 16)     # accepting strongly
    k, why = eng.planner._spec_decision(eng)
    assert k == 2 and why is None


# -- admission deferral ------------------------------------------------------

def test_admit_max_zero_defers_wave(cfg_params):
    """An admit_max=0 plan parks the queued request for the tick; a
    None plan admits it on the next."""
    cfg, params = cfg_params
    eng = ServingEngine(cfg, params, EngineConfig(**EC))
    deferred = TickPlan(horizon=1, chunk_budget=eng._step_budget,
                        spec_ks=(0,) * 4, spec_cap=0, admit_max=0,
                        reason="admit_deferred")
    eng.planner.plan = lambda _e: deferred
    req = Request(prompt_ids=list(RNG.integers(0, cfg.vocab_size, 24)),
                  max_new_tokens=4)
    eng.submit(req)
    for _ in range(3):
        eng._tick()
    assert eng.metrics["requests"] == 0
    assert all(r is None for r in eng.rows)
    open_plan = TickPlan(horizon=1, chunk_budget=eng._step_budget,
                         spec_ks=(0,) * 4, spec_cap=0, admit_max=None,
                         reason="static")
    eng.planner.plan = lambda _e: open_plan
    eng._tick()
    assert eng.metrics["requests"] == 1
    while req.finish_reason is None:
        eng._tick()
    assert req.finish_reason == "length"


# -- deadline-slack horizon cap ----------------------------------------------

def test_deadline_slack_caps_horizon(cfg_params):
    """A latency-bound in-flight row caps the horizon of the tick it
    rides: slack 2.5s at a measured 1s/step keeps only H<=2 candidates;
    a slack-rich row leaves the full horizon."""
    cfg, params = cfg_params
    eng = ServingEngine(cfg, params, EngineConfig(
        decode_horizon=8, planner="mpc", **EC))
    req = Request(prompt_ids=list(RNG.integers(0, cfg.vocab_size, 40)),
                  max_new_tokens=64, deadline_s=1000.0)
    eng.submit(req)
    for _ in range(200):
        eng._tick()
        if len(req.output_ids) >= 1:
            break
    eng.planner._rates["step"] = 1.0           # measured: 1 s per step
    p = eng.planner.plan(eng)
    assert p.horizon == 8 and p.reason == "steady"   # slack-rich
    # slack shrinks to ~2.5 s from NOW, however long the warm-up ticks'
    # compiles took (relative to submission it fell under 2 s whenever
    # they took more than half a second)
    req.submitted_s = time.perf_counter() - 997.5
    p = eng.planner.plan(eng)
    assert p.reason == "deadline_h_cap"
    assert p.horizon == 2
    assert p.predicted_s == pytest.approx(2.0)


# -- observability -----------------------------------------------------------

def test_planner_view_and_health_shape(cfg_params):
    cfg, params = cfg_params
    eng, _reqs, _ = _run(cfg, params, planner="mpc", decode_horizon=4)
    v = eng.planner_view()
    assert v["mode"] == "mpc" and v["plans"] > 0
    assert isinstance(v["decisions"], dict) and v["decisions"]
    assert 0.0 <= v["deadline_miss_rate"] <= 1.0
    last = v["last"]
    for k in ("horizon", "chunk_budget", "spec_cap", "reason", "clamped"):
        assert k in last, k
    # measured EWMA rates fed from committed flight records
    assert "step" in v.get("rates", {})


def test_plan_error_histogram_and_flight_stamp(cfg_params):
    """Once a measured step rate exists, plans carry predicted_s and
    every committed tick scores the prediction into ``perf_plan_error``
    and the flight ring's ``plan_err``/``plan`` stamps."""
    cfg, params = cfg_params
    eng, _reqs, _ = _run(cfg, params, planner="mpc", decode_horizon=4)
    h = eng.histograms().get("perf_plan_error")
    assert h is not None and h.count > 0
    ring = eng.flight.view()["ring"]
    stamped = [r for r in ring if "plan" in r]
    assert stamped
    assert {"h", "cb", "sk", "why"} <= set(stamped[-1]["plan"])
    assert any("plan_err" in r for r in ring)


def test_make_planner_modes():
    assert isinstance(make_planner(EngineConfig(planner="mpc")), MPCPlanner)
    assert isinstance(make_planner(EngineConfig(planner="static")),
                      StaticPlanner)
    with pytest.raises(ValueError, match="planner"):
        make_planner(EngineConfig(planner="bogus"))
