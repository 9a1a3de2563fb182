"""What the benchmark's readers make of a run with a program in flight
behind the running one (PR 48), with no edit to any of them.

``ahead_tick_share`` (a data file over ``counter_scaled``) on planted
counter scrapes, and the three new forms of ``engine/dispatch`` (a dispatch
behind the running program, a queued program's window marked after its
predecessor's sync, a tick that only reads) through
``xplane_spans.dispatches`` and ``module_variants`` on a planted parsed
trace whose times are those of the order the engine keeps: every execution
is filed under its own kind, none as cut by the capture's edge.  No jax."""

import json
import os
import sys

import pytest

BENCH = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))), "perfbench")
sys.path[:0] = [p for p in (BENCH,) if p not in sys.path]

import run  # noqa: E402
import xplane_spans as xs  # noqa: E402
from readers import counter_scaled  # noqa: E402

CELLS = [w["name"] for w in json.load(open(os.path.join(
    os.path.dirname(BENCH), "BENCHMARK.json")))["workloads"]]


def _scrapes(ahead, ticks, have=True):
    def at(a, n):
        m = {"perf_ticks_attributed": n}
        if have:
            m["ahead_ticks_total"] = a
        return {"metrics": m}
    return {"record": {"counters": {"start": at(ahead[0], ticks[0]),
                                    "end": at(ahead[1], ticks[1])}}}


def _reader():
    return run.load_json(os.path.join(BENCH, "layer_metrics",
                                      "ahead_tick_share.json"))["reader"]


@pytest.mark.parametrize("ahead,ticks,have,want", [
    ((40, 1800), (100, 2100), True, 88.0),     # a dense cell
    ((0, 0), (100, 1500), True, 0.0),          # a recurrent cell: reported, 0
    ((0, 0), (100, 1500), False, None),        # the parent: no such counter
    ((5, 5), (100, 100), True, None),          # no tick in the window
])
def test_ahead_tick_share_over_planted_counters(ahead, ticks, have, want):
    got = counter_scaled.read(_reader(), _scrapes(ahead, ticks, have))
    assert got == want


def test_the_metric_is_declared_for_every_cell_as_data():
    bench = json.load(open(os.path.join(os.path.dirname(BENCH),
                                        "BENCHMARK.json")))
    # (found by name: later PRs append their own metrics after it)
    entry = next(m for m in bench["per_layer"]
                 if m["name"] == "ahead_tick_share")
    assert entry == {"name": "ahead_tick_share", "unit": "%",
                     "better": "higher", "source": "program_counter",
                     "layer": "scheduler / tick transaction",
                     "moves": "itl_p95_ms", "workloads": CELLS}
    assert _reader()["kind"] == "counter_scaled"


# -- the spans ------------------------------------------------------------------

TICK_MS, HOST_MS, TAIL_MS = 14.0, 5.0, 0.8


def _planted():
    """A mixed tick, then a run of four steady programs S1..S4 of which
    S2..S4 are queued ahead, then a mixed tick again, as the engine orders
    them (seconds): the device runs back to back inside the run; the host
    learns of an execution's end ``TAIL_MS`` after it."""
    ms = 1e-3
    spans, modules = [], []

    def tick(a, b, kids):
        spans.append(["tick", a, b, {"tick": len(modules)}])
        spans.extend(kids)

    def disp(a, variant=None, **more):
        attrs = dict(family="tick.steady", rows_active=16, **more)
        if variant:
            attrs["variant"] = variant
        return ["dispatch", a, a + (0 if "queued" in more else 1.5 * ms),
                attrs]

    t = 0.0
    # a serial mixed tick: dispatch, execution, sync
    m0 = t + 1 * ms
    modules.append(["jit__ragged_tick(1)", m0 + 0.3 * ms, 30 * ms])
    end = m0 + 30.3 * ms + TAIL_MS * ms
    tick(t, end + 1 * ms, [disp(m0, "mixed"),
                           ["sync", m0 + 2 * ms, end, {}]])
    t = end + 1 * ms
    # the run's first tick: its own dispatch, the next behind it, sync,
    # then the queued program's window opens
    d1 = t + 1 * ms
    e1 = d1 + 0.3 * ms
    modules.append(["jit__ragged_tick(2)", e1, TICK_MS * ms])
    sync_end = e1 + TICK_MS * ms + TAIL_MS * ms
    tick(t, sync_end + 1 * ms, [
        disp(d1, "steady"), disp(d1 + 1.6 * ms, ahead="steady"),
        ["sync", d1 + 3.2 * ms, sync_end, {}],
        disp(sync_end, "steady", queued=1)])
    t = sync_end + 1 * ms
    e = e1 + TICK_MS * ms               # S2 starts where S1 ended
    for k in (2, 3):                    # S2, S3: ahead ticks
        modules.append([f"jit__ragged_tick({k + 1})", e, TICK_MS * ms])
        e += TICK_MS * ms
        sync_end = e + TAIL_MS * ms
        tick(t, sync_end + 1 * ms, [
            disp(t + 1 * ms, ahead="steady"),
            ["sync", t + 2.6 * ms, sync_end, {}],
            disp(sync_end, "steady", queued=1)])
        t = sync_end + 1 * ms
    # S4: read by a tick that dispatches nothing (an arrival waits)
    modules.append(["jit__ragged_tick(5)", e, TICK_MS * ms])
    e += TICK_MS * ms
    sync_end = e + TAIL_MS * ms
    tick(t, sync_end + 1 * ms, [
        ["dispatch", t + 1 * ms, t + 1 * ms, {"queued": 1}],
        ["sync", t + 1.1 * ms, sync_end, {}]])
    t = sync_end + 1 * ms
    # the mixed tick after the run, serial again
    m1 = t + HOST_MS * ms
    modules.append(["jit__ragged_tick(6)", m1 + 0.3 * ms, 30 * ms])
    end = m1 + 30.3 * ms + TAIL_MS * ms
    tick(t, end + 1 * ms, [disp(m1, "mixed"),
                           ["sync", m1 + 2 * ms, end, {}]])
    spans.sort(key=lambda s: (s[1], -s[2]))
    return {"spans": spans, "modules": modules, "ops": []}


def test_every_execution_is_filed_under_its_own_kind():
    parsed = _planted()
    got = [v for _, _, _, v in xs.module_variants(parsed, "ragged_tick")]
    assert got == ["mixed", "steady", "steady", "steady", "steady", "mixed"]
    assert xs.variant_median_ms(parsed, "ragged_tick", "steady") == \
        pytest.approx(TICK_MS)


def test_a_queued_programs_window_is_one_execution_long():
    """The windows ``dispatches()`` gives: the ahead dispatches and the
    tick that only reads give none; a queued program's runs from its
    predecessor's sync to its own, so it holds all of its execution but
    the sync's tail, and no other window holds more than that tail."""
    parsed = _planted()
    disp = xs.dispatches(parsed["spans"])
    assert [d["variant"] for d in disp] == [
        "mixed", "steady", "steady", "steady", "steady", "mixed"]
    assert all("ahead" not in d["attrs"] for d in disp)
    for d, (_, s, dur) in zip(disp, parsed["modules"]):
        held = xs._overlap(d["a"], d["end"], s, s + dur)
        assert held >= dur - (TAIL_MS + 0.01) * 1e-3
        others = [xs._overlap(o["a"], o["end"], s, s + dur)
                  for o in disp if o is not d]
        assert max(others) <= (TAIL_MS + 0.01) * 1e-3


def test_a_tick_that_only_reads_counts_as_a_working_tick():
    table = xs.gap_table(_planted())
    assert table["working_ticks"] == 6
