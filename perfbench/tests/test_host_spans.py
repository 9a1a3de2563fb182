"""The readers of the host half of a token's gap (PR 42) on planted data

    JAX_PLATFORMS=cpu python -m pytest perfbench/tests/test_host_spans.py -q

``counter_sum_scaled`` on a planted pair of counter scrapes, and
``idle_overlap`` on a planted trace: a small ``.xplane.pb`` ENCODED here, by
hand, with ``engine/*`` spans on the engine thread's line, ``server/*``
spans on the event loop's, and three executions of the tick program on chip
0 whose gaps are placed so that every number below is known.  The file goes
through xplane_spans.parse_xplane and the reader's own parse, so the wire
format both read is the tested one.  No jax import.
tests/test_perfbench_host_spans.py runs the same cases in tier-1.
"""

from __future__ import annotations

import os
import struct
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path[:0] = [BENCH, os.path.dirname(BENCH), HERE]

import run  # noqa: E402
import xplane_spans as xs  # noqa: E402
from readers import counter_sum_scaled, idle_overlap  # noqa: E402

PHASES = ("plan", "checkpoint", "admit", "prepare", "dispatch", "drain",
          "commit", "record")


def metric(name: str) -> dict:
    return run.load_json(os.path.join(BENCH, "layer_metrics",
                                      name + ".json"))["reader"]


# -- counter_sum_scaled ----------------------------------------------------------

def scrapes(ticks=(100, 300), wall_ms=2.0, cpu_ms=0.5, skip=()):
    """Counters at the window's edges: every phase advances by ``wall_ms``
    and ``cpu_ms`` a tick (sync and its children by more, to be left out)."""
    def at(n):
        m = {"perf_ticks_attributed": n}
        for p in PHASES + ("sync", "sync_wait", "sync_fetch", "tick"):
            far = 10 if p.startswith(("sync", "tick")) else 1
            m[f"perf_phase_{p}_s"] = far * n * wall_ms * 1e-3
            m[f"perf_phase_{p}_cpu_s"] = far * n * cpu_ms * 1e-3
        return {k: v for k, v in m.items() if k not in skip}
    return {"record": {"counters": {"start": {"metrics": at(ticks[0])},
                                    "end": {"metrics": at(ticks[1])}}}}


def test_host_wait_and_cpu_sum_the_phases_whose_cpu_time_is_read():
    """checkpoint, prepare, dispatch, drain: not sync's children, and not
    the phases the engine reads no CPU clock for."""
    ctx = scrapes(skip=tuple(f"perf_phase_{p}_cpu_s" for p in (
        "plan", "admit", "commit", "record", "sync")))
    wait = counter_sum_scaled.read(metric("host_wait_ms_per_tick"), ctx)
    cpu = counter_sum_scaled.read(metric("host_cpu_ms_per_tick"), ctx)
    assert wait == pytest.approx(4 * (2.0 - 0.5))       # ms a tick
    assert cpu == pytest.approx(4 * 0.5)


@pytest.mark.parametrize("ctx", [
    scrapes(skip=("perf_phase_drain_cpu_s",)),      # a program before PR 42
    scrapes(ticks=(100, 100)),                      # no tick in the window
    scrapes(skip=("perf_ticks_attributed",)),
], ids=["no_cpu_counter", "no_tick", "no_denominator"])
def test_counter_sum_scaled_reads_nothing_rather_than_a_guess(ctx):
    assert counter_sum_scaled.read(metric("host_wait_ms_per_tick"),
                                   ctx) is None


def test_a_counter_new_after_the_start_counts_from_zero():
    ctx = scrapes()
    del ctx["record"]["counters"]["start"]["metrics"]["perf_phase_drain_s"]
    got = counter_sum_scaled.read(metric("host_wait_ms_per_tick"), ctx)
    assert got == pytest.approx(4 * 1.5 + 100 * 2.0 / 200)


# -- a planted trace -----------------------------------------------------------------

def _varint(n: int) -> bytes:
    assert n >= 0
    out = bytearray()
    while True:
        n, b = n >> 7, n & 0x7F
        out.append(b | (0x80 if n else 0))
        if not n:
            return bytes(out)


def _f(no: int, value) -> bytes:
    """One protobuf field: an int as a varint, bytes length-delimited."""
    if isinstance(value, int):
        return _varint(no << 3) + _varint(value)
    return _varint(no << 3 | 2) + _varint(len(value)) + value


def _plane(name: str, lines: dict, stat_names=("tick", "variant", "steady",
                                               "n", "bytes")) -> bytes:
    """An XPlane: {line name: [(event name, start_ms, end_ms, {stat:
    value})]}; times from the capture's start."""
    names = sorted({e[0] for evs in lines.values() for e in evs})
    ids = {n: i + 1 for i, n in enumerate(names)}
    stat_ids = {n: i + 1 for i, n in enumerate(stat_names)}
    out = _f(2, name.encode())
    for i, (l_name, evs) in enumerate(lines.items()):
        line = _f(1, i + 1) + _f(2, l_name.encode()) + _f(3, 0)
        for e_name, a, b, stats in evs:
            ev = (_f(1, ids[e_name]) + _f(2, round(a * 1e12))
                  + _f(3, round((b - a) * 1e12)))
            for k, v in stats.items():
                st = _f(1, stat_ids[k])
                st += (_f(7, stat_ids[v]) if isinstance(v, str)
                       else _f(3, v))       # a string stat is a reference
                ev += _f(4, st)
            line += _f(4, ev)
        out += _f(3, line)
    for n, i in ids.items():
        out += _f(4, _f(1, i) + _f(2, _f(1, i) + _f(2, n.encode())))
    for n, i in stat_ids.items():
        out += _f(5, _f(1, i) + _f(2, _f(1, i) + _f(2, n.encode())))
    return out


def _tick(t0: float, n: int) -> list:
    """One tick of 20 ms whose program runs [t0 + 2, t0 + 12): dispatched
    at the end of ``prepare``, waited for and fetched inside ``drain``.  No
    two spans touch (a span's start + duration is a float sum: as on the
    chip, siblings lie 0.1 ms apart)."""
    return [("engine/tick", t0, t0 + 19.9, {"tick": n}),
            ("engine/prepare", t0 + 0.1, t0 + 2, {}),
            ("engine/dispatch", t0 + 1, t0 + 1.9, {"variant": "steady"}),
            ("engine/drain", t0 + 2.1, t0 + 18, {}),
            ("engine/sync", t0 + 3, t0 + 17, {}),
            ("engine/sync_wait", t0 + 3.1, t0 + 13, {}),
            ("engine/sync_fetch", t0 + 13.1, t0 + 16, {"n": 2, "bytes": 136}),
            ("engine/commit", t0 + 18.1, t0 + 19, {}),
            ("engine/record", t0 + 19.1, t0 + 19.8, {})]


# chip 0 runs [2, 12), [22, 32), [42, 52): idle [12, 22) and [32, 42).  Of a
# gap, in ms by innermost span: sync_wait 1 (the first fetch returns 1 ms
# after the program ends), sync_fetch 2.9, sync 1.1, drain 1, commit .9,
# record .7, the ticks themselves .4, between two ticks .1, and of the NEXT tick
# prepare .9 + .1 and dispatch .9
ENGINE = _tick(0, 1) + _tick(20, 2) + _tick(40, 3)
SERVER = [("server/stream_write", 12.5, 13.5, {}),  # sync_wait .5, fetch .4
          ("server/stream_write", 15.0, 16.5, {}),  # fetch 1 (sync's .5: no)
          ("server/stream_write", 20.5, 21.5, {}),  # prepare .5, dispatch .5
          ("server/stream_write", 24.0, 25.0, {}),  # the device is busy
          ("server/stream_write", 34.0, 35.0, {}),  # fetch 1
          ("other/thing", 32.0, 42.0, {})]
PROGRAMS = [("jit__ragged_tick(1)", t, t + 10, {}) for t in (2, 22, 42)]
OPS = [("%fusion.1 = f32[8]{0} fusion(f32[8]{0} %p)", t, t + 10, {})
       for t in (2, 22, 42)]


def _ms(lines: dict) -> dict:
    return {k: [(n, a * 1e-3, b * 1e-3, s) for n, a, b, s in v]
            for k, v in lines.items()}


@pytest.fixture
def planted(tmp_path, monkeypatch):
    """The planted trace as this run's: (path, its parsed form)."""
    def write(server=SERVER, engine=ENGINE):
        space = _f(1, _plane("/host:CPU", _ms({"python3": engine,
                                               "asyncio": server})))
        space += _f(1, _plane("/device:TPU:0", _ms({"XLA Modules": PROGRAMS,
                                                    "XLA Ops": OPS})))
        path = tmp_path / "planted.xplane.pb"
        path.write_bytes(space)
        parsed = xs.parse_xplane(str(path))
        monkeypatch.setattr(xs, "load", lambda ctx: parsed)
        monkeypatch.setattr(idle_overlap, "run_trace_path",
                            lambda: str(path))
        return str(path), parsed
    return write


def test_the_planted_trace_parses_as_a_chip_trace_does(planted):
    path, parsed = planted()
    assert len(parsed["modules"]) == len(parsed["ops"]) == 3
    # engine/* from the engine thread's line only: no server span, and the
    # split of engine/sync does not hide the span the windows end at
    assert len(parsed["spans"]) == 27
    assert {s[0] for s in parsed["spans"]} == {
        "tick", "prepare", "dispatch", "drain", "sync", "sync_wait",
        "sync_fetch", "commit", "record"}
    assert [round(d["end"] * 1e3, 6) for d in xs.dispatches(parsed["spans"])
            ] == [17, 37, 57]
    fetch = next(s for s in parsed["spans"] if s[0] == "sync_fetch")
    assert fetch[3] == {"n": 2, "bytes": 136}
    table = xs.gap_table(parsed)
    assert table["total"] == pytest.approx(0.020)
    assert {k: round(v * 1e3, 6) for k, v in table["by_phase"].items()} == {
        "sync_wait": 2, "sync_fetch": 5.8, "sync": 2.2, "drain": 2,
        "commit": 1.8, "record": 1.4, "prepare": 2, "dispatch": 1.8}
    assert table["tick"] == pytest.approx(0.8e-3)
    assert table["none"] == pytest.approx(0.2e-3)
    # breakdown.idle_gaps lists the children with NO edit to the benchmark,
    # and the accepted groups read what they read
    assert xs.idle_by_span(parsed)["engine/sync_fetch"] == pytest.approx(
        5.8e-3)
    assert xs.group_idle_ms(table, "prepare_dispatch") == pytest.approx(
        3.8 / 3)
    assert xs.group_idle_ms(table, "drain_commit") == pytest.approx(5.2 / 3)
    assert idle_overlap.family_spans(path, "server/") == [
        pytest.approx((a * 1e-3, b * 1e-3)) for _, a, b, _ in SERVER[:5]]


def test_idle_overlap_reads_the_planted_share(planted):
    planted()
    ctx = {"trace": {"busy_s": 0.03}}
    got = idle_overlap.read(metric("idle_stream_overlap_share"), ctx)
    # 2 x (1 + 2.9 + 1 + .9) ms idle in the four phases; .9 + 1 + 1 + 0 +
    # 1 of them under a server span
    assert got == pytest.approx(100 * 3.9 / 11.6)
    note = ctx["notes"]["idle_overlap"]
    assert note["idle_s"] == pytest.approx(0.0116)
    assert note["overlap_s"] == pytest.approx(0.0039)
    assert note["family_spans"] == 5
    # another choice of phases: sync's own 2.2 ms, .6 of them under a span
    own = idle_overlap.overlap(xs.load(ctx), idle_overlap.family_spans(
        idle_overlap.run_trace_path(), "server/"), ["sync"])
    assert own["idle_s"] == pytest.approx(0.0022)
    assert own["overlap_s"] == pytest.approx(0.0006)


@pytest.mark.parametrize("case", ["no_server_span", "no_engine_span",
                                  "no_trace"])
def test_idle_overlap_reads_nothing_from_a_program_without_the_spans(
        planted, monkeypatch, case):
    if case == "no_server_span":        # the parent's program
        planted(server=[("other/thing", 32.0, 42.0, {})])
    elif case == "no_engine_span":
        planted(engine=[("other/thing", 0.0, 1.0, {})])
    else:
        planted()
        monkeypatch.setattr(xs, "load", lambda ctx: None)
    ctx = {"trace": {"busy_s": 0.03}}
    assert idle_overlap.read(metric("idle_stream_overlap_share"),
                             ctx) is None
    assert "notes" not in ctx
