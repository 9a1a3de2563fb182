"""The span readers (PR 25) against a recorded cut of one chip trace

    JAX_PLATFORMS=cpu python -m pytest perfbench/tests/test_spans.py -q

``testdata/spans_small.json`` is the parsed form (xplane_spans.py) of a few
ticks of ``mistral7b.chat-steady`` traced on a TPU v5e (my chip run, PR 25;
the cell was retired in PR 33 for ``mistral7b.chat-near-knee``, the same
configuration and shapes at a higher rate: the recorded trace stays):
the host spans of those ticks, their module executions, and of each tick's
operations the first few dozen, with name paths and HLO text as the trace
gave them.  How it was cut, and the values below, are in ``CUT`` there.
No jax import: everything tested here is a pure function of that form.
"""

from __future__ import annotations

import copy
import json
import os
import sys
import time

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path[:0] = [BENCH, os.path.dirname(BENCH)]

import xplane_spans as xs  # noqa: E402

CONFIG = json.load(open(os.path.join(BENCH, "configs",
                                     "mistral-7b-v0.3.int4.json")))


@pytest.fixture(scope="module")
def small() -> dict:
    with open(os.path.join(BENCH, "testdata", "spans_small.json")) as f:
        return json.load(f)


def reader(kind: str):
    import importlib

    return importlib.import_module(f"readers.{kind}")


def ctx_for(parsed, monkeypatch) -> dict:
    """A reader's ctx whose trace is ``parsed`` (load() is what finds and
    parses a run's file; the readers are tested past it)."""
    monkeypatch.setattr(xs, "load", lambda ctx: parsed)
    return {"trace": {"modules": []}, "config": CONFIG,
            "hf_config": {k: v for k, v in CONFIG.items()
                          if not isinstance(v, (dict, list))}}


# -- finding this run's trace, and never another's ---------------------------------

def _write_trace(tmp, workload, stamp, mtime):
    d = tmp / workload / "trace" / "plugins" / "profile" / stamp
    d.mkdir(parents=True)
    p = d / "host.xplane.pb"
    p.write_bytes(b"x")
    os.utime(p, (mtime, mtime))
    return str(p)


def test_a_trace_older_than_the_process_is_never_read(tmp_path):
    start = time.time()
    old = _write_trace(tmp_path, "cell.a", "2026_01_01_00_00_00", start - 60)
    assert xs.find_run_trace(str(tmp_path), start) is None
    new = _write_trace(tmp_path, "cell.a", "2026_01_01_00_10_00", start + 1)
    assert xs.find_run_trace(str(tmp_path), start) == new
    # another cell's stale trace beside it changes nothing
    _write_trace(tmp_path, "cell.b", "2026_01_01_00_05_00", start - 5)
    assert xs.find_run_trace(str(tmp_path), start) == new
    # a cell whose NEWEST file is stale: trace_reduce.find_xplane picks that
    # one, as run.py does, and it is refused
    os.utime(new, (start - 1, start - 1))
    assert xs.find_run_trace(str(tmp_path), start) is None
    assert old != new
    # nothing to tell runs apart by: nothing is read
    assert xs.find_run_trace(str(tmp_path), None) is None
    assert xs.process_start_s() <= time.time()


def test_load_gives_none_without_a_reduced_trace_or_a_fresh_file():
    assert xs.load({"trace": None}) is None
    # this process traced nothing under the checkout's scratch directory
    assert xs.load({"trace": {"modules": []}}) is None


# -- the raw file ---------------------------------------------------------------------

def test_parse_reads_spans_with_their_stats_from_a_real_capture(tmp_path):
    """A capture made here (CPU, the benchmark's profiler options) of
    annotations as the engine writes them: the wire-format reader gives the
    spans nested and their stats typed, a string stat (a reference in the
    file) as its text.  No device plane: no modules, no operations."""
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    with jax.profiler.TraceAnnotation("engine/tick", tick=2 ** 40 + 7):
        with jax.profiler.TraceAnnotation("engine/prepare"):
            with jax.profiler.TraceAnnotation(
                    "engine/dispatch", variant="mixed", family="tick.admission",
                    p_b=4, width=32, maxp=8, rows_active=0):
                time.sleep(0.002)
        with jax.profiler.TraceAnnotation("not/ours"):
            pass
    jax.profiler.stop_trace()
    import trace_reduce

    parsed = xs.parse_xplane(trace_reduce.find_xplane(str(tmp_path)))
    assert parsed["modules"] == [] and parsed["ops"] == []
    assert [s[0] for s in parsed["spans"]] == ["tick", "prepare", "dispatch"]
    tick, prepare, dispatch = parsed["spans"]
    assert tick[3] == {"tick": 2 ** 40 + 7}
    assert dispatch[3] == {"variant": "mixed", "family": "tick.admission",
                           "p_b": 4, "width": 32, "maxp": 8, "rows_active": 0}
    assert tick[1] <= prepare[1] <= dispatch[1] < dispatch[2] <= prepare[2] \
        <= tick[2]
    assert 0.002 <= dispatch[2] - dispatch[1] < 0.2
    (d,) = xs.dispatches(parsed["spans"])
    assert d["variant"] == "mixed" and d["end"] == float("inf")


# -- the variant join ------------------------------------------------------------------

def test_every_execution_gets_the_variant_of_the_span_that_holds_it(small):
    """Five executions of the tick program in the cut: the first began
    before the capture did (no span holds it), then ticks 1023, 1024
    (steady) and 1025, 1026 (mixed).  Execution 1023 starts 0.68 ms after
    its engine/dispatch opened, 1024 0.96 ms after: the join is by overlap,
    not by a start inside the span."""
    got = xs.module_variants(small, "ragged_tick")
    assert [v for *_, v in got] == [None, "steady", "steady", "mixed",
                                    "mixed"]
    assert [round(d, 9) for _, _, d, _ in got] == [
        0.04601304, 0.10608925, 0.106073932, 0.164713645, 0.165167323]
    # an execution that seems to start before its dispatch opened (the two
    # clocks agree to a few tenths of a ms) still belongs to it
    skewed = copy.deepcopy(small)
    for m in skewed["modules"]:
        m[1] -= 0.0012
    assert [v for *_, v in xs.module_variants(skewed, "ragged_tick")] == [
        None, "steady", "steady", "mixed", "mixed"]
    # median of two = their mean
    assert xs.variant_median_ms(small, "ragged_tick", "steady") == \
        pytest.approx((106.08925 + 106.073932) / 2, abs=1e-9)
    assert xs.variant_median_ms(small, "ragged_tick", "mixed") == \
        pytest.approx((164.713645 + 165.167323) / 2, abs=1e-9)
    assert xs.variant_median_ms(small, "ragged_tick", "chunk") is None
    # the windows: a dispatch's ends with its tick's sync
    disp = xs.dispatches(small["spans"])
    assert [d["variant"] for d in disp] == ["steady", "steady", "mixed",
                                            "mixed"]
    assert disp[0]["a"] == 0.054316452 and disp[0]["end"] == 0.16352478
    assert disp[0]["attrs"]["rows_active"] == 7


def test_a_chunk_tick_without_a_sync_runs_to_the_next_dispatch():
    spans = [["tick", 0.0, 1.0, {"tick": 1}],
             ["dispatch", 0.1, 0.2, {"variant": "chunk"}],
             ["tick", 1.0, 2.0, {"tick": 2}],
             ["dispatch", 1.1, 1.2, {"variant": "steady"}],
             ["sync", 1.2, 1.9, {}]]
    a, b = xs.dispatches(spans)
    assert (a["variant"], a["end"]) == ("chunk", 1.1)
    assert (b["variant"], b["end"]) == ("steady", 1.9)
    parsed = {"spans": spans, "ops": [],
              "modules": [["jit__ragged_tick(1)", 0.15, 0.9],
                          ["jit__ragged_tick(2)", 1.15, 0.7],
                          ["jit_gather(3)", 1.05, 0.01]]}
    assert [v for *_, v in xs.module_variants(parsed, "ragged_tick")] == [
        "chunk", "steady"]


def test_tick_median_reader(small, monkeypatch):
    ctx = ctx_for(small, monkeypatch)
    read = reader("span_tick_median").read
    params = {"module": "ragged_tick", "variant": "mixed"}
    assert read(params, ctx) == pytest.approx(164.940484, abs=1e-6)
    assert ctx["notes"]["tick_executions_by_variant"] == {
        "cut_by_the_capture_edge": 1, "steady": 2, "mixed": 2}
    assert read({**params, "variant": "spec"}, ctx) is None


# -- operations by class -----------------------------------------------------------------

# self seconds by class, summed by hand (a throwaway script that knows only
# trace_reduce's rule: an operation's time counts against the latest earlier
# operation still running when it starts) over the cut's 282 operations
BY_CLASS = {"unscoped": 0.318612407, "kv_pool_move": 0.023070624,
            "qmatmul": 0.000149725, "tick/prefill": 0.000061157,
            "ragged_paged_attention": 0.000048218,
            "tick/decode": 0.000038436, "layer/qkv": 0.000028425,
            "sample": 0.000002567, "layer/attn": 0.000002016}


def test_classes_partition_the_busy_time(small):
    pool = xs.pool_pattern(CONFIG, CONFIG)
    assert pool.pattern == r"\[(?:\d+,)?256,8,128,128\]"
    got = xs.classify(small, pool)
    assert got["seconds"] == pytest.approx(BY_CLASS, abs=1e-9)
    assert got["total_s"] == pytest.approx(0.342013575, abs=1e-9)
    assert sum(got["seconds"].values()) == pytest.approx(got["total_s"])
    # the cut keeps each program's while loops and few of their operations:
    # the loops' self time, which carries no scope, is most of the busy time
    assert got["unscoped_ops"]["while"] == pytest.approx(0.318597126,
                                                         abs=1e-9)
    # the same self times as trace_reduce computes, so the shares of the
    # kernels here and there agree
    import trace_reduce

    events = {0: {trace_reduce.OPS_LINE: [tuple(o[:3]) for o in small["ops"]],
                  trace_reduce.MODULE_LINE: [tuple(m)
                                             for m in small["modules"]]}}
    red = trace_reduce.reduce_events(events)
    assert red["ops"]["qmatmul"][1] == pytest.approx(BY_CLASS["qmatmul"])
    assert red["ops"]["ragged_paged_attention"][1] == pytest.approx(
        BY_CLASS["ragged_paged_attention"])
    assert sum(s for _, s in red["ops"].values()) == pytest.approx(
        got["total_s"])


def test_what_counts_as_moving_the_pool():
    pool = xs.pool_pattern(CONFIG, CONFIG)
    layer = "bf16[256,8,128,128]{3,1,2,0:T(8,128)(2,1)}"

    def cls(name, path=""):
        parsed = {"ops": [[name, 0.0, 1.0, path, ""],
                          ["%x = f32[2] add()", 2.0, 1.0, "jit(f)/head/add:",
                           ""]]}
        return max(xs.classify(parsed, pool)["seconds"].items(),
                   key=lambda kv: (kv[1], kv[0] != "head"))[0]

    # a compiler-made copy of one layer of the pool: no name path at all
    assert cls(f"%copy.7 = {layer} copy({layer} %p)") == "kv_pool_move"
    # slicing a layer out of the stacked pool, under the tick's scope only
    assert cls(f"%constant_dynamic-slice_fusion.9 = bf16[1,256,8,128,128] "
               f"fusion(bf16[32,256,8,128,128] %g)",
               "jit(f)/tick/decode/while/body/squeeze:") == "kv_pool_move"
    # the page scatter, by its scope, whatever its shapes
    assert cls("%fusion.1 = bf16[16,8,128] fusion()",
               "jit(f)/tick/decode/while/body/closed_call/layer/kv_write/"
               "scatter:") == "kv_pool_move"
    # the attention kernel reads the pool: a kernel, not a move
    assert cls(f"%ragged_paged_attention.2 = bf16[16,32,128] custom-call("
               f"{layer} %k)", "jit(f)/x/layer/attn/pallas_call:") \
        == "ragged_paged_attention"
    # a loop's own time: its text lists the pool it carries
    assert cls(f"%while.3 = (s32[], {layer}) while(%t)") == "unscoped"
    # slicing a layer's WEIGHTS out of their stack is not the pool
    assert cls("%dynamic-slice_bitcast_fusion.9 = u8[2048,4096] fusion("
               "u8[32,2048,4096] %w)",
               "jit(f)/tick/decode/while/body/squeeze:") == "tick/decode"
    # pool sizes the configuration does not state are the engine's default
    eng = {"max_rows": 16, "max_seq_len": 4096, "page_size": 128}
    assert xs.pool_pattern({"engine": eng}, CONFIG).pattern == \
        xs.pool_pattern(CONFIG, CONFIG).pattern


def test_op_share_reader(small, monkeypatch):
    ctx = ctx_for(small, monkeypatch)
    read = reader("span_op_share").read
    assert read({"class": "kv_pool_move"}, ctx) == pytest.approx(
        100 * 0.023070624 / 0.342013575, abs=1e-6)
    assert read({"class": "unscoped"}, ctx) == pytest.approx(
        100 * 0.318612407 / 0.342013575, abs=1e-6)
    assert read({"class": "layer/mlp"}, ctx) == 0.0     # none in the cut
    shares = ctx["notes"]["busy_share_by_class"]["shares"]
    assert sum(shares.values()) == pytest.approx(100.0)
    assert list(shares)[:2] == ["unscoped", "kv_pool_move"]


# -- device idle time by host span ---------------------------------------------------------

# seconds of idle time between programs inside each span, by hand: each of the
# cut's 28 gaps between two programs split at the span boundaries inside it
# and every piece booked to the shortest span that holds its middle
BY_PHASE = {"prepare": 0.029303956, "sync": 0.007015482,
            "dispatch": 0.003059634, "record": 0.00118327,
            "plan": 0.00108922, "checkpoint": 0.00066863,
            "commit": 0.00063742, "drain": 0.00028865, "admit": 0.00020878}


def test_gaps_are_booked_to_the_innermost_span(small):
    gaps = xs.program_gaps(small)
    assert len(gaps) == 28
    t = xs.gap_table(small)
    assert t["by_phase"] == pytest.approx(BY_PHASE, abs=1e-9)
    assert t["tick"] == pytest.approx(0.00087061, abs=1e-9)
    # before the first span of the cut (the capture began inside tick 1022,
    # whose own spans it therefore lacks) and between two ticks
    assert t["none"] == pytest.approx(0.002529137, abs=1e-9)
    assert t["total"] == pytest.approx(0.046854789, abs=1e-9)
    assert sum(t["by_phase"].values()) + t["tick"] + t["none"] == \
        pytest.approx(t["total"], abs=1e-12)
    assert t["working_ticks"] == 4
    assert xs.group_idle_ms(t, "plan_admit") == pytest.approx(0.4916575)
    assert xs.group_idle_ms(t, "prepare_dispatch") == pytest.approx(8.0908975)
    assert xs.group_idle_ms(t, "drain_commit") == pytest.approx(0.527335)
    # the longest single gap: 7.5 ms from the end of mixed tick 1025's
    # program to the first operation tick 1026's prepare puts on the device
    lo, hi = max(gaps, key=lambda g: g[1] - g[0])
    assert (lo, hi) == (0.457347755, 0.464858815)


def test_the_breakdown_names_idle_time_by_host_span(small):
    """The result line's ``idle_gaps``: time between programs by the
    innermost span; ``host_between_programs`` only for what no span holds,
    which is all of it in a trace without the program's spans."""
    import trace_reduce

    by_span = xs.idle_by_span(small)
    assert by_span["host_between_programs"] == pytest.approx(0.002529137,
                                                             abs=1e-9)
    assert by_span["engine/sync"] == pytest.approx(BY_PHASE["sync"], abs=1e-9)
    between = sum(by_span.values())
    assert between == pytest.approx(0.046854789, abs=1e-9)
    reduced = {"ops": {}, "gap_total_s": between + 0.01,
               "gap_between_s": between, "idle_by_span": by_span}
    gaps = dict(trace_reduce.breakdown(reduced)["idle_gaps"])
    assert len(gaps) <= 10 and gaps["inside_a_program"] == pytest.approx(0.01)
    longest = max(by_span, key=by_span.get)
    assert longest.startswith("engine/") and longest in gaps
    assert all(v > 0 for v in gaps.values())
    assert xs.idle_by_span(dict(small, spans=[])) is None
    reduced["idle_by_span"] = None
    assert dict(trace_reduce.breakdown(reduced)["idle_gaps"]) == {
        "host_between_programs": pytest.approx(between),
        "inside_a_program": pytest.approx(0.01)}


def test_idle_reader_writes_the_whole_table_once(small, monkeypatch, capsys):
    ctx = ctx_for(small, monkeypatch)
    read = reader("span_idle_ms").read
    assert read({"group": "prepare_dispatch"}, ctx) == pytest.approx(8.0908975)
    assert read({"group": "drain_commit"}, ctx) == pytest.approx(0.527335)
    note = ctx["notes"]["idle_between_programs_by_span"]
    assert note["by_phase"]["sync"] == pytest.approx(0.007015482)
    err = capsys.readouterr().err
    assert err.count("device idle between programs, by host span") == 1


# -- a trace without the program's names ----------------------------------------------------

def test_a_trace_without_spans_or_scopes_gives_none(small, monkeypatch):
    """What the parent commit's trace holds: device events, no engine/*
    span, no scope in any name path."""
    old = {"spans": [], "modules": small["modules"],
           "ops": [[n, s, d, "", c] for n, s, d, _, c in small["ops"]]}
    assert xs.module_variants(old, "ragged_tick")[1][3] is None
    assert xs.gap_table(old) is None
    assert xs.classify(old, xs.pool_pattern(CONFIG, CONFIG)) is None
    assert xs.classify({"ops": []}, xs.pool_pattern(CONFIG, CONFIG)) is None
    ctx = ctx_for(old, monkeypatch)
    assert reader("span_tick_median").read(
        {"module": "ragged_tick", "variant": "steady"}, ctx) is None
    assert reader("span_op_share").read({"class": "unscoped"}, ctx) is None
    assert reader("span_idle_ms").read({"group": "plan_admit"}, ctx) is None
    assert "notes" not in ctx
    # and no trace of this run at all
    ctx = ctx_for(None, monkeypatch)
    for kind, params in (("span_tick_median", {"module": "x",
                                               "variant": "steady"}),
                         ("span_op_share", {"class": "unscoped"}),
                         ("span_idle_ms", {"group": "plan_admit"})):
        assert reader(kind).read(params, ctx) is None


# -- counters --------------------------------------------------------------------------------

def test_counter_scaled_reader():
    read = reader("counter_scaled").read

    def ctx(start, end):
        return {"record": {"counters": {"start": {"metrics": start},
                                        "end": {"metrics": end}}}}

    wait = {"num": "queue_wait_s_total", "den": "first_tokens", "scale": 1000}
    a = {"queue_wait_s_total": 1.5, "first_tokens": 10, "mixed_steps": 40,
         "perf_ticks_attributed": 100}
    b = {"queue_wait_s_total": 9.5, "first_tokens": 42, "mixed_steps": 220,
         "perf_ticks_attributed": 500}
    assert read(wait, ctx(a, b)) == pytest.approx(250.0)    # 8 s / 32
    share = {"num": "mixed_steps", "den": "perf_ticks_attributed",
             "scale": 100}
    assert read(share, ctx(a, b)) == pytest.approx(45.0)    # 180 / 400
    assert read(wait, ctx(a, a)) is None                    # nothing advanced
    # a program from before the counters: not there, so nothing to report
    assert read(wait, ctx({"tokens": 1}, {"tokens": 9})) is None


def test_every_new_metric_resolves_to_its_reader_and_cells():
    import run

    root = os.path.dirname(BENCH)
    cells = {w: {m["name"]: m for m in run.resolve_cell(root, w)["per_layer"]}
             for w in ("mistral7b.decode-saturated",
                       "mistral7b.chat-near-knee")}
    both = {"steady_tick_device_ms", "mixed_tick_share", "kv_pool_move_share",
            "unscoped_device_share"}
    ds_only = {"idle_plan_admit_ms", "idle_prepare_dispatch_ms",
               "idle_drain_commit_ms"}
    cs_only = {"mixed_tick_device_ms", "queue_wait_mean_ms",
               "admit_to_first_token_mean_ms"}
    ds, cs = (cells["mistral7b.decode-saturated"],
              cells["mistral7b.chat-near-knee"])
    assert both | ds_only <= set(ds) and not cs_only & set(ds)
    assert both | cs_only <= set(cs) and not ds_only & set(cs)
    for name in both | ds_only | cs_only:
        m = (ds if name in ds else cs)[name]
        assert os.path.exists(os.path.join(
            BENCH, "readers", m["reader"]["kind"] + ".py")), name
        assert m["layer"] in ("tick program", "decoder body",
                              "scheduler / tick transaction")
