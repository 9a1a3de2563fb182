"""Device-time observatory (PR 15, serving/perfwatch.py).

The contracts under test:

- **attribution bucket math**: the four buckets (dispatch / device /
  sync / bookkeep) PARTITION the tick wall clock exactly — unit-level
  over synthetic windows, and engine-level over every committed
  flight-ring record (the acceptance bound: sum within 5% of wall);
- **recompile sentinel**: quiet across the manifest-locked grid (an
  on-grid engine's compiles are all cold, zero warm, zero out-of-grid),
  fires on a deliberately out-of-grid shape (counted, flagged in the
  perf view, recorded in the flight ring), and the membership rules
  (pow2-within-max magnitude axes, exact structural axes) are pinned;
- **MFU join**: hand-computed against a synthetic manifest entry —
  scale x executed multiplier, linear rows fallback — and nonzero
  end-to-end on the real tiny model via the real programs.lock.json;
- **rollback residue**: a transient-faulted tick that rolls back
  contributes NOTHING — histogram observation counts equal the
  committed per-family tick counts exactly;
- **JP106 runtime cross-check**: a dispatch the hand-maintained counter
  sees but perfwatch does not (or vice versa) records a
  ``dispatch_mismatch`` flight field and raises the debug assert;
- **host phase spans**: the ``perf_phase_*_s`` counters partition the
  tick's wall clock and agree with the four buckets; a rolled-back tick,
  a bisection probe and an idle tick add nothing to them or to the wait
  counters; a profiler capture holds ``engine/tick`` spans with the
  phases nested inside; the lowered tick names every device scope;
- **surfaces**: /health carries the ``perf`` block (and no
  ``dispatch`` block: the kernel choice is the platform's), /metrics
  carries the ``perf_*`` counters and per-family attribution histograms,
  and the router fleet-SUMS the sentinel counters.
"""

import asyncio
import json
import threading
import time
import urllib.request

import numpy as np
import pytest

from ipex_llm_tpu.serving.engine import (EngineConfig, Request,
                                         ServingEngine, stream_tokens)
from ipex_llm_tpu.serving.faults import (DeterministicFault, FaultInjector,
                                         TransientFault)
from ipex_llm_tpu.serving.perfwatch import (CPU_PHASES, PHASES, PerfWatch,
                                            locked_points,
                                            model_flops_per_token,
                                            outside_axes, parse_point_key,
                                            point_in_grid)
from tests.test_decoder import rand_params, tiny_cfg

RNG = np.random.default_rng(29)


@pytest.fixture(scope="module")
def cfg_params():
    cfg = tiny_cfg(vocab_size=131, hidden_size=48, intermediate_size=96,
                   num_heads=4, num_kv_heads=2, head_dim=12,
                   max_position_embeddings=512)
    return cfg, rand_params(cfg, qtype="bf16")


def _drive(eng, reqs, ticks=4000):
    if isinstance(reqs, Request):
        reqs = [reqs]
    for r in reqs:
        eng.submit(r)
    for _ in range(ticks):
        eng._tick()
        if all(r.finish_reason is not None for r in reqs):
            return [list(stream_tokens(r, timeout=5)) for r in reqs]
    raise AssertionError("requests never finished")


def _prompts(n, length, vocab=131):
    return [list(RNG.integers(1, vocab, length).astype(int))
            for _ in range(n)]


# -- bucket math (unit) ------------------------------------------------------

def test_bucket_classification_partitions_wall():
    w = PerfWatch(hists={})
    w.tick_begin()
    time.sleep(0.004)                      # pre-dispatch bookkeeping
    with w.dispatch("tick.steady"):
        time.sleep(0.010)                  # "the jitted call"
    time.sleep(0.006)                      # overlapped window
    w.note_sync(0.004)                     # blocked the last 4ms of it
    time.sleep(0.003)                      # post-sync drain walk
    out = w.tick_finish(manual_dispatches=1, working=True)
    a = out["attrib"]
    # the partition is exact by construction (4 fields rounded to 1e-6)
    assert abs(sum(a.values()) - out["wall_s"]) < 5e-6
    assert a["dispatch"] >= 0.009
    assert 0.003 <= a["sync"] <= 0.006
    # the gap between dispatch return and sync start is device time
    assert a["device"] >= 0.001
    # pre-dispatch + post-sync host work
    assert a["bookkeep"] >= 0.005
    assert out["perf_family"] == "tick.steady"
    assert "dispatch_mismatch" not in out
    # histograms registered per (family, bucket) and observed once
    for b in ("dispatch", "device", "sync", "bookkeep"):
        assert w.hists[f"perf_tick_steady_{b}_s"].count == 1


def test_idle_tick_discards_scratch():
    w = PerfWatch(hists={})
    w.tick_begin()
    assert w.tick_finish(manual_dispatches=0, working=False) == {}
    assert w.ticks_attributed == 0
    assert w.hists == {}


def test_dispatch_crosscheck_unit():
    w = PerfWatch(hists={})
    w.tick_begin()
    with w.dispatch("tick.steady"):
        pass
    out = w.tick_finish(manual_dispatches=2, working=True)
    assert out["dispatch_mismatch"] == {"observed": 1, "manual": 2}
    assert w.dispatch_mismatches == 1


# -- grid membership (unit) --------------------------------------------------

def test_point_in_grid_rules():
    locked = [parse_point_key(k) for k in (
        "horizon=1,kv=bf16,rows=4,width=0",
        "horizon=8,kv=bf16,rows=8,width=0",
        "horizon=1,kv=bf16,rows=4,width=8",
        "horizon=1,kv=bf16,rows=4,width=128",
        "horizon=1,kv=bf16,rows=4,wd=False,width=8",
        "horizon=1,kv=bf16,rows=4,spec=4,width=0",
    )]
    ok = lambda **pt: point_in_grid(pt, locked)   # noqa: E731
    # exact and pow2-within-max magnitudes
    assert ok(rows=4, width=0, horizon=1, kv="bf16")
    assert ok(rows=8, width=0, horizon=4, kv="bf16")      # pow2 <= max
    assert ok(rows=2, width=16, horizon=1, kv="bf16")     # sampled around
    # the engine-pad axes (pb/maxp/ew) never affect membership
    assert ok(rows=4, width=8, horizon=1, kv="bf16", pb=4, maxp=2, ew=2)
    # magnitude violations
    assert not ok(rows=6, width=0, horizon=1, kv="bf16")  # not pow2
    assert not ok(rows=16, width=0, horizon=1, kv="bf16")  # > max
    assert not ok(rows=4, width=256, horizon=1, kv="bf16")  # > max
    assert not ok(rows=4, width=0, horizon=16, kv="bf16")   # > max
    # structural violations
    assert not ok(rows=4, width=0, horizon=1, kv="fp8")
    assert not ok(rows=4, width=0, horizon=1, kv="bf16", wq="sym_int4")
    assert not ok(rows=4, width=0, horizon=1, kv="bf16", tp=2)
    # wd=False only matches the wd=False family (and it is width>0 only)
    assert ok(rows=4, width=8, horizon=1, kv="bf16", wd=False)
    assert not ok(rows=4, width=0, horizon=1, kv="bf16", wd=False)
    # spec: bounded by the locked max, structural presence required
    assert ok(rows=4, width=0, horizon=1, kv="bf16", spec=2)
    assert not ok(rows=4, width=0, horizon=1, kv="bf16", spec=8)
    # no manifest = membership disabled, never flags
    assert point_in_grid({"rows": 99, "width": 3}, None)


def test_locked_points_loads_real_manifest():
    from ipex_llm_tpu.analysis.trace import manifest as mf

    locked = locked_points(mf.load())
    assert locked and len(locked) >= 30
    # the steady tiny point every serving test dispatches is locked
    assert point_in_grid(
        {"rows": 4, "width": 0, "horizon": 1, "kv": "bf16"}, locked)


# -- MFU join (unit, hand-computed) -----------------------------------------

def _toy_manifest():
    return {"programs": {"serving.ragged_tick": {"entries": {
        "horizon=1,kv=bf16,rows=4,width=0":
            {"flops": 1000, "bytes_accessed": 2000},
        "horizon=1,kv=bf16,rows=4,width=8":
            {"flops": 5000, "bytes_accessed": 7000},
    }}}}


def test_mfu_join_hand_computed_manifest_entry():
    w = PerfWatch(hists={}, manifest=_toy_manifest(),
                  flops_scales={"bf16": 2.0}, peak_flops=1e6,
                  peak_bytes_s=1e6)
    pt = {"rows": 4, "width": 0, "horizon": 1, "kv": "bf16"}
    # exact entry: flops x scale x executed
    assert w.cost_for(pt, executed=3) == (6000.0, 12000.0)
    # the engine-pad axes are stripped before the cost lookup
    assert w.cost_for({**pt, "ew": 2, "pb": 4}, executed=1) \
        == (2000.0, 4000.0)
    # linear-rows fallback: rows=8 has no entry, scales 2x off rows=4
    assert w.cost_for({**pt, "rows": 8}) == (4000.0, 8000.0)
    # linear-width fallback off the width=8 admission entry
    f16, _ = w.cost_for({**pt, "width": 16})
    assert f16 == pytest.approx(5000 * 2.0 * 2)
    # nothing structurally matching: no join
    assert w.cost_for({**pt, "kv": "fp8"}) is None
    # end-to-end through a tick: mfu == flops / device_view / peak
    w.tick_begin()
    with w.dispatch("tick.steady", point=pt):
        time.sleep(0.002)
    w.note_sync(0.001)
    w.note_executed(4)
    out = w.tick_finish(manual_dispatches=1, working=True)
    a = out["attrib"]
    dev = a["dispatch"] + a["device"] + a["sync"]   # no compiles fired
    assert out["mfu"] == pytest.approx(1000 * 2.0 * 4 / dev / 1e6,
                                       rel=0.02)
    assert out["bytes_per_s"] == pytest.approx(2000 * 2.0 * 4 / dev,
                                               rel=0.02)
    assert w.mfu("tick.steady") == pytest.approx(out["mfu"], rel=0.02)


def test_peaks_come_from_the_device_kind_table():
    """One table keyed by device_kind: the v5e row carries the published
    numbers, an unknown TPU kind raises instead of taking a default, and
    off the TPU platform there are no peaks (MFU is not measured)."""
    from types import SimpleNamespace as Dev

    from ipex_llm_tpu.serving.perfwatch import DEVICE_PEAKS, resolve_peaks

    assert resolve_peaks(Dev(platform="tpu", device_kind="TPU v5 lite")) \
        == DEVICE_PEAKS["TPU v5 lite"] == (197e12, 819e9)
    with pytest.raises(ValueError, match="TPU v99"):
        resolve_peaks(Dev(platform="tpu", device_kind="TPU v99"))
    assert resolve_peaks(Dev(platform="cpu", device_kind="cpu")) is None
    assert resolve_peaks() is None            # this suite runs on the CPU
    w = PerfWatch(hists={}, manifest=_toy_manifest())
    assert w.peak_flops is None and w.mfu() is None


def test_model_flops_scale_basis():
    from ipex_llm_tpu.analysis.trace.registry import audit_cfg

    cfg = tiny_cfg(vocab_size=131, hidden_size=48, intermediate_size=96,
                   num_heads=4, num_kv_heads=2, head_dim=12,
                   max_position_embeddings=512)
    mine, audit = (model_flops_per_token(cfg),
                   model_flops_per_token(audit_cfg("bf16")))
    assert mine > audit > 0
    # hand-check the audit model's analytic flops: qkv + o + mlp + head
    h, q, kv = 32, 4 * 8, 2 * 8
    per_layer = h * (q + 2 * kv) + q * h + 3 * h * 64
    assert audit == 2.0 * (2 * per_layer + h * 97)


# -- engine-level attribution + sentinel ------------------------------------

def test_engine_attribution_sums_to_tick_wall(cfg_params):
    """The acceptance bound: every committed working tick's buckets sum
    to within 5% of its measured wall clock, the steady family reports
    nonzero manifest-joined achieved rates, and the grid point rides the
    record."""
    cfg, params = cfg_params
    eng = ServingEngine(cfg, params, EngineConfig(
        max_rows=4, max_seq_len=256, page_size=16, prefill_bucket=16,
        decode_horizon=4))
    outs = _drive(eng, [Request(prompt_ids=p, max_new_tokens=10)
                        for p in _prompts(3, 24)])
    assert all(len(o) == 10 for o in outs)
    ring = eng.flight.view()["ring"]
    assert ring
    for rec in ring:
        a = rec["attrib"]
        assert set(a) == {"dispatch", "device", "sync", "bookkeep"}
        assert sum(a.values()) == pytest.approx(rec["wall_s"], rel=0.05,
                                                abs=1e-6)
        assert rec["perf_family"].startswith("tick.")
    steady = [r for r in ring if r["perf_family"] == "tick.steady"]
    assert steady
    # this suite runs on the CPU: achieved rates are joined, but MFU is
    # not measured off the TPU platform (no peaks row applies)
    assert all("mfu" not in r for r in steady)
    assert any(r.get("bytes_per_s", 0) > 0 for r in steady)
    assert all("rows=4" in r["grid_point"] for r in steady)
    pv = eng.perf_view()
    assert "mfu" not in pv["families"]["tick.steady"]
    assert pv["roofline"]["peak_flops"] is None
    assert pv["families"]["tick.steady"]["flops_per_s"] > 0
    assert pv["families"]["tick.steady"]["bytes_per_s"] > 0
    assert pv["ticks_attributed"] == len(ring)
    assert pv["dispatch_mismatches"] == 0
    # the committed /metrics view carries the per-family histograms
    hists = eng.histograms()
    assert hists["perf_tick_steady_dispatch_s"].count == len(steady)
    # numeric counters for the exposition
    nm = eng.perf_numeric()
    assert nm["perf_ticks_attributed"] == len(ring)
    assert "perf_mfu" not in nm


def test_sentinel_quiet_on_locked_grid(cfg_params):
    """An engine whose config lands on the locked grid compiles cold
    only: zero warm, zero out-of-grid across admission AND steady."""
    cfg, params = cfg_params
    eng = ServingEngine(cfg, params, EngineConfig(
        max_rows=4, max_seq_len=256, page_size=64, prefill_bucket=64,
        decode_horizon=2))
    _drive(eng, [Request(prompt_ids=p, max_new_tokens=8)
                 for p in _prompts(3, 20)])
    s = eng.perf.sentinel_view()
    assert s["compiles_total"] >= 1           # this shape is fresh here
    assert s["compiles_warm"] == 0
    assert s["compiles_out_of_grid"] == 0
    assert s["grid_locked"] and s["grid_locked"] >= 30
    assert s["compile_s_total"] > 0
    # per-family compile attribution recorded where the compile fired
    assert any(v["compiles"] for v in s["per_family"].values())


def test_sentinel_fires_on_out_of_grid_shape(cfg_params):
    """The acceptance gate's other half: a deliberately out-of-grid
    shape (rows=6 — not a power of two, so no locked point admits it) is
    counted, flagged in the perf view, and recorded in the flight ring."""
    cfg, params = cfg_params
    eng = ServingEngine(cfg, params, EngineConfig(
        max_rows=6, max_seq_len=256, page_size=16, prefill_bucket=16))
    _drive(eng, Request(prompt_ids=_prompts(1, 20)[0], max_new_tokens=6))
    s = eng.perf.sentinel_view()
    assert s["compiles_out_of_grid"] >= 1
    assert any("rows=6" in p for p in s["out_of_grid_points"])
    assert s["compiles_warm"] == 0            # novel, not a re-compile
    recs = [r for r in eng.flight.view()["ring"]
            if r.get("compiles_out_of_grid")]
    assert recs and recs[0]["compiles"] >= 1
    # the postmortem dump carries the sentinel evidence too
    d = eng.flight.dump("test")
    d.update(eng.perf.dump_fields())
    assert d["perf_compiles_out_of_grid"] >= 1


def test_rollback_leaves_no_attribution_residue(cfg_params):
    """A transient fault at the 'sample' site fires AFTER the fused
    dispatch window opened — the tick rolls back and retries.  No bucket
    observation, family tick count, or attributed-tick count may carry
    the doomed tick: histogram counts == committed family ticks, and the
    flight ring holds exactly the attributed records."""
    cfg, params = cfg_params
    inj = FaultInjector().inject("sample", TransientFault)
    eng = ServingEngine(cfg, params, EngineConfig(
        max_rows=4, max_seq_len=256, page_size=32, prefill_bucket=8,
        decode_horizon=2, retry_backoff_s=0.001), fault_injector=inj)
    outs = _drive(eng, [Request(prompt_ids=p, max_new_tokens=6)
                        for p in _prompts(2, 12)])
    assert all(len(o) == 6 for o in outs)
    assert eng.metrics["retries"] >= 1        # the fault really fired
    pv = eng.perf_view()
    ring = eng.flight.view()["ring"]
    assert pv["ticks_attributed"] == len(ring)
    for fam, row in pv["families"].items():
        for b in ("dispatch", "device", "sync", "bookkeep"):
            h = eng.hists[f"perf_{fam.replace('.', '_')}_{b}_s"]
            assert h.count == row["ticks"], (fam, b)
    # the committed scrape view agrees with the live (post-drive) state
    for k, h in eng.histograms().items():
        if k.startswith("perf_"):
            assert h.count == eng.hists[k].count


def test_dispatch_crosscheck_fails_loudly_in_engine(cfg_params):
    """Break the pairing deliberately (dispatch windows suppressed while
    the hand-maintained counter still bumps): the committed tick records
    a dispatch_mismatch field in the flight ring AND raises the debug
    assert — the runtime enforcement of JP106's `+= 1` bookkeeping."""
    cfg, params = cfg_params
    eng = ServingEngine(cfg, params, EngineConfig(
        max_rows=4, max_seq_len=256, page_size=32, prefill_bucket=32))
    from contextlib import nullcontext
    eng._perf_dispatch = lambda *a, **k: nullcontext()
    eng.submit(Request(prompt_ids=_prompts(1, 8)[0], max_new_tokens=4))
    with pytest.raises(AssertionError, match="JP106"):
        for _ in range(50):
            eng._tick()
    recs = [r for r in eng.flight.view()["ring"]
            if r.get("dispatch_mismatch")]
    assert recs
    mm = recs[-1]["dispatch_mismatch"]
    assert mm["observed"] == 0 and mm["manual"] >= 1
    assert eng.perf.dispatch_mismatches >= 1


@pytest.mark.parametrize("fault", [False, True])
def test_programs_dispatched_are_read_or_discarded_one_queued_at_most(
        cfg_params, fault):
    """JP106's runtime twin, restated for a program in flight behind the
    running one.  The static rule pins the tick's jitted callees; at run
    time a tick may dispatch its own program AND the next one, or none
    (it only reads one queued a tick ago), so the count holds over a run,
    not a tick: programs dispatched == ticks that committed a program +
    programs discarded unread (+ the one still queued), never more than
    ONE is queued behind the one being awaited, and perfwatch's windows
    still equal the engine's hand-kept count tick by tick.  A fault with a
    program queued (``fault``) discards it; the rolled-back tick counts
    nothing."""
    cfg, params = cfg_params
    inj = FaultInjector()
    if fault:
        inj.inject("decode-dispatch", TransientFault, nth=9)
    eng = ServingEngine(cfg, params, EngineConfig(
        max_rows=4, max_seq_len=256, page_size=16, prefill_bucket=16,
        retry_backoff_s=0.0), fault_injector=inj)
    inflight = {"n": 0, "max": 0}   # steady programs launched, not read
    launch, read, rollback = eng._launch, eng._horizon_step, eng._rollback

    def launched(*a, **kw):
        inflight["n"] += 1
        inflight["max"] = max(inflight["max"], inflight["n"])
        return launch(*a, **kw)

    def horizon_step(active):
        read(active)                # returns having read exactly one
        inflight["n"] -= 1

    def rolled_back(snap):
        inflight["n"] = 0           # everything in flight is thrown away
        return rollback(snap)

    eng._launch, eng._horizon_step = launched, horizon_step
    eng._rollback = rolled_back
    first = [Request(prompt_ids=p, max_new_tokens=n)
             for p, n in zip(_prompts(2, 20), (30, 12))]
    _drive(eng, first)
    late = Request(prompt_ids=_prompts(1, 33)[0], max_new_tokens=9)
    _drive(eng, late)
    m, ring = eng.metrics, eng.flight.view()["ring"]
    assert inj.fired == int(fault) and m["retries"] == int(fault)
    assert m["ahead_discarded_total"] == int(fault)
    assert m["ahead_ticks_total"] > 20
    assert inflight["max"] == 2     # the one awaited and ONE behind it
    assert all(r["dispatches"] <= 2 for r in ring)
    committed = sum(1 for r in ring if r["perf_family"] != "tick.host")
    assert sum(r["dispatches"] for r in ring) == (
        committed + m["ahead_discarded_total"]
        + (eng._ahead is not None))
    # every committed working tick is counted once, whatever it dispatched
    assert eng.perf.ticks_attributed == len(ring)
    assert eng.perf.dispatch_mismatches == 0
    assert m["d2h_total"] == m["host_syncs"]
    assert all(r["perf_family"] == "tick.steady" for r in ring
               if r["dispatches"] == 0 and r["tokens"])
    # the per-tick cost join sees ONE program a tick: the one it read
    assert all("grid_point" in r for r in ring
               if r["perf_family"] != "tick.host")


def test_perfwatch_disabled_engine(cfg_params):
    cfg, params = cfg_params
    eng = ServingEngine(cfg, params, EngineConfig(
        max_rows=4, max_seq_len=256, page_size=32, prefill_bucket=32,
        perfwatch=False))
    _drive(eng, Request(prompt_ids=_prompts(1, 8)[0], max_new_tokens=4))
    assert eng.perf is None
    assert eng.perf_view() is None
    assert eng.perf_numeric() == {}
    assert all("attrib" not in r for r in eng.flight.view()["ring"])
    assert not any(k.startswith("perf_") for k in eng.histograms())


def test_handoff_epoch_family_attributed(cfg_params):
    """Epoch-boundary work gets its own family: a prefix export (the
    disagg handoff's first leg) lands under 'handoff' with the same
    bucket partition, without inflating any tick family."""
    cfg, params = cfg_params
    eng = ServingEngine(cfg, params, EngineConfig(
        max_rows=4, max_seq_len=256, page_size=16, prefill_bucket=32))
    prompt = _prompts(1, 40)[0]
    _drive(eng, Request(prompt_ids=prompt, max_new_tokens=4))
    ticks0 = {f: r["ticks"]
              for f, r in eng.perf_view()["families"].items()}
    blob = eng.export_prefix(prompt)
    assert blob
    pv = eng.perf_view()
    assert pv["families"]["handoff"]["ticks"] == 1
    assert pv["families"]["handoff"]["wall_s"] > 0
    assert eng.hists["perf_handoff_sync_s"].count == 1
    for f, n in ticks0.items():               # tick families untouched
        assert pv["families"][f]["ticks"] == n


# -- HTTP surfaces -----------------------------------------------------------

def _serve(srv):
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
    assert started.wait(10)
    return holder["port"], loop


def _get(port, path):
    return urllib.request.urlopen(
        f"http://127.0.0.1:{port}{path}", timeout=30).read().decode()


class _Tok:
    eos_token_id = None
    chat_template = None

    def __call__(self, text):
        return {"input_ids": [int(x) % 131 if x.isdigit() else 1
                              for x in text.split()]}

    def decode(self, ids):
        return " ".join(str(i) for i in ids)


def test_health_metrics_perf_surface_e2e(cfg_params):
    """/health carries the perf block (families + sentinel + roofline)
    and no ``dispatch`` block; /metrics carries the perf_* counters and
    the per-family attribution histogram series."""
    pytest.importorskip("aiohttp")
    from ipex_llm_tpu.serving.api_server import OpenAIServer

    cfg, params = cfg_params
    eng = ServingEngine(cfg, params, EngineConfig(
        max_rows=4, max_seq_len=256, page_size=32,
        prefill_bucket=32)).start()
    srv = OpenAIServer(eng, _Tok(), "tiny")
    port, _ = _serve(srv)
    try:
        body = json.dumps({"prompt": "1 2 3 4 5 6 7 8",
                           "max_tokens": 4, "temperature": 0.0}).encode()
        urllib.request.urlopen(urllib.request.Request(
            f"http://127.0.0.1:{port}/v1/completions", data=body,
            headers={"Content-Type": "application/json"}), timeout=60)

        h = json.loads(_get(port, "/health"))
        perf = h["perf"]
        assert perf["sentinel"]["compiles_warm"] == 0
        assert perf["sentinel"]["compiles_out_of_grid"] == 0
        assert perf["sentinel"]["grid_locked"] >= 30
        assert perf["ticks_attributed"] >= 1
        assert any(f.startswith("tick.") for f in perf["families"])
        assert perf["roofline"]["peak_flops"] is None    # CPU: no peaks
        assert "dispatch" not in h

        text = _get(port, "/metrics")
        assert "ipex_llm_tpu_perf_compiles_total" in text
        assert "ipex_llm_tpu_perf_compiles_warm" in text
        assert "ipex_llm_tpu_perf_ticks_attributed" in text
        assert "_bucket" in text
        js = json.loads(_get(port, "/metrics?format=json"))
        assert js["metrics"]["perf_compiles_warm"] == 0
        perf_hists = [k for k in js["histograms"]
                      if k.startswith("perf_tick")]
        assert perf_hists
        for k in perf_hists:
            assert js["histograms"][k]["count"] >= 1
    finally:
        eng.stop()


def test_router_fleet_sums_perf_counters(cfg_params):
    """The router's /metrics aggregation fleet-SUMS the sentinel
    counters across replicas (they are true counters) and re-labels the
    per-replica series."""
    pytest.importorskip("aiohttp")
    from ipex_llm_tpu.serving.api_server import OpenAIServer
    from ipex_llm_tpu.serving.router import HTTPBackend, Router, \
        RouterConfig

    cfg, params = cfg_params
    engines, ports = [], []
    for _ in range(2):
        eng = ServingEngine(cfg, params, EngineConfig(
            max_rows=4, max_seq_len=256, page_size=32,
            prefill_bucket=32)).start()
        engines.append(eng)
        port, _ = _serve(OpenAIServer(eng, _Tok(), "tiny"))
        ports.append(port)
    try:
        for port in ports:
            body = json.dumps({"prompt": "1 2 3 4", "max_tokens": 2,
                               "temperature": 0.0}).encode()
            urllib.request.urlopen(urllib.request.Request(
                f"http://127.0.0.1:{port}/v1/completions", data=body,
                headers={"Content-Type": "application/json"}),
                timeout=60)
        router = Router(
            [HTTPBackend(f"http://127.0.0.1:{p}") for p in ports],
            RouterConfig())
        async def go():
            text = await router.metrics_text()
            for r in router.replicas:
                await r.backend.close()
            return text

        loop = asyncio.new_event_loop()
        try:
            text = loop.run_until_complete(go())
        finally:
            loop.close()
        expect = sum(e.perf.compiles["compiles_total"] for e in engines)
        line = [ln for ln in text.splitlines()
                if ln.startswith("ipex_llm_tpu_fleet_perf_compiles_total")]
        assert line and float(line[0].split()[-1]) == expect
        assert any(ln.startswith("ipex_llm_tpu_fleet_perf_compiles_warm")
                   for ln in text.splitlines())
        # per-replica labelled series survive beside the sums
        assert 'ipex_llm_tpu_perf_compiles_total{replica="0"' in text
    finally:
        for eng in engines:
            eng.stop()


# -- host phase spans, wait counters, device scopes (PR 25) ------------------

WAIT_COUNTERS = ("queue_wait_s_total", "admit_to_first_token_s_total",
                 "first_tokens")


def _phase_counters(eng, clock="s"):
    nm = eng.perf_numeric()
    return {p: nm[f"perf_phase_{p}_{clock}"] for p in PHASES
            if clock == "s" or p in CPU_PHASES}


def _hand_clocks(monkeypatch):
    """``perfwatch``'s two clocks moved by hand: ``spend(ms, cpu_ms)``
    (the thread's CPU time a quarter of the wall time unless given, so
    every number stays exact in binary)."""
    import types

    from ipex_llm_tpu.serving import perfwatch

    now = [0.0, 0.0]
    monkeypatch.setattr(perfwatch, "time", types.SimpleNamespace(
        perf_counter=lambda: now[0] * 1e-3,
        thread_time=lambda: now[1] * 1e-3))

    def spend(ms, cpu_ms=None):
        now[0] += ms
        now[1] += ms / 4 if cpu_ms is None else cpu_ms

    return spend


def _ms(d):
    return {k: round(v * 1e3, 6) for k, v in d.items() if v}


def _bucket_sum(eng, bucket):
    return sum(h.sum for k, h in eng.hists.items()
               if k.startswith("perf_tick_") and k.endswith(f"_{bucket}_s"))


def test_phase_unit_self_times_and_carry(monkeypatch):
    """Nested phases book self times, ``tick`` its whole span; what is
    open when ``tick_finish`` closes the books is booked up to there and
    the rest counts into the NEXT committed tick; an aborted or idle
    tick books nothing and keeps the committed tail.  On clocks the
    test moves by hand (ms), so every number is exact; the thread's CPU
    time, a quarter of the wall time throughout, is booked by the same
    rules for the phases that read it (``CPU_PHASES``: ``drain``'s holds
    what ``sync``, which is not read, spent)."""
    spend = _hand_clocks(monkeypatch)
    ms = _ms
    w = PerfWatch()

    def tick(finish):
        with w.phase("tick", tick=1):
            spend(1)                        # in no phase
            w.tick_begin()
            with w.phase("admit"):
                spend(2)
                with w.dispatch("tick.steady"):
                    spend(3)
                spend(4)
            w.phase_to("drain")
            w.phase_to("drain")             # the open one again: stays
            spend(5)
            with w.phase("sync"):
                spend(6)
            w.phase_to(None)
            with w.phase("record"):
                spend(7)
                finish()
                spend(8)                    # after the books closed
            spend(9)

    tick(lambda: w.tick_finish(1, working=True))
    first = {"tick": 28, "admit": 6, "dispatch": 3, "drain": 5, "sync": 6,
             "record": 7}
    assert ms(w.phase_s) == first           # 28 = 1+2+3+4+5+6+7
    tick(w.tick_abort)                      # rolled back: nothing
    tick(lambda: w.tick_finish(0, working=False))   # idle: nothing
    assert ms(w.phase_s) == first
    tick(lambda: w.tick_finish(1, working=True))
    # the first tick's tail (8 of record; 8 + 9 of tick) arrived with the
    # next COMMITTED tick, through the aborted and the idle one
    assert ms(w.phase_s) == {"tick": 28 + 28 + 17, "admit": 12,
                             "dispatch": 6, "drain": 10, "sync": 12,
                             "record": 7 + 7 + 8}
    assert ms(w.phase_cpu_s) == {"tick": (28 + 28 + 17) / 4,
                                 "dispatch": 6 / 4, "drain": (10 + 12) / 4}
    assert w.metrics_numeric()["phase_record_s"] == 0.022
    assert w.metrics_numeric()["phase_drain_cpu_s"] == 0.0055
    assert "phase_record_cpu_s" not in w.metrics_numeric()
    assert w.metrics_numeric()["phase_plan_s"] == 0.0


@pytest.mark.parametrize("case", ["sleeps", "spins", "nested", "sync_split",
                                  "rolled_back"])
def test_phase_books_thread_cpu_beside_wall(monkeypatch, case):
    """One entry, two clocks: a phase that sleeps books wall >> cpu, one
    that spins cpu ~ wall (the real clocks); nested self times partition
    ``tick`` on BOTH clocks, ``sync_wait`` + ``sync_fetch`` + ``sync`` is
    the sync window, and a rolled-back tick leaves nothing in any
    ``_cpu_s`` (clocks moved by hand: exact)."""
    w = PerfWatch()
    if case in ("sleeps", "spins"):
        with w.phase("tick", tick=1):
            w.tick_begin()
            with w.phase("prepare"):
                if case == "sleeps":
                    time.sleep(0.05)
                else:
                    c0 = time.thread_time()
                    while time.thread_time() - c0 < 0.03:
                        pass
            flight = w.tick_finish(0, working=True)
        wall, cpu = w.phase_s["prepare"], w.phase_cpu_s["prepare"]
        assert flight["phases_cpu"]["prepare"] == round(cpu, 6)
        if case == "sleeps":
            assert wall >= 0.05 and cpu < 0.2 * wall, (wall, cpu)
        else:
            # the thread may be preempted while it spins, never credited
            # CPU time it did not have (2 ms: the clocks' grain)
            assert 0.03 <= cpu <= wall + 2e-3, (wall, cpu)
        return
    spend = _hand_clocks(monkeypatch)

    def tick(finish):
        with w.phase("tick", tick=1):
            spend(1, 1)                     # in no phase, all of it CPU
            w.tick_begin()
            with w.phase("prepare"):
                spend(2, 1)
                with w.dispatch("tick.steady"):
                    spend(4, 0.5)           # released the interpreter
            w.phase_to("drain")
            spend(2, 2)
            with w.phase("sync"):
                spend(0.5, 0.5)
                with w.phase("sync_wait"):
                    spend(16, 0)            # blocked: the device is busy
                with w.phase("sync_fetch", n=2, bytes=136):
                    spend(3, 0.25)
                spend(0.5, 0.5)
            w.phase_to(None)
            finish()

    tick(w.tick_abort if case == "rolled_back"
         else lambda: w.tick_finish(1, working=True))
    wall, cpu = _ms(w.phase_s), _ms(w.phase_cpu_s)
    if case == "rolled_back":
        assert wall == cpu == {}
        assert not any(v for k, v in w.metrics_numeric().items()
                       if k.startswith("phase_"))
    elif case == "nested":
        assert wall == {"tick": 29, "prepare": 2, "dispatch": 4, "drain": 2,
                        "sync": 1, "sync_wait": 16, "sync_fetch": 3}
        # ``sync`` is not read: its own 1 ms of CPU time is ``drain``'s
        assert cpu == {"tick": 5.75, "prepare": 1, "dispatch": 0.5,
                       "drain": 3, "sync_fetch": 0.25}
        for booked in (wall, cpu):      # 1 ms, all CPU, lies in no phase
            assert sum(v for k, v in booked.items() if k != "tick") \
                == booked["tick"] - 1
        assert set(w.phase_cpu_s) == set(CPU_PHASES)
    else:
        # the window the ``sync`` bucket times is the three phases' sum
        window = wall["sync"] + wall["sync_wait"] + wall["sync_fetch"]
        assert window == 20
        assert w.metrics_numeric()["phase_sync_s"] == 0.001


def test_phase_counters_partition_the_tick_and_agree_with_buckets(cfg_params):
    """Over committed ticks the eleven phases partition ``engine/tick``;
    ``dispatch`` and ``sync`` are the buckets of those names; and
    ``bookkeep`` (+ ``device``: host work that overlaps the device) is
    admit + prepare + drain + commit + the part of record before the
    books close."""
    cfg, params = cfg_params
    eng = ServingEngine(cfg, params, EngineConfig(
        max_rows=4, max_seq_len=256, page_size=16, prefill_bucket=16,
        decode_horizon=4))
    outs = _drive(eng, [Request(prompt_ids=p, max_new_tokens=10)
                        for p in _prompts(3, 24)])
    assert all(len(o) == 10 for o in outs)
    ph = _phase_counters(eng)
    ring = eng.flight.view()["ring"]
    n = len(ring)
    assert all(v > 0 for v in ph.values()), ph
    kids = sum(v for k, v in ph.items() if k != "tick")
    assert kids <= ph["tick"] + 1e-9
    # what is in no phase is a few statements between two of them; the
    # bound is wide because a loaded test machine can preempt the thread
    # right there (the unit test above has the exact partition)
    assert ph["tick"] - kids <= 0.1 * ph["tick"] + 1e-3 * n
    # a phase opens just before its bucket's window and closes just after:
    # never shorter, and longer by the span's own cost only (well under a
    # ms a tick even on a loaded test machine)
    window = {"dispatch": ph["dispatch"],
              "sync": ph["sync"] + ph["sync_wait"] + ph["sync_fetch"]}
    for name in ("dispatch", "sync"):
        extra = window[name] - _bucket_sum(eng, name)
        assert -1e-6 * n <= extra < 1e-3 * n, (name, extra)
    # the thread's CPU time, phase by phase: never more than the wall time
    # (to the clocks' grain), and partitioned by the same phases
    cpu = _phase_counters(eng, "cpu_s")
    assert set(cpu) == set(CPU_PHASES)
    assert all(0 <= cpu[p] <= ph[p] + 2e-3 * n for p in cpu if p != "drain")
    assert 0 <= cpu["drain"] <= ph["drain"] + ph["sync"] + 2e-3 * n
    assert sum(v for k, v in cpu.items() if k != "tick") <= cpu["tick"] + 1e-9
    book = _bucket_sum(eng, "bookkeep") + _bucket_sum(eng, "device")
    inner = ph["admit"] + ph["prepare"] + ph["drain"] + ph["commit"]
    assert inner - 1e-3 * n <= book <= inner + ph["record"] + 1e-3 * n
    # every committed tick's record carries its own split (a record's phase
    # is never shorter than its bucket; longer by the span's own cost, but for
    # the one tick where a loaded test machine preempts the thread between
    # the two clocks: the typical record is held, not each)
    extras = []
    for rec in ring:
        assert set(rec["phases"]) <= set(PHASES)
        assert set(rec["phases_cpu"]) == set(rec["phases"]) & CPU_PHASES
        extras.append(rec["phases"]["dispatch"] - rec["attrib"]["dispatch"])
        assert -2e-6 <= extras[-1], rec
    assert sorted(extras)[len(extras) // 2] < 1e-3, extras
    # the wait counters: three requests, each waited and was prefilled
    m = eng.metrics
    assert m["first_tokens"] == 3
    assert m["queue_wait_s_total"] > 0
    assert m["admit_to_first_token_s_total"] > 0


@pytest.mark.parametrize("horizon,spec_k", [(1, 0), (8, 0), (4, 2)])
def test_d2h_total_counts_every_fetch_of_the_tick(cfg_params, horizon, spec_k):
    """``d2h_total``, the mirror of ``h2d_total``: whatever a tick that
    decodes sends home (the token block, the logprob block and ``n_exec``;
    a mixed tick's first tokens and their logprobs; a speculating tick's
    accepted counts and its two draft counters) is ONE packed buffer and
    ONE fetch, a pure-chunk tick fetches nothing.  The fetch is inside
    ``engine/sync``: it blocks in ``sync_wait``, and ``sync_fetch`` takes
    the buffer apart."""
    cfg, params = cfg_params
    eng = ServingEngine(cfg, params, EngineConfig(
        max_rows=4, max_seq_len=256, page_size=32, prefill_bucket=32,
        decode_horizon=horizon, spec_k=spec_k))
    m = eng.metrics
    first = Request(prompt_ids=_prompts(1, 40)[0], max_new_tokens=40)
    late = Request(prompt_ids=_prompts(1, 40)[0][::-1], max_new_tokens=3)
    eng.submit(first)
    seen = set()                    # (mixed, completes, decodes, fetches)
    for n in range(400):
        if n == 4:
            eng.submit(late)        # two chunks beside a decoding row
        before = {k: m[k] for k in ("d2h_total", "mixed_steps",
                                    "first_tokens", "host_syncs")}
        eng._tick()
        d = {k: m[k] - v for k, v in before.items()}
        seen.add((d["mixed_steps"], d["first_tokens"], d["host_syncs"],
                  d["d2h_total"]))
        if first.finish_reason and late.finish_reason:
            break
    assert seen == {(1, 0, 0, 0),           # a chunk alone: no sync at all
                    (1, 1, 1, 1),           # mixed, completes a prompt
                    (1, 0, 1, 1),           # mixed, beside a decoding row
                    (0, 0, 1, 1)}, seen     # steady, any horizon, spec or not
    assert m["mixed_steps"] == 4
    assert m["d2h_total"] == m["host_syncs"]
    # the window's books: one sync_wait and one sync_fetch a syncing tick,
    # and the counter the benchmark divides still exported (~0, not absent)
    ring = eng.flight.view()["ring"]
    synced = [r for r in ring if "sync_wait" in r["phases"]]
    assert len(synced) == m["host_syncs"]
    assert all("sync_fetch" in r["phases"] for r in synced)
    assert eng.perf_numeric()["perf_phase_sync_fetch_s"] > 0


def test_a_slow_tick_freezes_the_flight_ring(cfg_params, monkeypatch):
    """A committed tick whose ``sync_wait`` passes the limit dumps the ring
    (reason ``slow_tick``, the ring's own ``max_dumps`` bounds them) and
    counts ``slow_ticks_total``; at the real limit no tick of a healthy
    run does."""
    from ipex_llm_tpu.serving import engine as engine_mod

    cfg, params = cfg_params
    ec = EngineConfig(max_rows=2, max_seq_len=128, page_size=16,
                      prefill_bucket=16)
    eng = ServingEngine(cfg, params, ec)
    _drive(eng, [Request(prompt_ids=_prompts(1, 12)[0], max_new_tokens=12)])
    assert eng.metrics["slow_ticks_total"] == 0
    assert eng.flight.view()["dumps"] == []
    monkeypatch.setattr(engine_mod, "SLOW_SYNC_WAIT_S", 0.0)
    eng = ServingEngine(cfg, params, ec)
    _drive(eng, [Request(prompt_ids=_prompts(1, 12)[0], max_new_tokens=12)])
    assert eng.metrics["slow_ticks_total"] == eng.metrics["host_syncs"] > 8
    dumps = eng.flight.view()["dumps"]
    assert len(dumps) == eng.flight.dumps.maxlen
    last = dumps[-1]
    assert last["reason"] == "slow_tick" and last["sync_wait_s"] > 0
    # the tick that lost the time is the ring's newest record
    assert last["ring"][-1]["tick"] == last["tick"]
    assert last["ring"][-1]["phases"]["sync_wait"] == last["sync_wait_s"]


def test_commit_stamps_one_a_request_a_tick(cfg_params):
    """The commit leaves ONE stamp a request a tick beside the items it
    releases, there before them: a horizon's block shares it, the terminal
    None rides the last one, and ``stream_stamp`` hands each item its
    commit's time in order."""
    from ipex_llm_tpu.serving.engine import stream_stamp

    cfg, params = cfg_params
    eng = ServingEngine(cfg, params, EngineConfig(
        max_rows=2, max_seq_len=128, page_size=16, prefill_bucket=16,
        decode_horizon=4))
    reqs = [Request(prompt_ids=p, max_new_tokens=9) for p in _prompts(2, 12)]
    for r in reqs:
        eng.submit(r)
    while not all(r.finish_reason for r in reqs):
        n0 = [len(r.commit_stamps) for r in reqs]
        t0 = time.perf_counter()
        eng._tick()
        for r, n in zip(reqs, n0):
            new = list(r.commit_stamps)[n:]
            assert len(new) <= 1
            assert all(t0 <= t <= time.perf_counter() for t, _ in new)
    for r in reqs:
        # the first token (a dense model's row takes its first decode
        # step in the NEXT tick), a block of four, the last four with the
        # None
        assert [n for _, n in r.commit_stamps] == [1, 4, 5]
        items, stamps = [], []
        while not r.stream_queue.empty():
            items.append(r.stream_queue.get())
            stamps.append(stream_stamp(r))
        assert items == r.output_ids + [None] and len(items) == 10
        assert stamps == sorted(stamps) and len(set(stamps)) == 3
        assert len(set(stamps[1:5])) == 1
        assert not r.commit_stamps and stream_stamp(r) is None


@pytest.mark.parametrize("stream", [True, False])
def test_server_counts_a_tokens_way_to_the_socket(cfg_params, stream):
    """``stream_events_total`` is the token events written (the tokens
    collected, without a stream), each with ``0 <= wake <= deliver`` from
    its commit's stamp, the event loop's CPU time beside them, and the
    ``stream_deliver_s`` histogram one observation an event."""
    pytest.importorskip("aiohttp")
    from ipex_llm_tpu.serving.api_server import OpenAIServer

    cfg, params = cfg_params
    eng = ServingEngine(cfg, params, EngineConfig(
        max_rows=4, max_seq_len=256, page_size=32, prefill_bucket=32,
        decode_horizon=4)).start()
    srv = OpenAIServer(eng, _Tok(), "tiny")
    port, _ = _serve(srv)
    try:
        m0 = json.loads(_get(port, "/metrics?format=json"))
        assert m0["metrics"]["stream_events_total"] == 0
        assert m0["histograms"]["stream_deliver_s"]["count"] == 0
        events = 0
        for i in range(2):
            body = json.dumps({"prompt": " ".join(map(str, range(i + 1, 30))),
                               "max_tokens": 11, "temperature": 0.0,
                               "stream": stream}).encode()
            with urllib.request.urlopen(urllib.request.Request(
                    f"http://127.0.0.1:{port}/v1/completions", data=body,
                    headers={"Content-Type": "application/json"}),
                    timeout=60) as resp:
                raw = resp.read().decode()
            if stream:
                chunks = [json.loads(ln[6:]) for ln in raw.splitlines()
                          if ln.startswith("data: {")]
                events += sum(1 for c in chunks if c["choices"][0]["text"])
            else:
                events += json.loads(raw)["usage"]["completion_tokens"]
        assert events == 22
        got = json.loads(_get(port, "/metrics?format=json"))
        m = got["metrics"]
        assert m["stream_events_total"] == events
        assert 0 <= m["stream_wake_s_total"] <= m["stream_deliver_s_total"]
        assert 0 < m["stream_cpu_s_total"]
        # a write that does not block costs the loop's thread what it
        # takes (2 ms an event: the clocks' grain, a loaded test machine)
        assert m["stream_cpu_s_total"] <= (m["stream_deliver_s_total"]
                                           - m["stream_wake_s_total"]
                                           + 2e-3 * events)
        h = got["histograms"]["stream_deliver_s"]
        assert h["count"] == events
        assert h["sum"] == pytest.approx(m["stream_deliver_s_total"],
                                         abs=1e-4)
        assert "ipex_llm_tpu_stream_deliver_s_bucket" in _get(port,
                                                              "/metrics")
    finally:
        eng.stop()


@pytest.mark.parametrize("kind", ["rollback", "probe", "idle"])
def test_phase_and_wait_counters_skip_uncommitted_ticks(cfg_params, kind):
    """A rolled-back tick, a tick whose recovery runs bisection probes,
    and an idle tick add nothing to any ``perf_phase_*`` counter nor to
    the wait counters (the retried, committed tick counts once)."""
    cfg, params = cfg_params
    inj = FaultInjector()
    if kind == "rollback":
        inj.inject("sample", TransientFault)
    elif kind == "probe":
        inj.inject("decode-dispatch", DeterministicFault, nth=3, times=None,
                   request_id="poison")
    eng = ServingEngine(cfg, params, EngineConfig(
        max_rows=4, max_seq_len=256, page_size=32, prefill_bucket=8,
        decode_horizon=2, retry_backoff_s=0.001, max_step_retries=1),
        fault_injector=inj)
    reqs = [Request(prompt_ids=p, max_new_tokens=6, request_id=rid)
            for p, rid in zip(_prompts(2, 12), ("poison", "ok"))]
    for r in reqs:
        eng.submit(r)
    uncommitted = 0
    for _ in range(400):
        before = (_phase_counters(eng), _phase_counters(eng, "cpu_s"),
                  {k: eng.metrics[k] for k in WAIT_COUNTERS})
        idle = all(r.finish_reason is not None for r in reqs)
        committed = eng._tick()
        if not committed or idle:
            uncommitted += 1
            after = (_phase_counters(eng), _phase_counters(eng, "cpu_s"),
                     {k: eng.metrics[k] for k in WAIT_COUNTERS})
            assert after == before, (kind, committed, idle)
        if idle and uncommitted >= 3:
            break
    assert uncommitted >= 3
    if kind == "rollback":
        assert eng.metrics["retries"] >= 1
    if kind == "probe":
        assert eng.metrics["errors_isolated"] == 1
        assert reqs[0].finish_reason == "error"
    # each request that reached its first token counted exactly once
    done = sum(1 for r in reqs if r.first_token_s > 0)
    assert eng.metrics["first_tokens"] == done
    assert eng.metrics["queue_wait_s_total"] == pytest.approx(
        sum(r.queue_wait_s for r in reqs), abs=1e-5)


def test_profiler_capture_holds_the_tick_spans(cfg_params, tmp_path):
    """A jax.profiler capture with the benchmark's options (host tracer
    level 1, no python tracer) holds ``engine/tick`` events on the host
    plane, numbered, and nested inside them the phases, with
    ``engine/dispatch`` carrying the variant and the grid point: of the
    program it dispatches, or (``PerfWatch.running``) of the queued
    program the device runs from there on."""
    import glob

    import jax

    cfg, params = cfg_params
    eng = ServingEngine(cfg, params, EngineConfig(
        max_rows=4, max_seq_len=256, page_size=16, prefill_bucket=16))
    _drive(eng, Request(prompt_ids=_prompts(1, 20)[0], max_new_tokens=4))
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        _drive(eng, [Request(prompt_ids=p, max_new_tokens=6)
                     for p in _prompts(2, 20)])
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(str(tmp_path / "plugins/profile/*/*.xplane.pb"))
    data = jax.profiler.ProfileData.from_file(path)
    spans = []
    for plane in data.planes:
        for line in plane.lines:
            mine = [(e.name, e.start_ns, e.start_ns + e.duration_ns,
                     dict(e.stats)) for e in line.events
                    if e.name.startswith("engine/")]
            if mine:
                assert plane.name == "/host:CPU"
                spans.append(mine)
    assert len(spans) == 1, "one thread ticks the engine"
    spans = spans[0]
    ticks = [s for s in spans if s[0] == "engine/tick"]
    assert len(ticks) >= 4
    numbers = [t[3]["tick"] for t in ticks]
    assert numbers == sorted(numbers) and numbers[0] >= 1
    seen, variants, kinds = set(), set(), set()
    for name, a, b, stats in spans:
        if name == "engine/tick":
            continue
        assert any(t[1] <= a and b <= t[2] for t in ticks), name
        seen.add(name.split("/", 1)[1])
        if name == "engine/dispatch" and set(stats) == {"queued"}:
            kinds.add("awaited")    # a tick that only reads a queued program
        elif name == "engine/dispatch":
            # a dispatch in the serial order, a program's window marked
            # after its predecessor's sync (``queued``), or a dispatch
            # behind the running program (``ahead``: no window of its own)
            assert ("variant" in stats) != ("ahead" in stats)
            kinds.add("ahead" if "ahead" in stats
                      else "running" if "queued" in stats else "serial")
            variants.add(stats.get("variant") or stats["ahead"])
            assert stats["family"].startswith("tick.")
            assert {"p_b", "width", "maxp", "rows_active"} <= set(stats)
    assert kinds == {"serial", "ahead", "running", "awaited"}
    assert seen == set(PHASES) - {"tick"}
    assert "steady" in variants and variants & {"mixed", "chunk"}
    assert variants <= {"steady", "mixed", "chunk", "spec"}


@pytest.mark.parametrize("form,point,stages", [
    # a dense model's mixed tick is ONE forward under ``tick/decode``, the
    # merge after it; the staged tick (here: kept by speculation) opens
    # ``tick/prefill`` around the block's own forward
    ("fused", {}, ("tick/decode", "tick/merge")),
    ("staged", {"spec": 4}, ("tick/prefill", "tick/merge", "tick/decode")),
])
def test_lowered_tick_names_every_device_scope(form, point, stages):
    """The mixed tick's lowered text (debug info, no execution) holds
    every named scope of the program: the tick's stages, the layer's
    stages, the head and the sampler."""
    from ipex_llm_tpu.analysis.trace.registry import _build_ragged_tick
    from ipex_llm_tpu.serving.engine import _ragged_tick

    args, kw = _build_ragged_tick({"rows": 4, "width": 8, "horizon": 1,
                                   "kv": "bf16", **point})
    text = _ragged_tick.lower(*args, **kw).as_text(debug_info=True)
    assert ("/tick/prefill/" in text) == (form == "staged")
    for scope in stages + ("layer/qkv",
                  "layer/kv_write", "layer/kv_read", "layer/attn",
                  "layer/o_proj", "layer/mlp", "head", "sample"):
        # a scope opens a name: at the start of one (a scan body's names
        # start anew) or after the scopes around it
        assert f'"{scope}/' in text or f"/{scope}/" in text, scope


def test_perfwatch_disabled_records_no_phase(cfg_params, tmp_path):
    """``perfwatch=False`` turns all of it off: no ``perf_phase_*``
    counter, no ``engine/*`` span in a capture, no ``phases`` in the
    flight ring.  (The wait counters are engine metrics and stay.)"""
    import glob

    import jax

    cfg, params = cfg_params
    eng = ServingEngine(cfg, params, EngineConfig(
        max_rows=4, max_seq_len=256, page_size=32, prefill_bucket=32,
        perfwatch=False))
    jax.profiler.start_trace(str(tmp_path))
    try:
        _drive(eng, Request(prompt_ids=_prompts(1, 8)[0], max_new_tokens=4))
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(str(tmp_path / "plugins/profile/*/*.xplane.pb"))
    data = jax.profiler.ProfileData.from_file(path)
    assert not [e.name for plane in data.planes for line in plane.lines
                for e in line.events if e.name.startswith("engine/")]
    assert not any(k.startswith("perf_") for k in eng.perf_numeric())
    assert all("phases" not in r for r in eng.flight.view()["ring"])
    assert eng.metrics["first_tokens"] == 1


def test_sentinel_warns_once_in_full_and_names_the_axis(cfg_params, caplog):
    """An engine's first out-of-grid compile warns in full and names the
    axis that is outside the locked grid; later ones log at debug level.
    The counters count every one of them as before."""
    import logging

    cfg, params = cfg_params
    eng = ServingEngine(cfg, params, EngineConfig(
        max_rows=6, max_seq_len=256, page_size=16, prefill_bucket=16))
    with caplog.at_level(logging.DEBUG, logger="ipex_llm_tpu.perfwatch"):
        _drive(eng, [Request(prompt_ids=p, max_new_tokens=6)
                     for p in _prompts(2, 20)])
    s = eng.perf.sentinel_view()
    assert s["compiles_out_of_grid"] >= 2
    full = [r for r in caplog.records if r.levelno == logging.WARNING
            and "OUTSIDE the manifest-locked" in r.getMessage()]
    assert len(full) == 1
    assert "rows=6 (locked up to 8)" in full[0].getMessage()
    later = [r for r in caplog.records if r.levelno == logging.DEBUG
             and "outside the locked grid" in r.getMessage()]
    assert len(later) == s["compiles_out_of_grid"] - 1
    grid = eng.perf.grid
    assert "structural form" in outside_axes(
        {"rows": 4, "width": 0, "horizon": 1, "kv": "int3"}, grid)
    assert outside_axes({"rows": 4, "width": 0, "horizon": 32,
                         "kv": "bf16"}, grid).startswith("horizon=32")
