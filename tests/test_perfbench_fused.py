"""``fused_mixed_tick_share`` (PR 52): a data file over ``counter_scaled``,
read on planted counter scrapes, and declared for every cell.  No jax."""

import json
import os
import sys

import pytest

BENCH = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))), "perfbench")
sys.path[:0] = [p for p in (BENCH,) if p not in sys.path]

import run  # noqa: E402
from readers import counter_scaled  # noqa: E402

BENCHMARK = json.load(open(os.path.join(os.path.dirname(BENCH),
                                        "BENCHMARK.json")))


def _scrapes(fused, mixed, have=True):
    def at(f, n):
        m = {"mixed_steps": n}
        if have:
            m["mixed_fused_steps"] = f
        return {"metrics": m}
    return {"record": {"counters": {"start": at(fused[0], mixed[0]),
                                    "end": at(fused[1], mixed[1])}}}


def _reader():
    return run.load_json(os.path.join(
        BENCH, "layer_metrics", "fused_mixed_tick_share.json"))["reader"]


@pytest.mark.parametrize("fused,mixed,have,want", [
    ((40, 240), (40, 240), True, 100.0),      # a dense cell: every mixed tick
    ((0, 0), (100, 900), True, 0.0),          # a staged family: reported, 0
    ((0, 0), (100, 900), False, None),        # the parent: no such counter
    ((7, 7), (30, 30), True, None),           # no mixed tick in the window
])
def test_fused_mixed_tick_share_over_planted_counters(fused, mixed, have,
                                                      want):
    assert counter_scaled.read(_reader(), _scrapes(fused, mixed, have)) == want


def test_the_metric_is_declared_for_every_cell_as_data():
    entry = next(m for m in BENCHMARK["per_layer"]
                 if m["name"] == "fused_mixed_tick_share")
    assert entry == {
        "name": "fused_mixed_tick_share", "unit": "%", "better": "higher",
        "source": "program_counter", "layer": "tick program",
        "moves": "itl_p95_ms",
        "workloads": [w["name"] for w in BENCHMARK["workloads"]]}
    assert BENCHMARK["per_layer"][-1] is entry       # appended, last
    # the layer's name is one the benchmark already had
    assert sum(m["layer"] == "tick program"
               for m in BENCHMARK["per_layer"]) > 1
