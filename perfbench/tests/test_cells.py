"""The table of cells (BENCHMARK.json against the files it names) and the
window's percentiles (PR 33)

    JAX_PLATFORMS=cpu python -m pytest perfbench/tests/test_cells.py -q

A rename that misses one list, or a traffic file that drifts from the cell it
replaced, fails here and not on the chip.  No jax import.  Tier-1 collects
these cases through test_attn_roofline.py (see its last lines).
"""

from __future__ import annotations

import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
sys.path[:0] = [BENCH, ROOT]

import run  # noqa: E402
import stats  # noqa: E402
import trafficgen  # noqa: E402

BENCHMARK = run.load_json(os.path.join(ROOT, "BENCHMARK.json"))
CELLS = {w["name"]: w for w in BENCHMARK["workloads"]}
METRICS = BENCHMARK["end_to_end"] + BENCHMARK["per_layer"]

# perfbench/traffic/chat-steady.json as PR 24 committed it and PR 33 retired
# it, without its prose (`why`, `assumed`): the successor may differ from it
# in the rate, and in the order's pattern that the driver's check forced
RETIRED_CHAT_STEADY = {
    "loop": "open",
    "rate_rps": 0.8,
    "prompt_len": {"dist": "lognormal", "median": 300, "sigma": 0.8,
                   "min": 32, "max": 1024},
    "output_len": {"dist": "lognormal", "median": 100, "sigma": 0.7,
                   "min": 16, "max": 256},
    "ramp_s": 10,
    "drain_s": 40,
    "warmup": {"p_b": [1, 2, 4, 8, 16], "longest_prompt": 1024,
               "replay_s": 6, "max_rounds": 4},
    "trace": {"start_s": 3, "seconds": 3},
}


@pytest.mark.parametrize("metric", METRICS, ids=lambda m: m["name"])
def test_a_metric_lists_only_cells_that_are_there(metric):
    listed = metric.get("workloads")
    if listed is None:
        return                      # reported in every cell
    assert listed and len(set(listed)) == len(listed)
    assert set(listed) <= set(CELLS), metric["name"]


@pytest.mark.parametrize("metric", BENCHMARK["per_layer"],
                         ids=lambda m: m["name"])
def test_a_per_layer_metric_has_its_file_its_reader_and_its_target(metric):
    spec = run.load_json(os.path.join(BENCH, "layer_metrics",
                                      metric["name"] + ".json"))
    assert spec["what"] and "\n" not in spec["what"]
    assert os.path.exists(os.path.join(
        BENCH, "readers", spec["reader"]["kind"] + ".py"))
    target = {m["name"]: m for m in BENCHMARK["end_to_end"]}[metric["moves"]]
    # each cell that reports the metric reports what it should move
    for cell in metric.get("workloads", CELLS):
        assert cell in target.get("workloads", CELLS), (metric["name"], cell)


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_a_cell_finds_its_configuration_and_its_traffic(cell):
    w = CELLS[cell]
    entry = {c["name"]: c for c in BENCHMARK["configs"]}[w["config"]]
    assert os.path.exists(os.path.join(ROOT, entry["file"]))
    traffic = run.load_json(os.path.join(BENCH, "traffic",
                                         w["traffic"] + ".json"))
    assert traffic["loop"] in ("open", "closed") and traffic["why"]
    resolved = run.resolve_cell(ROOT, cell)
    reported = {m["name"] for m in resolved["end_to_end"]}
    assert "setup_s" in reported and len(reported) >= 2
    assert resolved["per_layer"]


def test_no_traffic_file_is_left_without_a_cell():
    used = {w["traffic"] + ".json" for w in CELLS.values()}
    there = set(os.listdir(os.path.join(BENCH, "traffic")))
    # prefill-heavy is kept for a later cell (PERF.md section 7)
    assert used <= there and there - used <= {"prefill-heavy.json"}


def test_the_retired_cell_is_gone_from_every_list():
    assert "mistral7b.chat-steady" not in CELLS
    assert not os.path.exists(os.path.join(BENCH, "traffic",
                                           "chat-steady.json"))
    # a later PR adds cells by adding entries; these three stay
    assert set(CELLS) >= {"mistral7b.decode-saturated",
                          "mistral7b.chat-near-knee",
                          "nemotron3nano.decode-saturated"}
    successor = CELLS["mistral7b.chat-near-knee"]
    assert (successor["config"], successor["traffic"], successor["chips"]) \
        == ("mistral-7b-v0.3.int4", "chat-near-knee", 1)
    reported = {m["name"] for m in run.resolve_cell(
        ROOT, "mistral7b.chat-near-knee")["end_to_end"]}
    assert reported == {"itl_p95_ms", "setup_s"}    # the offered load is no metric


def test_the_successor_differs_from_the_retired_traffic_in_rate_and_order():
    new = run.load_json(os.path.join(BENCH, "traffic", "chat-near-knee.json"))
    prose = {"why", "assumed"}
    assert set(new) - prose == set(RETIRED_CHAT_STEADY) | {"order"}
    differs = {k for k in RETIRED_CHAT_STEADY if new[k] != RETIRED_CHAT_STEADY[k]}
    assert differs == {"rate_rps"}
    assert set(new["order"]) == {"pattern_seed", "class_width"}
    assert new["assumed"]["order"]
    # the sweep it rests on is in the file: the knee, the share of it, and
    # the tree it was swept on
    swept = new["assumed"]["rate_rps"]
    assert new["rate_rps"] == pytest.approx(
        round(swept["share_of_knee"] * swept["knee_rps"], 1))
    assert 0.6 <= swept["share_of_knee"] <= 0.85
    assert swept["commit"] and swept["date"]
    rates = [row["rate_rps"] for row in swept["sweep"]]
    assert swept["knee_rps"] in rates and max(rates) > swept["knee_rps"]


# -- the order's pattern ------------------------------------------------------------

NEAR_KNEE = run.load_json(os.path.join(BENCH, "traffic", "chat-near-knee.json"))
SEEDS = (7, 2 ** 31 + 12345, 4294967311)


def classes(traffic: dict, key: str, n: int) -> dict:
    """value -> the classes of ``class_width`` sorted neighbours it is in."""
    dist = traffic[key]
    out = {}
    for i, v in enumerate(trafficgen.quantiles(dist, n)):
        out.setdefault(v, set()).add(i // traffic["order"]["class_width"])
    return out


@pytest.mark.parametrize("key,field", [("prompt_len", "prompt_len"),
                                       ("output_len", "max_tokens")])
def test_a_pattern_keeps_every_class_at_its_place_for_every_seed(key, field):
    """The file's pattern_seed says which class of near-equal sizes stands
    where; the run's seed only says which member of the class."""
    runs = [[r for r in trafficgen.schedule(NEAR_KNEE, s, 40.0, 10.0)["requests"]
             if r["due_s"] >= 0] for s in SEEDS]
    n = len(runs[0])
    assert n == round(NEAR_KNEE["rate_rps"] * 40.0)
    cls = classes(NEAR_KNEE, key, n)
    for place in zip(*runs):
        assert set.intersection(*(cls[r[field]] for r in place)), place
    # the same multiset, and yet another order
    assert len({tuple(sorted(r[field] for r in run_)) for run_ in runs}) == 1
    assert len({tuple(r[field] for r in run_) for run_ in runs}) == len(SEEDS)


def test_a_pattern_keeps_the_arrivals_to_a_class_of_gaps():
    runs = [[r["due_s"] for r in
             trafficgen.schedule(NEAR_KNEE, s, 40.0, 10.0)["requests"]]
            for s in SEEDS]
    assert len({tuple(run_) for run_ in runs}) == len(SEEDS)
    for due in runs:
        assert due == sorted(due) and -10.0 <= due[0] and due[-1] < 40.0
    # the same request is due at nearly the same time under every seed: only
    # the class of the four longest gaps (1.1-2.0 s) has members far apart
    width = max(max(t) - min(t) for t in zip(*runs))
    assert width < 1.5, width
    free = dict(NEAR_KNEE)
    del free["order"]
    loose = [[r["due_s"] for r in
              trafficgen.schedule(free, s, 40.0, 10.0)["requests"]]
             for s in SEEDS]
    assert max(max(t) - min(t) for t in zip(*loose)) > 3 * width


def test_another_pattern_seed_is_another_pattern():
    other = dict(NEAR_KNEE, order=dict(NEAR_KNEE["order"], pattern_seed=34))
    a = trafficgen.schedule(NEAR_KNEE, 7, 40.0, 10.0)
    b = trafficgen.schedule(other, 7, 40.0, 10.0)
    assert a != b
    assert sorted(r["prompt_len"] for r in a["requests"]) == sorted(
        r["prompt_len"] for r in b["requests"])


def test_without_the_key_the_seed_permutes_freely_as_it_always_did():
    """The closed-loop cells' requests are the parent's, seed for seed."""
    import numpy as np
    traffic = run.load_json(os.path.join(BENCH, "traffic",
                                         "decode-saturated.json"))
    assert "order" not in traffic
    seed = 2 ** 31 + 12345
    rng = np.random.default_rng([seed & 0xFFFFFFFF, seed >> 32])
    n = int(traffic["pool"])
    p = trafficgen.quantiles(traffic["prompt_len"], n)
    o = trafficgen.quantiles(traffic["output_len"], n)
    p = [p[i] for i in rng.permutation(n)]
    o = [o[i] for i in rng.permutation(n)]
    got = trafficgen.schedule(traffic, seed, 40.0, 0.0)["requests"]
    assert [(r["prompt_len"], r["max_tokens"]) for r in got] == list(zip(p, o))


# -- the percentiles beside the 95th ----------------------------------------------

def two_populations(n_steady: int, n_mixed: int) -> dict:
    """Gaps of a steady tick (12-16 ms) and of a mixed one (45-75 ms), as a
    window's sample carries them."""
    steady = [12.0 + 4.0 * i / n_steady for i in range(n_steady)]
    mixed = [45.0 + 30.0 * i / n_mixed for i in range(n_mixed)]
    return {"ttft_ms": [], "itl_ms": steady + mixed, "output_tok_s": 1.0}


def test_the_window_carries_p90_and_p98_beside_p95():
    v = stats.metric_values(two_populations(800, 200))     # 20 % mixed
    # nearest rank over 1000 sorted gaps: the 900th, 950th and 980th
    assert v["itl_p90_ms"] == pytest.approx(45.0 + 30.0 * 99 / 200)
    assert v["itl_p95_ms"] == pytest.approx(45.0 + 30.0 * 149 / 200)
    assert v["itl_p98_ms"] == pytest.approx(45.0 + 30.0 * 179 / 200)
    assert v["itl_p50_ms"] < 16.0 < v["itl_p90_ms"] < v["itl_p95_ms"] < v["itl_p98_ms"]
    assert v["itl_p98_ms"] / v["itl_p90_ms"] < 1.8          # all three inside one population


def test_the_ratio_of_p98_to_p90_shows_a_percentile_on_the_cliff():
    """PERF.md section 4's rule: with 3-8 % of the gaps in the slow
    population the 90th percentile is a steady tick's gap and the 98th a
    mixed one's, and the 95th lies on the edge between them."""
    v = stats.metric_values(two_populations(940, 60))      # 6 % mixed
    assert v["itl_p90_ms"] < 16.0 and v["itl_p98_ms"] > 45.0
    assert v["itl_p98_ms"] / v["itl_p90_ms"] > 1.8


@pytest.mark.parametrize("n,carried", [
    (99, set()),                                 # 9.9 beyond the 90th
    (100, {"itl_p90_ms"}),
    (199, {"itl_p90_ms"}),                       # 9.95 beyond the 95th
    (200, {"itl_p90_ms", "itl_p95_ms"}),
    (499, {"itl_p90_ms", "itl_p95_ms"}),         # 9.98 beyond the 98th
    (500, {"itl_p90_ms", "itl_p95_ms", "itl_p98_ms"})])
def test_a_thin_tail_is_left_out_not_guessed(n, carried):
    v = stats.metric_values({"ttft_ms": [], "output_tok_s": 1.0,
                             "itl_ms": [float(i) for i in range(n)]})
    assert {k for k in v if k in ("itl_p90_ms", "itl_p95_ms", "itl_p98_ms")} \
        == carried
    assert stats.MIN_BEYOND == 10


def test_the_two_percentiles_are_unbounded_per_layer_metrics_of_the_new_cell():
    per_layer = {m["name"]: m for m in BENCHMARK["per_layer"]}
    for name in ("itl_p90_ms", "itl_p98_ms"):
        m = per_layer[name]
        assert "bound" not in m
        assert (m["layer"], m["source"], m["moves"], m["unit"], m["better"]) == (
            "scheduler / tick transaction", "host_clock", "itl_p95_ms", "ms",
            "lower")
        assert "mistral7b.chat-near-knee" in m["workloads"]
        spec = run.load_json(os.path.join(BENCH, "layer_metrics",
                                          name + ".json"))
        assert spec["reader"] == {"kind": "e2e_value", "name": name}
        assert "re-sweep" in spec["what"]
