"""The benchmark's own tests: small and fast on the CPU, run by hand

    JAX_PLATFORMS=cpu python -m pytest perfbench/tests -q

(the contract keeps the benchmark's files under its own directory, so the
repo's tier-1 run, which collects tests/, does not collect these).
No topology call, no jax import at import time.
"""

from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
sys.path[:0] = [BENCH, ROOT]

import run  # noqa: E402
import shapes  # noqa: E402
import stats  # noqa: E402
import trace_reduce  # noqa: E402
import trafficgen  # noqa: E402

CONTRACT_KEYS = {"correct", "attempted", "failed", "metrics", "device"}


def bench_json() -> dict:
    return run.load_json(os.path.join(ROOT, "BENCHMARK.json"))


def test_every_name_resolves_and_moves_are_reported():
    bench = bench_json()
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    assert "setup_s" in e2e
    for w in bench["workloads"]:
        cell = run.resolve_cell(ROOT, w["name"])
        assert cell["config"]["chips"] == w["chips"] == 1
        reported = {m["name"] for m in cell["end_to_end"]}
        assert "setup_s" in reported and len(reported) >= 2
        assert cell["per_layer"], w["name"]
        for m in cell["per_layer"]:
            assert m["moves"] in reported, (w["name"], m["name"])
            assert os.path.exists(os.path.join(
                BENCH, "readers", m["reader"]["kind"] + ".py")), m["name"]
        hf = run.hf_config(cell["config"])
        for found_by_model_type in ("references", "generators"):
            assert os.path.exists(os.path.join(
                BENCH, found_by_model_type, hf["model_type"] + ".py"))
        assert "eos_token_id" not in hf
    for c in bench["configs"]:
        assert any(w["config"] == c["name"] for w in bench["workloads"])
        assert c["file"].startswith(bench["paths"][0] + "/")


def test_schedule_is_a_pure_function_of_the_seed():
    for name in os.listdir(os.path.join(BENCH, "traffic")):
        traffic = run.load_json(os.path.join(BENCH, "traffic", name))
        big = 2 ** 31 + 12345
        a = trafficgen.schedule(traffic, big, 30.0, 10.0)
        b = trafficgen.schedule(traffic, big, 30.0, 10.0)
        c = trafficgen.schedule(traffic, 7, 30.0, 10.0)
        assert a == b and a != c
        # another seed: the same multiset of lengths, in another order
        key = lambda s: sorted((r["prompt_len"], ) for r in s["requests"])
        assert key(a) == key(c)
        assert sorted(r["max_tokens"] for r in a["requests"]) == sorted(
            r["max_tokens"] for r in c["requests"])
        lo, hi = traffic["prompt_len"]["min"], traffic["prompt_len"]["max"]
        assert all(lo <= r["prompt_len"] <= hi for r in a["requests"])
        if traffic["loop"] == "open":
            due = [r["due_s"] for r in a["requests"]]
            in_win = [d for d in due if d >= 0]
            assert len(in_win) == round(traffic["rate_rps"] * 30.0)
            assert min(due) >= -10.0 and max(due) < 30.0
    ids = trafficgen.prompt_ids(big, 3, 50, 1000)
    assert ids == trafficgen.prompt_ids(big, 3, 50, 1000)
    assert ids != trafficgen.prompt_ids(big, 4, 50, 1000)
    assert min(ids) >= trafficgen.FIRST_TOKEN_ID and max(ids) < 1000


def test_percentile_refuses_a_thin_tail():
    xs = list(range(1, 101))
    assert stats.percentile(xs, 90) == 90
    assert stats.percentile(xs, 50) == 50
    with pytest.raises(ValueError):
        stats.percentile(xs[:99], 90)       # 9.9 samples beyond
    with pytest.raises(ValueError):
        stats.percentile(xs, 95)            # 5 beyond
    with pytest.raises(ValueError):
        stats.percentile(xs[:19], 50)


def test_end_to_end_counts_and_failures():
    rec = {"loop": "closed", "window": {"t0": 10.0, "t1": 20.0}, "requests": [
        # from the ramp: its gaps inside the window count, it is not attempted
        {"idx": 0, "due": None, "sent": 8.0, "status": 200, "done": True,
         "finish": "length", "max_tokens": 3, "token_t": [9.0, 10.5, 11.0]},
        {"idx": 1, "due": None, "sent": 12.0, "status": 200, "done": True,
         "finish": "length", "max_tokens": 2, "token_t": [12.25, 12.5]},
        {"idx": 2, "due": None, "sent": 13.0, "status": 200, "done": True,
         "finish": "stop", "max_tokens": 2, "token_t": [13.1, 13.2]},
        {"idx": 3, "due": None, "sent": 19.0, "status": 200, "done": False,
         "finish": None, "max_tokens": 9, "token_t": [19.5]},
    ]}
    e = stats.end_to_end(rec)
    assert (e["attempted"], e["failed"]) == (3, 2)
    assert e["ttft_ms"] == [250.0]
    assert len(e["itl_ms"]) == 4
    assert e["output_tok_s"] == pytest.approx(7 / 10.0)


def test_shapes_and_peaks():
    hf = run.hf_config(run.load_json(os.path.join(
        BENCH, "configs", "mistral-7b-v0.3.int4.json")))
    peaks = run.load_json(os.path.join(BENCH, "peaks.json"))
    with pytest.raises(KeyError):
        shapes.peak_for(peaks, "TPU v9 imaginary")
    peak = shapes.peak_for(peaks, "TPU v5 lite")
    assert shapes.linear_shapes(hf)["qkv"] == (4096, 6144)
    ops, byts = shapes.qmatmul_cost(4096, 6144, 16)
    assert ops == 2 * 16 * 4096 * 6144
    assert byts == 4096 * 6144 / 2 + 128 * 6144 * 2 + 16 * 4096 * 2 + 16 * 6144 * 2
    dec = shapes.pass_min_seconds(hf, 16, 16, peak)
    pre = shapes.pass_min_seconds(hf, 128, 1, peak)
    assert dec["calls"] == pre["calls"] == 4 * 32 + 1
    # batch-16 decode is bandwidth-bound: ~3.9 GB of planes at 819 GB/s
    assert dec["by_bound"]["compute"] == 0 and 4.5e-3 < dec["seconds"] < 5.5e-3
    assert pre["by_bound"]["compute"] > 0 and pre["seconds"] > dec["seconds"]


def test_trace_reduce_on_the_recorded_trace():
    path = os.path.join(BENCH, "testdata", "events_small.json")
    planes = {int(k): v for k, v in run.load_json(path).items()}
    red = trace_reduce.reduce_events(planes)
    assert 0 < red["busy_s"] <= red["window_s"]
    assert red["modules"] and all(m["busy_s"] <= m["dur_s"] * 1.001
                                  for m in red["modules"])
    assert any(k.startswith("qmatmul") for k in red["ops"])
    bd = trace_reduce.breakdown(red)
    assert 0 < len(bd["device_ops"]) <= 10 and len(bd["idle_gaps"]) <= 10
    assert trace_reduce.reduce_events({}) is None
    # the union does not count overlapping operations twice
    busy, gaps = trace_reduce._union([(0, 2), (1, 3), (5, 6)])
    assert busy == 4 and gaps == [(3, 2)]


def test_no_tpu_means_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    name = bench_json()["workloads"][0]["name"]
    p = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload", name,
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, env=env, cwd=ROOT, timeout=300)
    assert p.returncode != 0 and p.stdout.strip() == ""
    assert "no CPU mode" in p.stderr


# -- the whole run at a tiny size, and throw-away cells added by files alone -----

TINY = {
    "source": "test only", "model_type": "mistral", "hidden_size": 128,
    "intermediate_size": 256, "num_hidden_layers": 2,
    "num_attention_heads": 4, "num_key_value_heads": 2, "hidden_act": "silu",
    "max_position_embeddings": 4096, "rms_norm_eps": 1e-05,
    "rope_theta": 1000000.0, "sliding_window": None,
    "tie_word_embeddings": False, "vocab_size": 512, "eos_token_id": 2,
    "reduced": [], "assumed": {}, "withheld": {"eos_token_id": "no EOS"},
    "weight_qtype": "sym_int4", "chips": 1, "tensor_parallel_size": 1,
    "engine": {"max_rows": 2, "max_seq_len": 256, "page_size": 16},
}
# a second FAMILY, whose tree the dense generator cannot express (a router and
# [layers, experts, ...] stacks): its generator and its plain reference are
# tests/families/{generators,references}/mixtral.py, found by model_type
FAMILIES_DIR = os.path.join(HERE, "families")
TINY_MOE = dict(
    TINY, model_type="mixtral", num_local_experts=4, num_experts_per_tok=2,
    reduced=["num_local_experts"],
    deployment={"chips_sharing_a_layer": 2, "how": "experts",
                "published": {"num_local_experts": 8}})
TINY_TRAFFIC = {
    "why": "test only", "loop": "closed", "clients": 2,
    "prompt_len": {"dist": "uniform", "min": 8, "max": 28},
    "output_len": {"dist": "uniform", "min": 4, "max": 8},
    "pool": 64, "ramp_s": 1, "drain_s": 60,
    "warmup": {"p_b": [1, 2], "longest_prompt": 28, "replay_s": 2,
               "max_rounds": 4},
    "trace": {"start_s": 0.5, "seconds": 1},
}


@pytest.fixture(scope="module")
def tiny_root(tmp_path_factory):
    """A copy of the checkout's benchmark data plus two configurations (one
    of a family the benchmark does not have), one traffic mix, a cell for
    each and one per-layer metric over an existing reader kind: new files
    and new BENCHMARK.json entries, no file edited."""
    root = str(tmp_path_factory.mktemp("root"))
    data = os.path.join(root, "perfbench")
    for sub in ("configs", "traffic", "layer_metrics"):
        shutil.copytree(os.path.join(BENCH, sub), os.path.join(data, sub))
    bench = bench_json()
    for name, config in (("tiny", TINY), ("tinymoe", TINY_MOE)):
        with open(os.path.join(data, "configs", name + ".json"), "w") as f:
            json.dump(config, f)
        bench["configs"].append({
            "name": name, "source": "test only", "why": "test only",
            "file": f"perfbench/configs/{name}.json",
            "reduced": config["reduced"]})
        bench["workloads"].append({
            "name": name + ".closed", "config": name,
            "traffic": "tiny-closed", "chips": 1, "why": "test only"})
    with open(os.path.join(data, "traffic", "tiny-closed.json"), "w") as f:
        json.dump(TINY_TRAFFIC, f)
    with open(os.path.join(data, "layer_metrics", "queue_rows.json"), "w") as f:
        json.dump({"reader": {"kind": "counter_ratio", "num": "requests",
                              "den": "ticks"}}, f)
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "workloads" in m:
            m["workloads"] += ["tiny.closed", "tinymoe.closed"]
    bench["per_layer"].append({
        "name": "queue_rows", "unit": "rows", "better": "higher",
        "source": "program_counter", "layer": "scheduler / tick transaction",
        "moves": "output_tok_s",
        "workloads": ["tiny.closed", "tinymoe.closed"]})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    # the second family's modules: on the path the harness searches
    sys.path.insert(0, FAMILIES_DIR)
    yield root
    sys.path.remove(FAMILIES_DIR)


@pytest.mark.parametrize("workload,trace", [
    ("tiny.closed", 0), ("tiny.closed", 1), ("tinymoe.closed", 0)])
def test_whole_run_at_a_tiny_size(tiny_root, workload, trace, capfd):
    """run.py end to end on the CPU (the first rehearsal): server, probes
    against the reference, warm-up to convergence, window, result line."""
    cell = run.resolve_cell(tiny_root, workload)
    assert any(m["name"] == "queue_rows" for m in cell["per_layer"])
    assert "deployment" not in run.hf_config(cell["config"])
    device = run.device_info()
    assert device["platform"] == "cpu"
    try:
        result = run.run_cell(tiny_root, cell, 2 ** 31 + 5, 4.0, bool(trace),
                              device)
    except run.BenchFailure as e:
        # a CPU trace has no device plane: the traced run must refuse
        assert trace == 1 and "no device operation" in str(e)
        return
    assert trace == 0
    assert set(result) == CONTRACT_KEYS
    assert result["correct"] is True
    assert result["attempted"] > 0 and result["failed"] == 0
    names = {m["name"] for m in cell["end_to_end"]}
    assert set(result["metrics"]) == names
    assert all(set(v) == {"value", "unit"} and v["value"] > 0
               for v in result["metrics"].values())
    assert json.loads(json.dumps(result)) == result
