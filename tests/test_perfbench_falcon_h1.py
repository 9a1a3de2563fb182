"""The benchmark's own files for ``model_type: falcon_h1``, in tier-1: what the
configuration's file and the cell's entries must state; the generator, the
reference and the configuration agreeing at the generator's ``tiny`` size
through run.py's own comparison (``correctness``'s pair); a depth-matched copy
telling rounding from a lower precision; the state's bytes against
``eval_shape``; the new reader on a planted trace, and nothing (without
raising) where there is none.  The served path's own tests are
tests/test_falcon_h1.py."""

from __future__ import annotations

import importlib
import json
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "perfbench")
for path in (BENCH, ROOT):
    if path not in sys.path:
        sys.path.insert(0, path)

import run  # noqa: E402
import trafficgen  # noqa: E402

CONFIG = "falcon-h1-34b-instruct.int4.l24"
CELL = "falconh1.reasoning-decode"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
NEW_METRICS = ("h1_ssm_state_roofline", "head_share", "mlp_share")
LISTED = ("steady_tick_device_ms", "mixed_tick_share", "ssm_share",
          "attn_roofline", "kv_pool_move_share", "unscoped_device_share",
          "weight_slice_share", "itl_p90_ms", "itl_p98_ms", "h2d_per_tick",
          "host_wait_ms_per_tick", "host_cpu_ms_per_tick",
          "sync_fetch_ms_per_tick", "d2h_per_tick", "stream_wake_mean_ms",
          "stream_deliver_mean_ms", "stream_cpu_us_per_event",
          "idle_stream_overlap_share", "sample_share", "sample_draw_share",
          "scale_plane_pass_share", "ahead_tick_share",
          "fused_mixed_tick_share")


def config() -> dict:
    return run.load_json(os.path.join(BENCH, "configs", CONFIG + ".json"))


def test_the_configuration_states_its_cut():
    c = config()
    for key, value in {"hidden_size": 5120, "intermediate_size": 21504,
                       "num_attention_heads": 20, "num_key_value_heads": 4,
                       "head_dim": 128, "mamba_n_heads": 32,
                       "mamba_d_head": 128, "mamba_d_ssm": 4096,
                       "mamba_d_state": 256, "mamba_n_groups": 2,
                       "mamba_d_conv": 4, "mamba_expand": 2,
                       "vocab_size": 261120, "rope_theta": 100000000000,
                       "max_position_embeddings": 262144,
                       "key_multiplier": 0.011048543456039804,
                       "lm_head_multiplier": 0.0078125,
                       "ssm_in_multiplier": 0.25}.items():
        assert c[key] == value, key
    assert c["reduced"] == ["num_hidden_layers"]
    assert c["num_hidden_layers"] == 24
    dep = c["deployment"]
    assert dep["chips_sharing_a_layer"] == 1
    assert dep["published"] == {"num_hidden_layers": 72}
    assert dep["how"].startswith("depth only: blocks 0..23 of 72")
    assert {"ssm_state_dtype", "ssm_conv_tail_dtype"} <= set(c["assumed"])
    assert set(c["withheld"]) == {"mamba_chunk_size", "num_logits_to_keep"}
    assert c["engine"] == {"max_rows": 16, "max_seq_len": 2176,
                           "page_size": 128, "pool_pages": 288,
                           "step_token_budget": 512, "prefill_bucket": 512}
    assert c["chips"] == 1 and c["weight_qtype"] == "sym_int4"
    hf = run.hf_config(c)
    assert "deployment" not in hf and "mamba_chunk_size" not in hf
    assert len(hf["ssm_multipliers"]) == 5 and len(hf["mlp_multipliers"]) == 2
    first = c["rehearsal"]["compiled"][0]
    assert first["state_buffers"] == 2 and first["mosaic_kernels"] > 0
    assert "ssm_scan" in first["dispatch"]
    # over 12.5 GB resident, and inside the chip's 15.75 GiB
    assert 12.5e9 < first["resident_bytes"] < 15.75 * 2 ** 30


@pytest.mark.skipif(not os.path.exists(CATALOG), reason="no catalog here")
def test_every_published_number_is_the_catalogs_or_listed_as_reduced():
    with open(CATALOG) as f:
        entry = next(e for e in map(json.loads, f)
                     if e["name"] == "Falcon-H1-34B-Instruct")
    c = config()
    assert c["source"] == entry["source_url"]
    for key, value in entry["config"].items():
        if key in c["reduced"]:
            assert c[key] != value, key
        else:
            assert c[key] == value, key


def test_the_bytes_the_file_states_are_the_trees():
    """The weights' bytes from ``jax.eval_shape`` of the generator's tree,
    the page's from the program's own geometry and the state's from
    ``kv.recurrent_state``, as the file's notes give them; and the
    yardstick's state bytes (shapes_falcon_h1, from the config's keys alone)
    against the same ``eval_shape``."""
    import jax

    import shapes_falcon_h1
    import weights
    from ipex_llm_tpu import kv
    from ipex_llm_tpu.models.families import FAMILIES

    c = config()
    hf = run.hf_config(c)
    cfg = FAMILIES["falcon_h1"].to_config(hf)
    gen = weights.generator("falcon_h1")
    tree = jax.eval_shape(lambda: gen.param_tree(cfg, hf, "sym_int4", 0))

    def n_bytes(t):
        return sum(x.size * x.dtype.itemsize
                   for x in jax.tree_util.tree_leaves(t))

    text = " ".join(c["notes"])
    assert f"{n_bytes(tree):,} B" in text
    assert f"{n_bytes(tree['hybrid']['parallel']):,} B" in text
    assert f"{n_bytes(tree['lm_head']):,} B" in text
    assert tree["embed"].shape == (261120, 5120)
    assert tree["hybrid"]["parallel"]["in_proj"].shape == (5120, 9248)
    assert tree["hybrid"]["parallel"]["in_proj"].data.shape == (24, 2560, 9344)
    page = kv.model_page_bytes(cfg, 128)
    assert page == 6291456 and f"{page:,} B a page" in text
    assert f"{288 * page:,} B" in text
    state = jax.eval_shape(lambda: kv.recurrent_state(cfg, 16))
    assert f"{n_bytes(state):,} B" in text
    assert f"{2 * n_bytes(state):,} B" in text
    row = shapes_falcon_h1.state_row_bytes(hf)
    assert row == 4225024 and f"{int(row):,} B" in text
    assert 16 * 24 * row == n_bytes(state) - state["stats"].size * 4
    assert shapes_falcon_h1.state_blocks(hf) == cfg.ssm_layers == 24
    assert shapes_falcon_h1.state_tick_bytes(hf, 16) == 2 * 16 * 24 * row
    # what the engine holds: the tree, the pool, the state twice
    resident = n_bytes(tree) + 288 * page + 2 * n_bytes(state)
    assert 14.2e9 < resident < 14.4e9


def test_the_cell_resolves_with_its_metrics():
    cell = run.resolve_cell(ROOT, CELL)
    assert cell["cell"]["chips"] == 1
    assert cell["cell"]["traffic"] == "reasoning-16"
    t = cell["traffic"]
    assert t["loop"] == "closed"
    assert t["clients"] == cell["config"]["engine"]["max_rows"] == 16
    eng = cell["config"]["engine"]
    assert 512 + 1536 <= eng["max_seq_len"]
    assert t["clients"] * -(-(512 + 1536) // eng["page_size"]) <= eng[
        "pool_pages"]
    reqs = trafficgen.schedule(t, 2 ** 31 + 9, 40, t["ramp_s"])["requests"]
    assert all(128 <= r["prompt_len"] <= 512
               and 768 <= r["max_tokens"] <= 1536 for r in reqs)
    assert {m["name"] for m in cell["end_to_end"]} == {"itl_p95_ms",
                                                       "setup_s"}
    moves = {m["name"]: m["moves"] for m in cell["per_layer"]}
    assert {*NEW_METRICS, *LISTED, "gen_late_p95_ms", "prefill_tok_per_tick",
            "compiles_in_window", "warmup_s",
            "attn_kernel_share"} == set(moves)
    assert set(moves.values()) == {"itl_p95_ms", "setup_s"}
    for m in cell["per_layer"]:
        assert os.path.exists(os.path.join(
            BENCH, "readers", m["reader"]["kind"] + ".py")), m["name"]
    bench = run.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    assert [w["name"] for w in bench["workloads"]].count(CELL) == 1
    assert [w["config"] for w in bench["workloads"]].count(CONFIG) == 1
    entry = next(c for c in bench["configs"] if c["name"] == CONFIG)
    assert entry["reduced"] == ["num_hidden_layers"]
    assert entry["file"] == f"perfbench/configs/{CONFIG}.json"
    for m in bench["per_layer"]:
        if m["name"] in NEW_METRICS:
            assert m["workloads"] == [CELL] and m["moves"] == "itl_p95_ms"
            assert m["unit"] == "%"
    # its list stays as it is until closed-loop clients are staggered
    out = next(m for m in bench["end_to_end"] if m["name"] == "output_tok_s")
    assert out["workloads"] == ["mistral7b.decode-saturated"]
    # the other state-space cell's reader keeps its own cell
    old = next(m for m in bench["per_layer"]
               if m["name"] == "ssm_state_roofline")
    assert old["workloads"] == ["nemotron3nano.decode-saturated"]
    assert all(len(w["why"]) <= 200 for w in bench["workloads"])
    assert len(json.dumps(bench)) < 64 * 1024


def test_the_traffic_file_is_the_one_the_other_cell_runs():
    """Reused UNEDITED: the bytes PR 44 left."""
    import hashlib

    with open(os.path.join(BENCH, "traffic", "reasoning-16.json"), "rb") as f:
        digest = hashlib.sha256(f.read()).hexdigest()
    assert digest == TRAFFIC_SHA256


TRAFFIC_SHA256 = (
    "09c634ca50e5f1126a065b9be3edfe319452be266f894e624aac9b4f7f7ebe9c")


# -- the new reader ----------------------------------------------------------------

def _planted() -> dict:
    """Three traced ticks: a mixed one, a steady one with the state step
    inside, and a steady one whose program the capture cut."""
    pre = "jit(_ragged_tick)/tick/"
    body = "while/body/layer/"
    spans = [
        ["tick", 0.0, 0.1, {"tick": 1}],
        ["dispatch", 0.001, 0.002, {
            "variant": "mixed", "family": "tick.admission", "rows_active": 15,
            "attn_pages_live": 150}],
        ["sync", 0.002, 0.09, {}],
        ["tick", 0.1, 0.2, {"tick": 2}],
        ["dispatch", 0.101, 0.102, {
            "variant": "steady", "family": "tick.steady", "rows_active": 16,
            "attn_pages_live": 160}],
        ["sync", 0.102, 0.19, {}],
        ["tick", 0.2, 0.3, {"tick": 3}],
        ["dispatch", 0.201, 0.202, {
            "variant": "steady", "family": "tick.steady", "rows_active": 16,
            "attn_pages_live": 160}],
        ["sync", 0.202, 0.29, {}]]
    ops = [
        ["%ssm_scan.1 = f32[24,16,32,128,256] custom-call(...)", 0.010, 0.02,
         pre + "prefill/" + body + "ssm/ssm/scan/pallas_call", "custom-call"],
        ["%ssm_scan.2 = f32[24,16,32,128,256] custom-call(...)", 0.110, 0.008,
         pre + "decode/" + body + "ssm/ssm/step/pallas_call", "custom-call"],
        ["%fusion.3 = f32[16,1,32,256] fusion(...)", 0.120, 0.002,
         pre + "decode/" + body + "ssm/ssm/step/mul", "fusion"],
        ["%qmatmul.4 = f32[16,5120] custom-call(...)", 0.130, 0.012,
         pre + "decode/" + body + "mlp/pallas_call", "custom-call"],
        ["%qmatmul.5 = f32[16,261120] custom-call(...)", 0.150, 0.003,
         pre + "decode/head/pallas_call", "custom-call"]]
    return {"spans": spans, "ops": ops, "modules": []}


def _ctx(parsed, monkeypatch, cfg=None) -> dict:
    import xplane_spans

    monkeypatch.setattr(xplane_spans, "load", lambda ctx: parsed)
    c = cfg or config()
    return {"trace": {"modules": []}, "config": c,
            "hf_config": run.hf_config(c),
            "device": {"kind": "TPU v5 lite"},
            "peaks": run.load_json(os.path.join(BENCH, "peaks.json")),
            "record": {"counters": {"start": {"metrics": {}},
                                    "end": {"metrics": {}}}}}


def _reader(name: str) -> dict:
    return run.load_json(os.path.join(BENCH, "layer_metrics",
                                      name + ".json"))["reader"]


def test_the_three_new_metrics_on_a_planted_trace(monkeypatch):
    from readers import h1_ssm_state_roofline, scope_share

    parsed = _planted()
    ctx = _ctx(parsed, monkeypatch)
    got = h1_ssm_state_roofline.read(_reader("h1_ssm_state_roofline"), ctx)
    # the one steady tick with its step inside: 16 rows x 24 blocks x
    # 4,225,024 B, read and written, at 819 GB/s, over 10 ms in ssm/step
    least = 2 * 16 * 24 * 4225024 / 819e9
    assert got == pytest.approx(100 * least / 0.010, rel=1e-6)
    assert ctx["notes"]["h1_ssm_state_roofline"]["ticks"] == 1
    assert got < 100
    busy = 0.02 + 0.008 + 0.002 + 0.012 + 0.003
    assert scope_share.read(_reader("mlp_share"), ctx) == pytest.approx(
        100 * 0.012 / busy)
    assert scope_share.read(_reader("head_share"), ctx) == pytest.approx(
        100 * 0.003 / busy)
    assert scope_share.read(_reader("ssm_share"), ctx) == pytest.approx(
        100 * 0.030 / busy)
    # another family's configuration, the parent's program, a CPU run:
    # nothing, and no raise
    nemotron = run.load_json(os.path.join(
        BENCH, "configs", "nemotron3-nano-30b-a3b.int4.ep2.json"))
    assert h1_ssm_state_roofline.read(
        _reader("h1_ssm_state_roofline"),
        _ctx(parsed, monkeypatch, nemotron)) is None
    bare = dict(parsed, ops=[o for o in parsed["ops"] if "ssm" not in o[3]])
    assert h1_ssm_state_roofline.read(
        _reader("h1_ssm_state_roofline"), _ctx(bare, monkeypatch)) is None
    assert scope_share.read(_reader("ssm_share"),
                            _ctx(bare, monkeypatch)) is None
    none = dict(_ctx(None, monkeypatch), trace=None)
    for name in NEW_METRICS:
        reader = importlib.import_module(
            "readers." + _reader(name)["kind"])
        assert reader.read(_reader(name), none) is None


# -- generator, reference and configuration at a size the CPU holds ------------------

def test_tiny_of_the_configuration_served_and_held_to_the_reference():
    """run.correctness's pair at a size the CPU holds: two probes through
    ``ServingEngine``, each against the reference's full forward over prompt
    + emitted tokens, by the reference's own ``compare``; sent again beside
    another request, a probe gives the same tokens."""
    import weights
    from ipex_llm_tpu.models.families import FAMILIES
    from ipex_llm_tpu.serving.engine import (EngineConfig, Request,
                                             ServingEngine, stream_tokens)

    gen = weights.generator("falcon_h1")
    reference = importlib.import_module("references.falcon_h1")
    hf = gen.tiny(run.hf_config(config()))
    assert hf["model_type"] == "falcon_h1" and hf["mamba_d_state"] == 256
    cfg = FAMILIES["falcon_h1"].to_config(hf)
    seed = 2 ** 31 + 77
    params = gen.param_tree(cfg, hf, "sym_int4", seed)
    again = gen.param_tree(cfg, hf, "sym_int4", seed)
    assert np.array_equal(params["hybrid"]["parallel"]["in_proj"].data,
                          again["hybrid"]["parallel"]["in_proj"].data)
    with pytest.raises(ValueError, match="sym_int4"):
        gen.param_tree(cfg, hf, "nf4", seed)
    probes = [trafficgen.prompt_ids(seed, 10 ** 6 + i, 60, hf["vocab_size"])
              for i in range(2)]
    eng = ServingEngine(cfg, params, EngineConfig(
        max_rows=4, max_seq_len=256, page_size=16, prefill_bucket=32)).start()

    def send(ids, n=run.PROBE_NEW_TOKENS):
        return eng.submit(Request(prompt_ids=ids, max_new_tokens=n,
                                  temperature=0.0))

    try:
        alone = []
        for ids in probes:
            req = send(ids)
            toks = list(stream_tokens(req, timeout=300))
            alone.append((toks, list(req.logprobs)))
        beside = send(trafficgen.prompt_ids(seed, 5, 90, hf["vocab_size"]), 24)
        under_load = send(probes[0])
        loaded = list(stream_tokens(under_load, timeout=300))
        list(stream_tokens(beside, timeout=300))
    finally:
        eng.stop()
    assert loaded == alone[0][0]
    for ids, (toks, lps) in zip(probes, alone):
        got = reference.logprobs(hf, params, ids + toks[:-1])
        res = reference.compare(got, len(ids), toks, lps)
        assert res["ok"], res
        assert len(res["errors"]) == len(toks)
    # the comparison fails what it must: another token's log-probabilities
    wrong = reference.compare(got, len(ids), toks, [lp - 2.0 for lp in lps])
    assert not wrong["ok"]


def test_a_depth_matched_copy_tells_rounding_from_a_lower_precision():
    """The tiny model at the CELL's depth (24 blocks), the reference against
    itself over 127 positions, teacher-forced on its own greedy tokens: the
    configuration's own precision (bf16 activations, float32 state) moves a
    log-probability by a hundredth or two and passes; fp8 ACTIVATIONS, one
    precision down, an order more and fail ``compare``; an fp8 STATE several
    times more and fails too.  A bfloat16 state is NOT told from float32 by
    any of this (one more rounding of 2**-9 among the dozen a block makes:
    its reading is the bf16 reading): it is held in the program (float32
    state buffers, tests/test_falcon_h1.py) and on the recurrence itself
    (``recurrence``: the same inputs, float32 against bfloat16 state)."""
    import weights
    from ipex_llm_tpu.models.families import FAMILIES

    gen = weights.generator("falcon_h1")
    reference = importlib.import_module("references.falcon_h1")
    hf = dict(gen.tiny(run.hf_config(config())), num_hidden_layers=24)
    cfg = FAMILIES["falcon_h1"].to_config(hf)
    params = gen.param_tree(cfg, hf, "sym_int4", 1)
    ids = trafficgen.prompt_ids(1, 10 ** 6, run.PROBE_TOKENS + 7,
                                hf["vocab_size"])
    exact = reference.logprobs(hf, params, ids)
    tokens = [int(t) for t in exact.argmax(-1)]
    # the drawn model is no flat distribution: a greedy token a few nats up
    assert -4.0 < float(exact.max(-1).mean()) < -0.5
    mean = {}
    for lower in ("bf16", "fp8", "state_bf16", "state_fp8"):
        rows = reference.logprobs(hf, params, ids, lower=lower)
        res = reference.compare(exact, 1, tokens,
                                [float(rows[j][t])
                                 for j, t in enumerate(tokens)])
        mean[lower] = res["logprob_err_mean"]
        assert res["ok"] == (lower in ("bf16", "state_bf16")), (lower, res)
    assert 0.003 < mean["bf16"] < 0.03
    assert mean["fp8"] > 8 * mean["bf16"]
    assert mean["state_fp8"] > 3 * mean["bf16"]
    assert mean["state_bf16"] < 1.5 * mean["bf16"]
