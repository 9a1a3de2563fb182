"""The benchmark's own pair for ``model_type: nemotron_h``, in tier-1: the
configuration's file at the generator's ``tiny`` size is drawn with
perfbench/generators/nemotron_h.py, served through ``ServingEngine`` and held
to perfbench/references/nemotron_h.py's ``compare``, as perfbench/run.py's
``correctness`` does on the chip at the published widths.  Beside it: what
the configuration's file and the cell's entries must state."""

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

CONFIG = "nemotron3-nano-30b-a3b.int4.ep2"
CELL = "nemotron3nano.decode-saturated"
PUBLISHED = "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME"


def config() -> dict:
    return run.load_json(os.path.join(BENCH, "configs", CONFIG + ".json"))


def test_the_configuration_states_its_cut():
    c = config()
    assert c["hybrid_override_pattern"] == PUBLISHED
    assert c["num_hidden_layers"] == 52 == len(PUBLISHED)
    # every published width unchanged
    for key, value in {"hidden_size": 2688, "mamba_num_heads": 64,
                       "mamba_head_dim": 64, "n_groups": 8,
                       "ssm_state_size": 128, "moe_intermediate_size": 1856,
                       "moe_shared_expert_intermediate_size": 3712,
                       "num_experts_per_tok": 6, "head_dim": 128,
                       "num_attention_heads": 32,
                       "num_key_value_heads": 2}.items():
        assert c[key] == value, key
    assert sorted(c["reduced"]) == ["n_routed_experts", "vocab_size"]
    assert (c["n_routed_experts"], c["vocab_size"]) == (64, 65536)
    assert c["deployment"]["chips_sharing_a_layer"] == 2
    assert c["deployment"]["published"] == {"n_routed_experts": 128,
                                            "vocab_size": 131072}
    assert c["n_router_experts"] == 128 and c["first_routed_expert"] == 0
    assert {"n_router_experts", "first_routed_expert",
            "attention_rotary"} <= set(c["assumed"])
    assert c["engine"] == {"max_rows": 32, "max_seq_len": 4096,
                           "page_size": 128, "pool_pages": 512}
    hf = run.hf_config(c)
    assert "deployment" not in hf and hf["n_router_experts"] == 128
    from ipex_llm_tpu.models.families import FAMILIES

    cfg = FAMILIES["nemotron_h"].to_config(hf)
    assert (cfg.ssm_layers, cfg.kv_layers) == (23, 6)
    assert (cfg.num_experts, cfg.moe_experts_held) == (128, 64)


def test_the_cell_resolves_with_its_metrics():
    cell = run.resolve_cell(ROOT, CELL)
    assert cell["cell"]["chips"] == 1
    assert cell["cell"]["traffic"] == "decode-saturated-32"
    assert cell["traffic"]["clients"] == 32 == cell["config"]["engine"][
        "max_rows"]
    assert cell["traffic"]["warmup"]["p_b"][-1] == 32
    # `output_tok_s` is NOT carried: the cohort of 32 stays in phase and the
    # 40 s window holds 1.5 of its periods (PERF.md section 7); with it go
    # the per-layer metrics that move it
    assert {m["name"] for m in cell["end_to_end"]} == {
        "itl_p95_ms", "setup_s"}
    moves = {m["name"]: m["moves"] for m in cell["per_layer"]}
    assert {"ssm_share", "moe_experts_share", "moe_shared_share",
            "moe_expert_roofline", "ssm_state_roofline",
            "moe_local_pair_share", "moe_tokens_per_hit_expert",
            "steady_tick_device_ms", "mixed_tick_device_ms",
            "mixed_tick_share", "attn_kernel_share",
            "compiles_in_window"} <= set(moves)
    assert "qmatmul_roofline" not in moves
    assert "output_tok_s" not in moves.values()
    # the traced seconds lie in the churn after the first completions, where
    # steady and mixed ticks alternate: both kinds are read
    assert cell["traffic"]["trace"]["start_s"] >= 20
    for m in cell["per_layer"]:
        assert os.path.exists(os.path.join(
            BENCH, "readers", m["reader"]["kind"] + ".py")), m["name"]
    bench = run.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    assert [w["name"] for w in bench["workloads"]].count(CELL) == 1
    assert len(json.dumps(bench)) < 64 * 1024


def test_the_new_readers_read_a_scoped_trace_and_give_nothing_otherwise():
    import shapes_nemotron_h
    from readers import moe_expert_roofline, scope_share, ssm_state_roofline

    hf = run.hf_config(config())
    # 2688 x 1856 twice: codes and one fp16 scale a block of 32
    assert shapes_nemotron_h.expert_bytes(hf) == 2 * (
        2688 * 1856 / 2 + 2688 * 1856 / 32 * 2)
    row = shapes_nemotron_h.state_row_bytes(hf)
    assert row == 64 * 64 * 128 * 4 + 3 * 6144 * 2
    assert shapes_nemotron_h.state_tick_bytes(hf, 32) == 2 * 32 * 23 * row
    ops = [["%while.1 = (s32[]) while(...)", 0.0, 1.0,
            "jit(_ragged_tick)/tick/decode/while", "x"],
           ["%fusion.2 = f32[64,32] fusion(...)", 0.125, 0.125,
            "jit(_ragged_tick)/tick/decode/while/body/layer/mlp/moe/experts/"
            "mul", "x"],
           ["%qmatmul_expert.3 = f32[64,32,1920] custom-call(...)", 0.25, 0.375,
            "jit(_ragged_tick)/tick/decode/while/body/layer/mlp/moe/experts/"
            "pallas_call", "x"],
           ["%fusion.4 = f32[32,64] fusion(...)", 0.75, 0.125,
            "jit(_ragged_tick)/tick/decode/while/body/layer/ssm/ssm/step/mul",
            "x"]]
    parsed = {"ops": ops, "spans": [], "modules": []}
    hit, total, n = scope_share.scoped_seconds(parsed, "moe/experts")
    assert (round(hit, 6), round(total, 6), n) == (0.5, 1.0, 2)
    assert scope_share.scoped_seconds(parsed, "layer/ssm")[0] == 0.125
    assert scope_share.scoped_seconds(parsed, "layer/attn")[2] == 0
    # no trace: nothing, and no raise (the parent's program, a CPU run)
    ctx = {"trace": None, "record": {"counters": {
        "start": {"metrics": {}}, "end": {"metrics": {}}}}}
    for reader, params in ((scope_share, {"scope": "layer/ssm"}),
                           (moe_expert_roofline, {"scope": "moe/experts",
                                                  "kernel": "qmatmul_expert",
                                                  "calls": 2}),
                           (ssm_state_roofline, {"scope": "ssm/step"})):
        assert reader.read(params, ctx) is None


def test_tiny_of_the_configuration_served_and_held_to_the_reference():
    """run.correctness's pair at a size the CPU holds: two probes through
    ``ServingEngine``, each against the reference's full forward over prompt
    + emitted tokens, by the reference's own ``compare``; sent again beside
    another request, a probe gives the same tokens."""
    import weights
    from ipex_llm_tpu.models.families import FAMILIES
    from ipex_llm_tpu.serving.engine import (EngineConfig, Request,
                                             ServingEngine, stream_tokens)

    gen = weights.generator("nemotron_h")
    reference = importlib.import_module("references.nemotron_h")
    hf = gen.tiny(run.hf_config(config()))
    assert hf["model_type"] == "nemotron_h" and hf["n_router_experts"] == 8
    cfg = FAMILIES["nemotron_h"].to_config(hf)
    seed = 2 ** 31 + 77
    params = gen.param_tree(cfg, hf, "sym_int4", seed)
    again = gen.param_tree(cfg, hf, "sym_int4", seed)
    assert np.array_equal(params["hybrid"]["moe"]["moe_down"].data,
                          again["hybrid"]["moe"]["moe_down"].data)
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
        assert len(res["errors"]) == len(res["gaps"]) == len(toks)
    # the comparison fails what it must: another token's log-probabilities
    wrong = reference.compare(got, len(ids), toks, [lp - 2.0 for lp in lps])
    assert not wrong["ok"]
