"""The benchmark's own files for ``model_type: xing4_0``, in tier-1: what the
configuration's file and the cell's entries must state, that the three new
metrics read a planted trace and give nothing (without raising) where there is
none.  The served path against the reference is tests/test_xing4.py."""

from __future__ import annotations

import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "perfbench")
for path in (BENCH, ROOT):
    if path not in sys.path:
        sys.path.insert(0, path)

import run  # noqa: E402
import trafficgen  # noqa: E402

CONFIG = "xing4.0-29b-a4b.int4.ep2"
CELL = "xing4.reasoning-decode"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
REDUCED = ["n_routed_experts", "vocab_size"]
NEW_METRICS = ("mhc_share", "mhc_coef_share", "mhc_mix_roofline")


def config() -> dict:
    return run.load_json(os.path.join(BENCH, "configs", CONFIG + ".json"))


def test_the_configuration_states_its_cut():
    c = config()
    for key, value in {"hidden_size": 3584, "intermediate_size": 9216,
                       "num_hidden_layers": 40, "first_k_dense_replace": 2,
                       "num_attention_heads": 32, "q_lora_rank": 768,
                       "kv_lora_rank": 512, "qk_nope_head_dim": 128,
                       "qk_rope_head_dim": 64, "v_head_dim": 128,
                       "moe_intermediate_size": 1024,
                       "num_experts_per_tok": 4, "n_group": 1,
                       "topk_group": 1, "n_shared_experts": 1,
                       "routed_scaling_factor": 2, "topk_method": "noaux_tc",
                       "hc_mult": 4, "hc_sinkhorn_iters": 20, "hc_eps": 1e-6,
                       "mhc_h_res_clamp_min": -30, "mhc_h_res_clamp_max": 30,
                       "max_position_embeddings": 262144}.items():
        assert c[key] == value, key
    assert c["rope_scaling"]["factor"] == 64
    assert sorted(c["reduced"]) == REDUCED
    assert (c["n_routed_experts"], c["vocab_size"]) == (32, 65536)
    dep = c["deployment"]
    assert dep["chips_sharing_a_layer"] == 2
    assert dep["published"] == {"n_routed_experts": 64, "vocab_size": 131072}
    assert "1 token a decode tick" in dep["how"] and "int4 ridge" in dep["how"]
    assert c["n_router_experts"] == 64 and c["first_routed_expert"] == 0
    assert {"n_router_experts", "first_routed_expert",
            "hc_streams_in_and_out", "hc_sinkhorn_order_and_eps",
            "hc_tensors", "yarn_scale"} <= set(c["assumed"])
    assert "arXiv:2409.19606" in c["assumed"]["hc_streams_in_and_out"]
    assert "arXiv:2512.24880" in c["assumed"]["hc_sinkhorn_order_and_eps"]
    assert set(c["withheld"]) == {"num_nextn_predict_layers"}
    assert c["engine"] == {"max_rows": 16, "max_seq_len": 2176,
                           "page_size": 128, "pool_pages": 384,
                           "step_token_budget": 512, "prefill_bucket": 512}
    assert c["chips"] == 1 and c["weight_qtype"] == "sym_int4"
    hf = run.hf_config(c)
    assert "deployment" not in hf and "num_nextn_predict_layers" not in hf
    assert hf["hc_mult"] == 4
    first = c["rehearsal"]["compiled"][0]
    assert first["mosaic_kernels"] > 0
    # over a quarter of the chip's 16 GB, and inside it
    assert 4 * 10 ** 9 < first["resident_bytes"] < 15 * 10 ** 9


@pytest.mark.skipif(not os.path.exists(CATALOG), reason="no catalog here")
def test_every_published_number_is_the_catalogs_or_listed_as_reduced():
    with open(CATALOG) as f:
        entry = next(e for e in map(json.loads, f)
                     if e["name"] == "Xing4.0-29B-A4B")
    c = config()
    assert c["source"] == entry["source_url"]
    for key, value in entry["config"].items():
        if key in c["reduced"]:
            assert c[key] != value, key
        else:
            assert c[key] == value, key
    assert "num_nextn_predict_layers" in c            # kept, and withheld


def test_the_bytes_the_file_states_are_the_trees():
    """The weights' bytes from ``jax.eval_shape`` of the generator's tree and
    the page's bytes from the program's own geometry, as the file's notes give
    them; the hyper-connections' tensors float32, outside the packed
    planes."""
    import jax
    import jax.numpy as jnp

    import weights
    from ipex_llm_tpu import kv
    from ipex_llm_tpu.models.families import FAMILIES

    c = config()
    hf = run.hf_config(c)
    cfg = FAMILIES["xing4_0"].to_config(hf)
    gen = weights.generator("xing4_0")
    tree = jax.eval_shape(lambda: gen.param_tree(cfg, hf, "sym_int4", 0))
    n_bytes = sum(x.size * x.dtype.itemsize
                  for x in jax.tree_util.tree_leaves(tree))
    text = " ".join(c["notes"])
    assert f"{n_bytes:,} B" in text
    page = kv.model_page_bytes(cfg, 128)
    assert page == 5898240 and f"{page:,} B a page" in text
    assert f"{384 * page:,} B" in text
    for stack, n in (("layers_dense", 2), ("layers", 38)):
        for sub in ("attn", "mlp"):
            proj = tree[stack][f"hc_{sub}_proj"]
            assert proj.shape == (n, 24, 4 * 3584)
            assert proj.dtype == jnp.float32
            assert tree[stack][f"hc_{sub}_scale"].shape == (n, 3)
            assert tree[stack][f"hc_{sub}_bias"].shape == (n, 24)
    assert tree["layers"]["moe_gate_up"].data.shape[:2] == (38, 32)
    assert tree["layers"]["router"].shape == (38, 3584, 64)
    assert tree["embed"].shape == (65536, 3584)


def test_the_cell_resolves_with_its_metrics():
    cell = run.resolve_cell(ROOT, CELL)
    assert cell["cell"]["chips"] == 1
    assert cell["cell"]["traffic"] == "reasoning-16"
    t = cell["traffic"]
    assert t["loop"] == "closed"
    assert t["clients"] == cell["config"]["engine"]["max_rows"] == 16
    assert (t["prompt_len"], t["output_len"]) == (
        {"dist": "uniform", "min": 128, "max": 512},
        {"dist": "uniform", "min": 768, "max": 1536})
    assert 30 <= t["ramp_s"] <= 90 and t["drain_s"] == 80
    assert t["warmup"]["p_b"] == [1, 2, 4, 8, 16]
    assert t["warmup"]["longest_prompt"] == 512
    assert "order" not in t
    # the longest request fits a row, and the traffic's most fits the pool
    eng = cell["config"]["engine"]
    assert 512 + 1536 <= eng["max_seq_len"]
    assert t["clients"] * -(-(512 + 1536) // eng["page_size"]) <= eng[
        "pool_pages"]
    reqs = trafficgen.schedule(t, 2 ** 31 + 9, 40, t["ramp_s"])["requests"]
    assert len(reqs) == t["pool"] >= 64
    assert all(128 <= r["prompt_len"] <= 512
               and 768 <= r["max_tokens"] <= 1536 for r in reqs)
    assert {m["name"] for m in cell["end_to_end"]} == {"itl_p95_ms",
                                                       "setup_s"}
    moves = {m["name"]: m["moves"] for m in cell["per_layer"]}
    assert {*NEW_METRICS, "steady_tick_device_ms", "mixed_tick_share",
            "moe_experts_share", "moe_shared_share", "moe_local_pair_share",
            "moe_tokens_per_hit_expert", "moe_row_fill_share",
            "moe_gated_expert_roofline", "mla_attn_roofline",
            "mla_absorb_share", "weight_slice_share", "h2d_per_tick",
            "host_wait_ms_per_tick", "host_cpu_ms_per_tick",
            "sync_fetch_ms_per_tick", "d2h_per_tick", "stream_wake_mean_ms",
            "stream_deliver_mean_ms", "stream_cpu_us_per_event",
            "idle_stream_overlap_share", "sample_share", "sample_draw_share",
            "attn_kernel_share", "compiles_in_window"} <= set(moves)
    # a window of steady ticks holds too few mixed ones for a median, and the
    # K/V-page and indexer readers have nothing to read here
    for name in ("mixed_tick_device_ms", "attn_roofline", "kv_pool_move_share",
                 "qmatmul_roofline", "moe_expert_roofline", "dsa_index_share",
                 "device_idle_share", "rows_per_tick"):
        assert name not in moves, name
    assert set(moves.values()) == {"itl_p95_ms", "setup_s"}
    for m in cell["per_layer"]:
        assert os.path.exists(os.path.join(
            BENCH, "readers", m["reader"]["kind"] + ".py")), m["name"]
    bench = run.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    assert [w["name"] for w in bench["workloads"]].count(CELL) == 1
    assert [w["config"] for w in bench["workloads"]].count(CONFIG) == 1
    # (found by name: the next configuration's entry goes after this one)
    entry = next(c for c in bench["configs"] if c["name"] == CONFIG)
    assert sorted(entry["reduced"]) == REDUCED
    for m in bench["per_layer"]:
        if m["name"] in NEW_METRICS:
            assert CELL in m["workloads"] and m["moves"] == "itl_p95_ms"
    # its list stays as it is until closed-loop clients are staggered
    out = next(m for m in bench["end_to_end"] if m["name"] == "output_tok_s")
    assert out["workloads"] == ["mistral7b.decode-saturated"]
    assert all(len(w["why"]) <= 200 for w in bench["workloads"])
    assert len(json.dumps(bench)) < 64 * 1024


def test_the_yardsticks_shapes():
    import shapes_xing4

    hf = run.hf_config(config())
    assert shapes_xing4.streams(hf) == 4
    assert shapes_xing4.sublayers(hf) == 80
    assert shapes_xing4.token_bytes(hf) == 2 * 4 * 3584 * 2 == 57344
    assert shapes_xing4.projection_bytes(hf) == 344064 * 4


def _planted() -> dict:
    """Three traced ticks: a mixed one and a steady one with their stream
    mixing inside, and one whose program the capture cut."""
    pre = "jit(_ragged_tick)/tick/"
    body = "while/body/layer/mhc/"
    spans = [
        ["tick", 0.0, 0.1, {"tick": 1}],
        ["dispatch", 0.001, 0.002, {
            "variant": "mixed", "family": "tick.admission", "rows_active": 15,
            "attn_pages_live": 150, "mhc_tokens": (15 + 400) * 80}],
        ["sync", 0.002, 0.09, {}],
        ["tick", 0.1, 0.2, {"tick": 2}],
        ["dispatch", 0.101, 0.102, {
            "variant": "steady", "family": "tick.steady", "rows_active": 16,
            "attn_pages_live": 160, "mhc_tokens": 16 * 80}],
        ["sync", 0.102, 0.19, {}],
        ["tick", 0.2, 0.3, {"tick": 3}],
        ["dispatch", 0.201, 0.202, {
            "variant": "steady", "family": "tick.steady", "rows_active": 16,
            "attn_pages_live": 160, "mhc_tokens": 16 * 80}],
        ["sync", 0.202, 0.29, {}]]
    ops = [
        ["%fusion.1 = f32[24,1,400] fusion(...)", 0.010, 0.004,
         pre + "prefill/" + body + "mhc/coef/dot_general", "fusion"],
        ["%fusion.2 = bf16[4,1,400,3584] fusion(...)", 0.020, 0.003,
         pre + "prefill/" + body + "mhc/mix/add", "fusion"],
        ["%fusion.3 = f32[24,16,1] fusion(...)", 0.110, 0.0012,
         pre + "decode/" + body + "mhc/coef/dot_general", "fusion"],
        ["%fusion.4 = f32[4,4,16,1] fusion(...)", 0.112, 0.0004,
         pre + "decode/" + body + "mhc/coef/div", "fusion"],
        ["%fusion.5 = bf16[4,16,1,3584] fusion(...)", 0.114, 0.0006,
         pre + "decode/" + body + "mhc/mix/add", "fusion"],
        ["%qmatmul.6 = f32[16,3584] custom-call(...)", 0.120, 0.010,
         pre + "decode/while/body/layer/mlp/pallas_call", "custom-call"]]
    return {"spans": spans, "ops": ops, "modules": []}


def _ctx(parsed, monkeypatch) -> dict:
    import xplane_spans

    monkeypatch.setattr(xplane_spans, "load", lambda ctx: parsed)
    c = config()
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
    from readers import mhc_mix_roofline, scope_share

    parsed = _planted()
    ctx = _ctx(parsed, monkeypatch)
    got = mhc_mix_roofline.read(_reader("mhc_mix_roofline"), ctx)
    tokens = (15 + 400) * 80 + 16 * 80                  # the third tick: cut
    byts = tokens * 57344 + 2 * 80 * 344064 * 4
    spent = 0.004 + 0.003 + 0.0012 + 0.0004 + 0.0006
    assert got == pytest.approx(100.0 * byts / 819e9 / spent)
    assert 0 < got < 100
    note = ctx["notes"]["mhc_mix_roofline"]
    assert (note["ticks"], note["mhc_tokens"], note["bytes"]) == (
        2, tokens, byts)
    assert note["scoped_s"] == pytest.approx(spent)
    # the two shares read the same trace by data files alone; mhc/coef and
    # mhc/mix nest inside layer/mhc
    busy = spent + 0.010
    for name, seconds in (("mhc_share", spent),
                          ("mhc_coef_share", 0.004 + 0.0012 + 0.0004)):
        reader = _reader(name)
        assert reader["kind"] == "scope_share"
        assert scope_share.read(reader, ctx) == pytest.approx(
            100.0 * seconds / busy)


def test_the_new_readers_give_nothing_where_there_is_nothing(monkeypatch):
    """The parent's program writes no such stat or scope; a run without a
    trace has none to read; another family's configuration has one stream:
    None each time, no raise."""
    from readers import mhc_mix_roofline, scope_share

    roofline = _reader("mhc_mix_roofline")
    no_trace = {"trace": None, "hf_config": run.hf_config(config()),
                "record": {"counters": {"start": {"metrics": {}},
                                        "end": {"metrics": {}}}}}
    assert mhc_mix_roofline.read(roofline, no_trace) is None
    for name in ("mhc_share", "mhc_coef_share"):
        assert scope_share.read(_reader(name), no_trace) is None
    # the parent: the spans are there, the stat and the scopes are not
    parsed = _planted()
    for span in parsed["spans"]:
        span[3].pop("mhc_tokens", None)
    parsed["ops"] = [o for o in parsed["ops"] if "mhc/" not in o[3]]
    ctx = _ctx(parsed, monkeypatch)
    assert mhc_mix_roofline.read(roofline, ctx) is None
    for name in ("mhc_share", "mhc_coef_share"):
        assert scope_share.read(_reader(name), ctx) is None
    # the stat without the scopes, and the scopes without the stat
    half = _planted()
    half["ops"] = [o for o in half["ops"] if "mhc/" not in o[3]]
    assert mhc_mix_roofline.read(roofline, _ctx(half, monkeypatch)) is None
    half = _planted()
    for span in half["spans"]:
        span[3].pop("mhc_tokens", None)
    assert mhc_mix_roofline.read(roofline, _ctx(half, monkeypatch)) is None
    # another family's cell
    other = _ctx(_planted(), monkeypatch)
    other["hf_config"] = run.hf_config(run.load_json(os.path.join(
        BENCH, "configs", "a.x-k1.int4.ep16.json")))
    assert mhc_mix_roofline.read(roofline, other) is None
