"""The benchmark's files for ``model_type: glm5_next_text`` (PR 50): the
configuration against the catalog and against the trees it states, the cell
against the table, the new readers on a planted trace (and nothing where there
is nothing to read), the yardstick's numbers (shapes_glm5_next) against the
program's own shapes, and the reference's control precision at a tiny size."""

from __future__ import annotations

import importlib
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

CONFIG = "glm-5.3-flash.int4.ep8"
FAMILY = "glm5_next_text"
CELL = "glm53flash.long-context"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
NEW_METRICS = ("kda_share", "kda_chunk_share", "kda_step_share",
               "kda_chunk_roofline", "kda_state_roofline",
               "dsa_pooled_selected_share")
LISTED = ("mixed_tick_device_ms", "mixed_tick_share", "moe_experts_share",
          "moe_shared_share", "moe_local_pair_share",
          "moe_tokens_per_hit_expert", "moe_row_fill_share",
          "moe_gated_expert_roofline", "mla_absorb_share", "dsa_index_share",
          "dsa_select_share", "dsa_attend_share", "dsa_selected_share",
          "mhc_share", "mhc_coef_share", "mhc_mix_roofline",
          "weight_slice_share", "scale_plane_pass_share",
          "unscoped_device_share", "h2d_per_tick", "d2h_per_tick",
          "host_wait_ms_per_tick", "host_cpu_ms_per_tick",
          "sync_fetch_ms_per_tick", "stream_wake_mean_ms",
          "stream_deliver_mean_ms", "stream_cpu_us_per_event",
          "idle_stream_overlap_share", "sample_share", "sample_draw_share",
          "ahead_tick_share")
REDUCED = ["num_hidden_layers", "first_k_dense_replace", "layer_types",
           "mlp_layer_types", "indexer_types", "linear_attn_config",
           "n_routed_experts", "vocab_size"]


def config() -> dict:
    return run.load_json(os.path.join(BENCH, "configs", CONFIG + ".json"))


def test_the_configuration_states_its_cut():
    c = config()
    for key, value in {"hidden_size": 4096, "intermediate_size": 12288,
                       "num_attention_heads": 64, "q_lora_rank": 1536,
                       "kv_lora_rank": 512, "qk_nope_head_dim": 256,
                       "qk_rope_head_dim": 0, "v_head_dim": 256,
                       "index_n_heads": 32, "index_head_dim": 128,
                       "index_topk": 2048, "index_kpool": 4,
                       "moe_intermediate_size": 2048,
                       "num_experts_per_tok": 8, "hc_mult": 4,
                       "hc_sinkhorn_iters": 20, "swiglu_limit": 10,
                       "n_router_experts": 288}.items():
        assert c[key] == value, key
    la = c["linear_attn_config"]
    assert (la["num_heads"], la["head_dim"], la["short_conv_kernel_size"],
            la["gate_lower_bound"]) == (64, 128, 4, -5)
    assert la["kda_layers"] == [0, 2, 3, 4, 6, 7, 8]
    assert la["full_attn_layers"] == [1, 5]
    assert c["reduced"] == REDUCED
    L, D = "linear_attention", "deepseek_sparse_attention"
    assert c["layer_types"] == [L, D, L, L, L, D, L, L, L]
    assert c["mlp_layer_types"] == ["dense"] + ["sparse"] * 8
    assert (c["num_hidden_layers"], c["n_routed_experts"],
            c["vocab_size"]) == (9, 36, 19360)
    dep = c["deployment"]
    assert dep["chips_sharing_a_layer"] == 8
    assert set(dep["published"]) == set(REDUCED)
    assert dep["published"]["n_routed_experts"] == 288
    assert len(dep["published"]["layer_types"]) == 45
    assert {"kda_gate", "kda_low_rank_gates", "index_kpool", "no_rotary",
            "hc_res_clamp", "swiglu_limit"} <= set(c["assumed"])
    assert set(c["withheld"]) == {"num_nextn_predict_layers",
                                  "index_share_for_mtp_iteration"}
    assert c["engine"] == {"max_rows": 32, "page_size": 128,
                           "max_seq_len": 13312, "pool_pages": 3360,
                           "step_token_budget": 512, "prefill_bucket": 512}
    assert c["chips"] == 1 and c["weight_qtype"] == "sym_int4"
    hf = run.hf_config(c)
    assert "deployment" not in hf and "num_nextn_predict_layers" not in hf
    for entry in c["rehearsal"]["compiled"]:
        assert entry["state_buffers"] == 2 and entry["mosaic_kernels"] > 0
        # over a quarter of the chip, and inside its 15.75 GiB
        assert 4e9 < entry["resident_bytes"] < 15.75 * 2 ** 30


@pytest.mark.skipif(not os.path.exists(CATALOG), reason="no catalog here")
def test_every_published_number_is_the_catalogs_or_listed_as_reduced():
    with open(CATALOG) as f:
        entry = next(e for e in map(json.loads, f)
                     if e["name"] == "GLM-5.3-Flash")
    c = config()
    assert c["source"] == entry["source_url"]
    for key, value in entry["config"].items():
        if key in c["reduced"]:
            assert c[key] != value, key
        else:
            assert c[key] == value, key
    # inside the one nested group that is cut, only the layer lists changed
    for key, value in entry["config"]["linear_attn_config"].items():
        if key not in ("kda_layers", "full_attn_layers"):
            assert c["linear_attn_config"][key] == value, key


def test_the_bytes_the_file_states_are_the_trees():
    """The weights' bytes from ``jax.eval_shape`` of the generator's tree, the
    page's from the program's own geometry and the state's from
    ``kv.recurrent_state``, as the file's notes give them; and the yardstick's
    numbers (shapes_glm5_next, from the config's keys alone) against the same
    shapes and against the kernel's own count."""
    import jax

    import shapes_glm5_next as work
    import weights
    from ipex_llm_tpu import kv
    from ipex_llm_tpu.models.families import FAMILIES
    from ipex_llm_tpu.ops import kda
    from ipex_llm_tpu.ops.pallas import kda_chunk

    c = config()
    hf = run.hf_config(c)
    cfg = FAMILIES[FAMILY].to_config(hf)
    gen = weights.generator(FAMILY)
    tree = jax.eval_shape(lambda: gen.param_tree(cfg, hf, "sym_int4", 0))

    def n_bytes(t):
        return sum(x.size * x.dtype.itemsize
                   for x in jax.tree_util.tree_leaves(t))

    text = " ".join(c["notes"])
    for part in (tree, tree["layers_dense"], tree["layers"], tree["embed"],
                 tree["lm_head"], tree["layers"]["kda"],
                 tree["layers"]["mla"], tree["layers"]["indexer"]):
        assert f"{n_bytes(part):,} B" in text
    assert tree["layers"]["kda"]["in_proj"].shape == (4096, 24896)
    assert tree["layers"]["kda"]["in_proj"].data.shape == (6, 2048, 24960)
    assert tree["layers"]["mla"]["kv_a"].shape == (4096, 512)
    page = kv.model_page_bytes(cfg, 128)
    assert page == 278528 and f"{page:,} B a page" in text
    assert f"{3360 * page:,} B" in text
    state = jax.eval_shape(lambda: kv.recurrent_state(cfg, 32))
    assert f"{n_bytes(state):,} B" in text
    assert f"{2 * n_bytes(state):,} B" in text
    row = work.kda_state_row_bytes(hf)
    assert row == 4341760 and f"{int(row):,} B" in text
    assert 32 * 7 * row == n_bytes(
        {k: v for k, v in state.items() if k.startswith("kda_")})
    assert work.kda_layers(hf) == cfg.kda_layers == 7
    assert work.sparse_layers(hf) == cfg.kv_layers == 2
    assert (work.CHUNK, work.SUB) == (kda.CHUNK, kda.SUB)
    assert work.kda_block_flops(128) == kda_chunk.block_flops(64, 128)
    assert work.kda_step_bytes(hf, 32 * 7) == 2 * 32 * 7 * row
    assert work.latent_row_bytes(hf) == 1024
    # what the engine holds: the tree, the pool, the state twice: ~half a chip
    resident = n_bytes(tree) + 3360 * page + 2 * n_bytes(state)
    assert 8.0e9 < resident < 8.3e9


def test_the_cell_resolves_with_its_metrics():
    cell = run.resolve_cell(ROOT, CELL)
    assert cell["cell"] == {
        "name": CELL, "config": CONFIG, "traffic": "long-context-32",
        "chips": 1, "why": cell["cell"]["why"]}
    assert len(cell["cell"]["why"]) <= 200
    assert [m["name"] for m in cell["end_to_end"]] == ["itl_p95_ms",
                                                       "setup_s"]
    names = [m["name"] for m in cell["per_layer"]]
    assert set(NEW_METRICS) | set(LISTED) <= set(names)
    # another family's roofline readers leave this cell out
    assert not {"dsa_attn_roofline", "mla_attn_roofline", "ssm_share",
                "ssm_state_roofline", "h1_ssm_state_roofline"} & set(names)
    bench = run.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    for m in bench["per_layer"]:
        if m["name"] in NEW_METRICS:
            assert m["workloads"] == [CELL] and m["moves"] == "itl_p95_ms"
            assert m["unit"] == "%"
    t = cell["traffic"]
    assert (t["loop"], t["clients"]) == ("closed", 32)
    assert t["prompt_len"] == {"dist": "uniform", "min": 4096, "max": 12288}
    assert t["output_len"] == {"dist": "uniform", "min": 256, "max": 512}
    assert "order" in t and max(t["warmup"]["p_b"]) == 4
    eng = cell["config"]["engine"]
    assert t["prompt_len"]["max"] + t["output_len"]["max"] <= eng["max_seq_len"]


# -- the new readers ---------------------------------------------------------------

def _planted() -> dict:
    """Three traced ticks: a mixed one (a 512-token chunk beside 24 decoding
    rows), a steady one, and a mixed one whose program the capture cut."""
    pre = "jit(_ragged_tick)/tick/"
    body = "while/body/layer/kda/kda/"
    mixed = {"variant": "mixed", "family": "tick.admission",
             "rows_active": 24, "kda_chunk_tokens": 7 * 512,
             "kda_chunk_rows": 7, "kda_step_rows": 7 * 24}
    spans = [
        ["tick", 0.0, 0.1, {"tick": 1}],
        ["dispatch", 0.001, 0.002, mixed], ["sync", 0.002, 0.09, {}],
        ["tick", 0.1, 0.2, {"tick": 2}],
        ["dispatch", 0.101, 0.102, {
            "variant": "steady", "family": "tick.steady", "rows_active": 32,
            "kda_chunk_tokens": 0, "kda_chunk_rows": 0,
            "kda_step_rows": 7 * 32}],
        ["sync", 0.102, 0.19, {}],
        ["tick", 0.2, 0.3, {"tick": 3}],
        ["dispatch", 0.201, 0.202, mixed], ["sync", 0.202, 0.29, {}]]
    ops = [
        ["%kda_chunk.1 = f32[1,64,512,128] custom-call(...)", 0.010, 0.004,
         pre + "prefill/" + body + "chunk/pallas_call", "custom-call"],
        ["%fusion.2 = f32[1,64,512,128] fusion(...)", 0.015, 0.001,
         pre + "prefill/" + body + "chunk/transpose", "fusion"],
        ["%fusion.3 = f32[32,64,128,128] fusion(...)", 0.030, 0.006,
         pre + "decode/" + body + "step/mul", "fusion"],
        ["%fusion.4 = f32[32,64,128,128] fusion(...)", 0.130, 0.005,
         pre + "decode/" + body + "step/mul", "fusion"],
        ["%qmatmul.5 = bf16[32,24960] custom-call(...)", 0.140, 0.002,
         pre + "decode/" + body + "proj/pallas_call", "custom-call"],
        ["%qmatmul.6 = f32[32,19360] custom-call(...)", 0.150, 0.002,
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
            "record": {"counters": {
                "start": {"metrics": {"dsa_tokens_selected_total": 100,
                                      "dsa_tokens_visible_total": 200}},
                "end": {"metrics": {"dsa_tokens_selected_total": 1100,
                                    "dsa_tokens_visible_total": 4200}}}}}


def _reader(name: str) -> dict:
    return run.load_json(os.path.join(BENCH, "layer_metrics",
                                      name + ".json"))["reader"]


def test_the_new_metrics_on_a_planted_trace(monkeypatch):
    import shapes_glm5_next as work
    from readers import counter_scaled, kda_roofline, scope_share

    parsed = _planted()
    ctx = _ctx(parsed, monkeypatch)
    hf = ctx["hf_config"]
    got = kda_roofline.read(_reader("kda_state_roofline"), ctx)
    # two ticks with their step inside (24 and 32 rows x 7 layers), 11 ms
    least = 2 * (24 + 32) * 7 * 4341760 / 819e9
    assert got == pytest.approx(100 * least / 0.011, rel=1e-6)
    assert ctx["notes"]["kda_roofline.step"]["ticks"] == 2
    got = kda_roofline.read(_reader("kda_chunk_roofline"), ctx)
    # the one mixed tick with its chunk inside: 5 ms in kda/chunk
    least = max(work.kda_chunk_flops(hf, 7 * 512) / 197e12,
                work.kda_chunk_bytes(hf, 7 * 512, 7) / 819e9)
    assert got == pytest.approx(100 * least / 0.005, rel=1e-6)
    assert ctx["notes"]["kda_roofline.chunk"]["ticks"] == 1
    assert 0 < got < 100
    busy = 0.004 + 0.001 + 0.006 + 0.005 + 0.002 + 0.002
    for name, sec in (("kda_share", busy - 0.002), ("kda_chunk_share", 0.005),
                      ("kda_step_share", 0.011)):
        assert scope_share.read(_reader(name), ctx) == pytest.approx(
            100 * sec / busy)
    assert counter_scaled.read(_reader("dsa_pooled_selected_share"),
                               ctx) == pytest.approx(25.0)
    # another family's configuration, the parent's program (no such scope,
    # no such stat), a CPU run: nothing, and no raise
    glm52 = run.load_json(os.path.join(BENCH, "configs",
                                       "glm-5.2.int4.ep16.json"))
    bare = dict(parsed, ops=[o for o in parsed["ops"] if "kda" not in o[3]])
    plain = dict(parsed, spans=[[n, a, b, {k: v for k, v in at.items()
                                           if not k.startswith("kda_")}]
                                for n, a, b, at in parsed["spans"]])
    for name in ("kda_chunk_roofline", "kda_state_roofline"):
        assert kda_roofline.read(_reader(name),
                                 _ctx(parsed, monkeypatch, glm52)) is None
        assert kda_roofline.read(_reader(name),
                                 _ctx(bare, monkeypatch)) is None
        assert kda_roofline.read(_reader(name),
                                 _ctx(plain, monkeypatch)) is None
    assert scope_share.read(_reader("kda_share"),
                            _ctx(bare, monkeypatch)) is None
    none = dict(_ctx(None, monkeypatch), trace=None)
    none["record"] = {"counters": {"start": {"metrics": {}},
                                   "end": {"metrics": {}}}}
    for name in NEW_METRICS:
        reader = importlib.import_module("readers." + _reader(name)["kind"])
        assert reader.read(_reader(name), none) is None


# -- the reference's control precision, at a size the CPU holds -----------------------

def test_the_control_precision_reads_above_the_configurations_own():
    """The reference against itself over a 150-token sequence of the tiny
    model: activations through fp8 read several times what activations
    rounded to bf16 read (the limits themselves are set at the published
    widths on the chip: references/glm5_next_text.py)."""
    import numpy as np

    import weights
    from ipex_llm_tpu.models.families import FAMILIES

    gen = weights.generator(FAMILY)
    hf = gen.tiny(run.hf_config(config()))
    cfg = FAMILIES[FAMILY].to_config(hf)
    params = gen.param_tree(cfg, hf, "sym_int4", 3)
    ref = importlib.import_module("references." + FAMILY)
    ids = [int(i) for i in np.random.default_rng(2).integers(3, 256, 150)]
    exact = ref.logprobs(hf, params, ids, first_row=100)
    tokens = [int(t) for t in np.argmax(exact[0], axis=-1)]
    read = {}
    for lower in ("bf16", "fp8"):
        rows = ref.logprobs(hf, params, ids, lower=lower, first_row=100)[0]
        res = ref.compare(exact, 1, tokens,
                          [float(rows[j][t]) for j, t in enumerate(tokens)])
        read[lower] = res["logprob_err_mean"]
    assert read["fp8"] > 3 * read["bf16"] > 0, read
