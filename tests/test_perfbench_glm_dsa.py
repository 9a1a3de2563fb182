"""The benchmark's own files for ``model_type: glm_moe_dsa``, in tier-1: what
the configuration's file and the cell's entries must state, that the new
reader reads a planted trace and gives nothing (without raising) where there
is none, and that the sparse path's kernels compile for a described v5e at the
cell's widths.  The served path against the reference is
tests/test_glm_dsa.py."""

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

CONFIG = "glm-5.2.int4.ep16"
CELL = "glm52.long-document"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
REDUCED = ["first_k_dense_replace", "indexer_types", "mlp_layer_types",
           "n_routed_experts", "num_hidden_layers", "vocab_size"]


def config() -> dict:
    return run.load_json(os.path.join(BENCH, "configs", CONFIG + ".json"))


def test_the_configuration_states_its_cut():
    c = config()
    for key, value in {"hidden_size": 6144, "intermediate_size": 12288,
                       "num_attention_heads": 64, "q_lora_rank": 2048,
                       "kv_lora_rank": 512, "qk_nope_head_dim": 192,
                       "qk_rope_head_dim": 64, "v_head_dim": 256,
                       "moe_intermediate_size": 2048,
                       "num_experts_per_tok": 8, "n_group": 1,
                       "topk_group": 1, "n_shared_experts": 1,
                       "routed_scaling_factor": 2.5, "index_topk": 2048,
                       "index_n_heads": 32, "index_head_dim": 128,
                       "index_topk_freq": 4, "topk_method": "noaux_tc",
                       "max_position_embeddings": 1048576}.items():
        assert c[key] == value, key
    assert c["rope_parameters"] == {"rope_theta": 8000000,
                                    "rope_type": "default"}
    assert sorted(c["reduced"]) == REDUCED
    assert (c["num_hidden_layers"], c["first_k_dense_replace"],
            c["n_routed_experts"], c["vocab_size"]) == (9, 1, 16, 19360)
    # one dense layer with a full indexer, then two whole periods
    assert c["indexer_types"] == ["full"] + ["shared", "shared", "shared",
                                             "full"] * 2
    assert c["mlp_layer_types"] == ["dense"] + ["sparse"] * 8
    dep = c["deployment"]
    assert dep["chips_sharing_a_layer"] == 16
    assert sorted(dep["published"]) == REDUCED
    assert (dep["published"]["num_hidden_layers"],
            dep["published"]["first_k_dense_replace"],
            dep["published"]["n_routed_experts"],
            dep["published"]["vocab_size"]) == (78, 3, 256, 154880)
    assert len(dep["published"]["indexer_types"]) == 78
    assert c["n_router_experts"] == 256 and c["first_routed_expert"] == 0
    assert {"n_router_experts", "first_routed_expert", "shared_layers",
            "indexer_norm_and_scale", "hadamard", "page_geometry",
            "step_token_budget"} <= set(c["assumed"])
    assert {"num_nextn_predict_layers",
            "index_share_for_mtp_iteration"} <= set(c["withheld"])
    eng = c["engine"]
    assert (eng["max_rows"], eng["max_seq_len"], eng["page_size"],
            eng["step_token_budget"], eng["prefill_bucket"]) == (
        16, 17408, 128, 512, 512)
    assert eng["pool_pages"] >= 16 * 132 and c["chips"] == 1
    hf = run.hf_config(c)
    assert "deployment" not in hf and "num_nextn_predict_layers" not in hf
    assert c["rehearsal"]["compiled"][0]["mosaic_kernels"] > 0
    assert c["rehearsal"]["compiled"][0]["argument_bytes"] < 9 * 10 ** 9


@pytest.mark.skipif(not os.path.exists(CATALOG), reason="no catalog here")
def test_every_published_number_is_the_catalogs_or_listed_as_reduced():
    with open(CATALOG) as f:
        entry = next(e for e in map(json.loads, f) if e["name"] == "GLM-5.2")
    c = config()
    assert c["source"] == entry["source_url"]
    for key, value in entry["config"].items():
        if key in c["reduced"]:
            assert c[key] != value, key
        else:
            assert c[key] == value, key


def test_the_bytes_the_file_states_are_the_trees():
    """The weights' bytes from ``jax.eval_shape`` of the generator's tree and
    the page's bytes by kind from the program's own geometry, as the file's
    notes give them."""
    import jax

    import weights
    from ipex_llm_tpu import kv
    from ipex_llm_tpu.models.families import FAMILIES

    c = config()
    hf = run.hf_config(c)
    cfg = FAMILIES["glm_moe_dsa"].to_config(hf)
    gen = weights.generator("glm_moe_dsa")
    tree = jax.eval_shape(lambda: gen.param_tree(cfg, hf, "sym_int4", 0))
    n_bytes = sum(x.size * x.dtype.itemsize
                  for x in jax.tree_util.tree_leaves(tree))
    text = " ".join(c["notes"])
    assert f"{n_bytes:,} B" in text
    page = kv.model_page_bytes(cfg, 128)
    assert page == 1572864 and f"{page:,} B a page" in text
    assert f"{2176 * page:,} B" in text
    ix = tree["layers"]["indexer"]
    assert ix["wq_b"].data.shape[0] == 2 and "indexer" in tree["layers_dense"]


def test_the_cell_resolves_with_its_metrics():
    cell = run.resolve_cell(ROOT, CELL)
    assert cell["cell"]["chips"] == 1
    assert cell["cell"]["traffic"] == "long-document-16"
    t = cell["traffic"]
    assert t["loop"] == "closed"
    assert t["clients"] == cell["config"]["engine"]["max_rows"] == 16
    assert (t["prompt_len"], t["output_len"]) == (
        {"dist": "uniform", "min": 8192, "max": 16384},
        {"dist": "uniform", "min": 256, "max": 512})
    assert 45 <= t["ramp_s"] <= 90 and t["drain_s"] == 60
    assert t["warmup"]["p_b"] == [1, 2, 4, 8, 16]
    assert t["warmup"]["longest_prompt"] == 1024
    # the longest request fits a row, and the traffic's most fits the pool
    eng = cell["config"]["engine"]
    assert 16384 + 512 <= eng["max_seq_len"]
    assert t["clients"] * -(-(16384 + 512) // eng["page_size"]) <= eng[
        "pool_pages"]
    reqs = trafficgen.schedule(t, 2 ** 31 + 9, 40, t["ramp_s"])["requests"]
    assert len(reqs) == t["pool"] >= 64
    assert all(8192 <= r["prompt_len"] <= 16384
               and 256 <= r["max_tokens"] <= 512 for r in reqs)
    assert {m["name"] for m in cell["end_to_end"]} == {"itl_p95_ms",
                                                       "setup_s"}
    moves = {m["name"]: m["moves"] for m in cell["per_layer"]}
    assert {"dsa_index_share", "dsa_select_share", "dsa_attend_share",
            "dsa_selected_share", "dsa_attn_roofline", "attn_kernel_share",
            "mixed_tick_device_ms", "mixed_tick_share", "weight_slice_share",
            "moe_experts_share", "moe_shared_share", "moe_local_pair_share",
            "moe_tokens_per_hit_expert", "mla_absorb_share",
            "moe_gated_expert_roofline", "h2d_per_tick",
            "compiles_in_window"} <= set(moves)
    # a walk over selected tokens is not what these readers count, and an
    # all-mixed trace holds no steady tick
    for name in ("mla_attn_roofline", "steady_tick_device_ms",
                 "attn_roofline", "kv_pool_move_share", "qmatmul_roofline",
                 "moe_expert_roofline", "device_idle_share"):
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
    assert all(len(w["why"]) <= 200 for w in bench["workloads"])
    assert len(json.dumps(bench)) < 64 * 1024


def test_the_yardsticks_shapes():
    import shapes_glm_dsa

    hf = run.hf_config(config())
    assert shapes_glm_dsa.latent_row_bytes(hf) == 1152
    assert shapes_glm_dsa.index_key_bytes(hf) == 256
    assert shapes_glm_dsa.attention_layers(hf) == 9
    assert shapes_glm_dsa.full_layers(hf) == 3
    assert shapes_glm_dsa.cache_token_bytes(hf) == 9 * 1152 + 3 * 256 == 11136
    assert shapes_glm_dsa.walk_flops_per_pair(hf) == 2 * 64 * (576 + 512)
    assert shapes_glm_dsa.score_flops_per_pair(hf) == 2 * 32 * 128
    whole = dict(hf, num_hidden_layers=78, first_k_dense_replace=3)
    del whole["indexer_types"]
    assert shapes_glm_dsa.full_layers(whole) == 21


def _planted() -> dict:
    """Three traced ticks: a mixed one and a steady one with their sparse
    walks inside, and one whose program the capture cut."""
    pre = "jit(_ragged_tick)/tick/"
    attend = "layer/attn/dsa/attend/"
    spans = [
        ["tick", 0.0, 0.1, {"tick": 1}],
        ["dispatch", 0.001, 0.002, {
            "variant": "mixed", "family": "tick.admission", "rows_active": 13,
            "attn_pages_live": 1300, "dsa_keys_scored": 9000000,
            "dsa_tokens_selected": 9 * (512 + 13) * 2048,
            "dsa_decode_selected": 9 * 13 * 2048}],
        ["sync", 0.002, 0.09, {}],
        ["tick", 0.1, 0.2, {"tick": 2}],
        ["dispatch", 0.101, 0.102, {
            "variant": "steady", "family": "tick.steady", "rows_active": 16,
            "attn_pages_live": 1600, "dsa_keys_scored": 600000,
            "dsa_tokens_selected": 9 * 16 * 2048,
            "dsa_decode_selected": 9 * 16 * 2048}],
        ["sync", 0.102, 0.19, {}],
        ["tick", 0.2, 0.3, {"tick": 3}],
        ["dispatch", 0.201, 0.202, {
            "variant": "steady", "family": "tick.steady", "rows_active": 16,
            "attn_pages_live": 1600, "dsa_keys_scored": 600000,
            "dsa_tokens_selected": 9 * 16 * 2048,
            "dsa_decode_selected": 9 * 16 * 2048}],
        ["sync", 0.202, 0.29, {}]]
    ops = [
        ["%ragged_paged_attention_sparse.1 = bf16[1,32768,512] "
         "custom-call(...)", 0.010, 0.030,
         pre + "prefill/while/body/" + attend + "pallas_call", "custom-call"],
        ["%fusion.2 = bf16[16,2048,640] fusion(...)", 0.050, 0.008,
         pre + "decode/while/body/" + attend + "gather", "fusion"],
        ["%fusion.3 = f32[512,17408] fusion(...)", 0.060, 0.004,
         pre + "prefill/while/body/layer/attn/dsa/index/dsa/score/dot_general",
         "fusion"],
        ["%fusion.4 = u32[512] fusion(...)", 0.065, 0.002,
         pre + "prefill/while/body/layer/attn/dsa/select/reduce", "fusion"],
        ["%fusion.5 = bf16[16,2048,640] fusion(...)", 0.110, 0.008,
         pre + "decode/while/body/" + attend + "gather", "fusion"],
        ["%qmatmul.6 = f32[16,6144] custom-call(...)", 0.120, 0.010,
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


def test_the_sparse_walks_roofline_on_a_planted_trace(monkeypatch):
    from readers import dsa_attn_roofline, scope_share

    parsed = _planted()
    params = run.load_json(os.path.join(
        BENCH, "layer_metrics", "dsa_attn_roofline.json"))["reader"]
    ctx = _ctx(parsed, monkeypatch)
    got = dsa_attn_roofline.read(params, ctx)
    pairs = 9 * (512 + 13) * 2048 + 9 * 16 * 2048      # the third tick: cut
    flops_s = pairs * 2 * 64 * 1088 / 197e12
    bytes_s = 9 * (13 + 16) * 2048 * 1152 / 819e9
    spent = 0.030 + 0.008 + 0.008
    assert got == pytest.approx(100.0 * max(flops_s, bytes_s) / spent)
    assert 0 < got < 100
    note = ctx["notes"]["dsa_attn_roofline"]
    assert (note["ticks"], note["pairs_selected"]) == (2, pairs)
    assert note["kernel_s"] == pytest.approx(spent)
    # the scope shares read the same trace by data files alone; dsa/score
    # nests inside dsa/index
    busy = 0.030 + 0.008 + 0.004 + 0.002 + 0.008 + 0.010
    for name, seconds in (("dsa_index_share", 0.004),
                          ("dsa_select_share", 0.002),
                          ("dsa_attend_share", spent)):
        reader = run.load_json(os.path.join(
            BENCH, "layer_metrics", name + ".json"))["reader"]
        assert reader["kind"] == "scope_share"
        assert scope_share.read(reader, ctx) == pytest.approx(
            100.0 * seconds / busy)
    assert scope_share.scoped_seconds(parsed, "dsa/score")[0] == 0.004


def test_the_new_readers_give_nothing_where_there_is_nothing(monkeypatch):
    """The parent's program writes no such stat, scope or counter; a run
    without a trace has none to read; another family's configuration has no
    indexer: None each time, no raise."""
    from readers import counter_scaled, dsa_attn_roofline, scope_share

    params = {"scope": "dsa/attend"}
    no_trace = {"trace": None, "hf_config": run.hf_config(config()),
                "record": {"counters": {"start": {"metrics": {}},
                                        "end": {"metrics": {}}}}}
    assert dsa_attn_roofline.read(params, no_trace) is None
    assert scope_share.read(params, no_trace) is None
    selected = run.load_json(os.path.join(
        BENCH, "layer_metrics", "dsa_selected_share.json"))["reader"]
    assert counter_scaled.read(selected, no_trace) is None
    counted = dict(no_trace, record={"counters": {
        "start": {"metrics": {"dsa_tokens_selected_total": 100,
                              "dsa_tokens_visible_total": 400}},
        "end": {"metrics": {"dsa_tokens_selected_total": 300,
                            "dsa_tokens_visible_total": 1400}}}})
    assert counter_scaled.read(selected, counted) == pytest.approx(20.0)
    # the parent: the spans are there, the stats are not
    parsed = _planted()
    for span in parsed["spans"]:
        for key in [k for k in span[3] if k.startswith("dsa_")]:
            del span[3][key]
    parsed["ops"] = [o for o in parsed["ops"] if "dsa/" not in o[3]]
    ctx = _ctx(parsed, monkeypatch)
    assert dsa_attn_roofline.read(params, ctx) is None
    assert scope_share.read(params, ctx) is None
    # another family's cell
    other = _ctx(_planted(), monkeypatch)
    other["hf_config"] = run.hf_config(run.load_json(os.path.join(
        BENCH, "configs", "a.x-k1.int4.ep16.json")))
    assert dsa_attn_roofline.read(params, other) is None


# -- the sparse path's kernels, compiled by Mosaic for a described v5e -------------

@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    os.environ.setdefault("TPU_LOG_DIR", "disabled")   # else it logs in /tmp
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


# name: (rows, tokens a row): the budget's 512 tokens over p_b joining rows
SPARSE_SHAPES = {"chunk512": (1, 512), "chunk128": (4, 128),
                 "chunk32": (16, 32), "decode": (16, 1)}


@pytest.mark.parametrize("kernel", ["walk", "write"])
@pytest.mark.parametrize("name", sorted(SPARSE_SHAPES))
def test_mosaic_takes_the_sparse_kernels(one_chip, name, kernel, monkeypatch):
    import jax
    import jax.numpy as jnp

    from ipex_llm_tpu import kv
    from ipex_llm_tpu.ops.pallas import paged_kv_write as pkw
    from ipex_llm_tpu.ops.pallas import ragged_paged_attention as rpa

    monkeypatch.setattr(rpa, "_interpret", lambda: False)
    monkeypatch.setattr(pkw, "_interpret", lambda: False)
    rows, t = SPARSE_SHAPES[name]
    layers, full, pages, ps, heads, width, rank = 9, 3, 64, 128, 64, 640, 512
    maxp, bf16, i32 = 136, jnp.bfloat16, jnp.int32

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    row_pool = arg((layers, pages, 1, ps, width), bf16)
    key_pool = arg((full, pages, 1, ps, 128), bf16)
    tables, lens = arg((rows, maxp), i32), arg((rows,), i32)
    if kernel == "walk":
        if t == 1:
            pytest.skip("a decode row gathers its rows: no kernel")
        fn = jax.jit(lambda q, pool, tb, sel, n, cl, layer:
                     rpa.sparse_paged_sdpa(q, pool, tb, sel, n, cl, rank=rank,
                                           scale=0.0625, layer=layer))
        args = (arg((rows, t, heads, width), bf16), row_pool, tables,
                arg((rows, t, maxp * ps), jnp.bool_), lens, lens,
                arg((), i32))
        kernels = 1
    else:
        def write(rows_, keys, tb, new_row, new_key, pos, layer, fi):
            cache = kv.PagedKVCache(rows_, keys, tb, jnp.zeros((), i32),
                                    latent=True, sparse=True)
            return (pkw.paged_pool_write(cache, rows_, layer, new_row, pos),
                    pkw.paged_pool_write(cache, keys, fi, new_key, pos))

        fn = jax.jit(write, donate_argnums=(0, 1))
        args = (row_pool, key_pool, tables, arg((rows, t, 1, width), bf16),
                arg((rows, t, 1, 128), bf16), lens, arg((), i32),
                arg((), i32))
        kernels = 2
    compiled = fn.lower(*args).compile()
    assert compiled.as_text().count("tpu_custom_call") == kernels
    # the pools are read and written where they lie (the walk's mask as
    # int32 is its one temporary: 512 x 17408 x 4 B)
    limit = 2 ** 20 if kernel == "write" else 40 * 2 ** 20
    assert compiled.memory_analysis().temp_size_in_bytes < limit
