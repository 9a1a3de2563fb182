"""The benchmark's own files for ``model_type: axk1``, in tier-1: what the
configuration's file and the cell's entries must state, that the new readers
read a scoped trace and give nothing (without raising) where there is none,
and that the latent kernels compile for a described v5e at the cell's widths.
The served path against the reference is tests/test_axk1.py."""

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

CONFIG = "a.x-k1.int4.ep16"
CELL = "axk1.long-context"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


def config() -> dict:
    return run.load_json(os.path.join(BENCH, "configs", CONFIG + ".json"))


def test_the_configuration_states_its_cut():
    c = config()
    for key, value in {"hidden_size": 7168, "intermediate_size": 18432,
                       "num_attention_heads": 64, "q_lora_rank": 1536,
                       "kv_lora_rank": 512, "qk_nope_head_dim": 128,
                       "qk_rope_head_dim": 64, "v_head_dim": 128,
                       "moe_intermediate_size": 2048,
                       "num_experts_per_tok": 8, "n_group": 8,
                       "topk_group": 4, "n_shared_experts": 1,
                       "first_k_dense_replace": 1,
                       "routed_scaling_factor": 2.5,
                       "max_position_embeddings": 131072}.items():
        assert c[key] == value, key
    assert c["rope_scaling"] == {
        "beta_fast": 32, "beta_slow": 1, "factor": 32, "mscale": 1,
        "mscale_all_dim": 1, "original_max_position_embeddings": 4096,
        "type": "yarn"}
    assert sorted(c["reduced"]) == ["n_routed_experts", "num_hidden_layers",
                                    "vocab_size"]
    assert (c["num_hidden_layers"], c["n_routed_experts"],
            c["vocab_size"]) == (8, 12, 20480)
    assert c["deployment"]["chips_sharing_a_layer"] == 16
    assert c["deployment"]["published"] == {
        "num_hidden_layers": 61, "n_routed_experts": 192,
        "vocab_size": 163840}
    assert c["n_router_experts"] == 192 and c["first_routed_expert"] == 0
    assert {"n_router_experts", "first_routed_expert", "topk_method",
            "yarn_scale", "step_token_budget"} <= set(c["assumed"])
    eng = c["engine"]
    assert (eng["max_rows"], eng["max_seq_len"], eng["page_size"],
            eng["step_token_budget"]) == (64, 8192, 128, 512)
    assert eng["pool_pages"] >= 2560 and c["chips"] == 1
    hf = run.hf_config(c)
    assert "deployment" not in hf and hf["n_router_experts"] == 192


@pytest.mark.skipif(not os.path.exists(CATALOG), reason="no catalog here")
def test_every_published_number_is_the_catalogs_or_listed_as_reduced():
    with open(CATALOG) as f:
        entry = next(e for e in map(json.loads, f) if e["name"] == "A.X-K1")
    c = config()
    assert c["source"] == entry["source_url"]
    for key, value in entry["config"].items():
        if key in c["reduced"]:
            assert c[key] != value, key
        else:
            assert c[key] == value, key


def test_the_cell_resolves_with_its_metrics():
    cell = run.resolve_cell(ROOT, CELL)
    assert cell["cell"]["chips"] == 1
    assert cell["cell"]["traffic"] == "long-context-64"
    t = cell["traffic"]
    assert t["loop"] == "closed" and t["pool"] == 512
    assert t["clients"] == cell["config"]["engine"]["max_rows"]
    assert (t["prompt_len"], t["output_len"]) == (
        {"dist": "uniform", "min": 2048, "max": 4096},
        {"dist": "uniform", "min": 512, "max": 1024})
    assert (t["ramp_s"], t["drain_s"], t["trace"]["seconds"]) == (45, 60, 3)
    assert t["warmup"]["p_b"] == [1, 2, 4, 8, 16, 32, 64]
    assert t["warmup"]["longest_prompt"] == 1024
    # the longest request fits a row, and the traffic's most fits the pool
    eng = cell["config"]["engine"]
    assert 4096 + 1024 <= eng["max_seq_len"]
    assert t["clients"] * -(-(4096 + 1024) // eng["page_size"]) <= eng[
        "pool_pages"]
    # every request of a schedule has the file's lengths
    reqs = trafficgen.schedule(t, 2 ** 31 + 9, 40, t["ramp_s"])["requests"]
    assert len(reqs) == 512
    assert all(2048 <= r["prompt_len"] <= 4096
               and 512 <= r["max_tokens"] <= 1024 for r in reqs)
    assert {m["name"] for m in cell["end_to_end"]} == {"itl_p95_ms",
                                                       "setup_s"}
    moves = {m["name"]: m["moves"] for m in cell["per_layer"]}
    assert {"mla_attn_roofline", "moe_gated_expert_roofline",
            "mla_absorb_share", "attn_kernel_share",
            "steady_tick_device_ms", "mixed_tick_device_ms",
            "mixed_tick_share", "weight_slice_share", "moe_experts_share",
            "moe_shared_share", "moe_local_pair_share",
            "moe_tokens_per_hit_expert", "compiles_in_window"} <= set(moves)
    for name in ("kv_pool_move_share", "unscoped_device_share",
                 "attn_roofline", "qmatmul_roofline", "moe_expert_roofline",
                 "device_idle_share"):
        assert name not in moves, name
    assert set(moves.values()) == {"itl_p95_ms", "setup_s"}
    for m in cell["per_layer"]:
        assert os.path.exists(os.path.join(
            BENCH, "readers", m["reader"]["kind"] + ".py")), m["name"]
    bench = run.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    assert [w["name"] for w in bench["workloads"]].count(CELL) == 1
    assert [w["config"] for w in bench["workloads"]].count(CONFIG) == 1
    assert len(json.dumps(bench)) < 64 * 1024


def test_the_yardsticks_shapes():
    import shapes_axk1

    hf = run.hf_config(config())
    # gate, up and down: codes and one fp16 scale a block of 32
    assert shapes_axk1.expert_bytes(hf) == 3 * (
        7168 * 2048 / 2 + 7168 * 2048 / 32 * 2) == 24772608
    assert shapes_axk1.latent_row_bytes(hf) == 1152
    assert shapes_axk1.latent_page_bytes(hf, config()) == 128 * 1152
    assert shapes_axk1.walk_flops_per_token(hf) == 2 * 64 * (576 + 512)
    assert shapes_axk1.attention_layers(hf) == 8


def test_the_new_readers_read_a_scoped_trace_and_give_nothing_otherwise():
    from readers import (mla_attn_roofline, moe_gated_expert_roofline,
                         scope_share)

    pre = "jit(_ragged_tick)/tick/decode/layer/"
    ops = [["%fusion.1 = bf16[64,64,512] fusion(...)", 0.0, 0.125,
            pre + "attn/mla/absorb/dot_general", "x"],
           ["%ragged_paged_attention_latent.2 = bf16[64,64,512] "
            "custom-call(...)", 0.125, 0.25, pre + "attn/pallas_call", "x"],
           ["%fusion.3 = bf16[512,64,128] fusion(...)", 0.375, 0.125,
            "jit(_ragged_tick)/tick/prefill/layer/attn/mla/absorb/dot_general",
            "x"],
           ["%qmatmul_expert.4 = f32[12,64,4096] custom-call(...)", 0.5,
            0.25, pre + "mlp/moe/experts/pallas_call", "x"],
           ["%qmatmul_expert.5 = f32[12,64,7168] custom-call(...)", 0.75,
            0.25, pre + "mlp/moe/experts/pallas_call", "x"]]
    parsed = {"ops": ops, "spans": [], "modules": []}
    assert scope_share.scoped_seconds(parsed, "mla/absorb")[0] == 0.25
    assert scope_share.scoped_seconds(parsed, "moe/experts")[::2] == (0.5, 2)
    # no trace: nothing, and no raise (the parent's program, a CPU run)
    ctx = {"trace": None, "hf_config": run.hf_config(config()),
           "record": {"counters": {"start": {"metrics": {}},
                                   "end": {"metrics": {}}}}}
    for reader, params in (
            (scope_share, {"scope": "mla/absorb"}),
            (mla_attn_roofline, {"ops": "^ragged_paged_attention"}),
            (moe_gated_expert_roofline, {"scope": "moe/experts",
                                         "kernel": "qmatmul_expert",
                                         "calls": 2})):
        assert reader.read(params, ctx) is None


# -- the latent kernels, compiled by Mosaic for a described v5e --------------------

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


# name: (rows, tokens a row)
LATENT_SHAPES = {"decode": (64, 1), "chunk512": (1, 512), "chunk8": (64, 8),
                 "verify4": (64, 5)}


@pytest.mark.parametrize("kernel", ["walk", "write"])
@pytest.mark.parametrize("name", sorted(LATENT_SHAPES))
def test_mosaic_takes_the_latent_kernels(one_chip, name, kernel, monkeypatch):
    import jax
    import jax.numpy as jnp

    from ipex_llm_tpu import kv
    from ipex_llm_tpu.ops.pallas import paged_kv_write as pkw
    from ipex_llm_tpu.ops.pallas import ragged_paged_attention as rpa

    monkeypatch.setattr(rpa, "_interpret", lambda: False)
    monkeypatch.setattr(pkw, "_interpret", lambda: False)
    rows, t = LATENT_SHAPES[name]
    layers, pages, ps, heads, rank, rope = 8, 64, 128, 64, 512, 64
    bf16, i32 = jnp.bfloat16, jnp.int32

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    c_pool = arg((layers, pages, 1, ps, rank), bf16)
    r_pool = arg((layers, pages, 1, rope, ps), bf16)
    tables, lens = arg((rows, 32), i32), arg((rows,), i32)
    if kernel == "walk":
        fn = jax.jit(lambda ql, qr, c, r, tb, n, cl, layer:
                     rpa.latent_paged_sdpa(ql, qr, c, r, tb, n, cl, scale=0.1,
                                           layer=layer))
        args = (arg((rows, t, heads, rank), bf16),
                arg((rows, t, heads, rope), bf16), c_pool, r_pool, tables,
                lens, lens, arg((), i32))
        kernels = 1
    else:
        def write(c, r, tb, new_c, new_r, pos, layer):
            cache = kv.PagedKVCache(c, r, tb, jnp.zeros((), i32), latent=True)
            return pkw.paged_kv_write(cache, c, r, layer, new_c, new_r, pos)

        fn = jax.jit(write, donate_argnums=(0, 1))
        args = (c_pool, r_pool, tables, arg((rows, t, 1, rank), bf16),
                arg((rows, t, 1, rope), bf16), lens, arg((), i32))
        kernels = 2
    compiled = fn.lower(*args).compile()
    assert compiled.as_text().count("tpu_custom_call") == kernels
    # the pools are read and written where they lie
    assert compiled.memory_analysis().temp_size_in_bytes < 2 ** 20
