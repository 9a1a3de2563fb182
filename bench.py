"""Single-chip TPU benchmark — the all-in-one runner equivalent.

Protocol follows the reference's perf harness (reference
dev/benchmark/all-in-one/config.yaml:12-15, run.py:145): batch 1, sym_int4,
1024 tokens in / 128 out, reporting decode tok/s and TTFT.  Model is a
Llama-2-7B-shaped random checkpoint (hidden 4096 / ffn 11008 / 32 layers)
built through the real quantize-on-load path — weights are synthesized
per-tensor so the benchmark is hermetic (no checkpoint download exists in
this environment) while exercising exactly the shapes of the reference's
headline single-GPU model class.

Prints ONE JSON line:
  {"metric": ..., "value": tok_s, "unit": "tok/s", "vs_baseline": ...}
stamped with the device as JAX reports it, plus effective HBM GB/s (decode is
bandwidth-bound — the roofline currency), a warm-start compile time for the
persistent compile cache, and the microbench and serving blocks.

It runs on a TPU only, in this one process: no accelerator is an error, and
a block that fails fails the run.  (chip_smoke.py is the standing proof that
the serving path starts on the chip; this file's protocol is ROADMAP S1/D5's
to rebuild.)

Baseline: BASELINE.md north-star = 20 decode tok/s/chip (Llama-3-70B INT4 on
v5e-16, i.e. per-chip parity target for the TP serving config).
"""

from __future__ import annotations

import json
import os
import time


def _build_model(size: str, qtype: str):
    from ipex_llm_tpu.models.random_init import llama_config, random_params

    if size == "7b":
        cfg = llama_config(
            hidden_size=4096, intermediate_size=11008, num_layers=32,
            num_heads=32, num_kv_heads=32, vocab_size=32000,
            max_position_embeddings=4096,
        )
    elif size == "1b":
        cfg = llama_config(
            hidden_size=2048, intermediate_size=5632, num_layers=22,
            num_heads=32, num_kv_heads=4, vocab_size=32000,
            max_position_embeddings=4096,
        )
    elif size == "tiny":  # the CPU replicas of serving_bench's chaos gate
        cfg = llama_config(
            hidden_size=256, intermediate_size=1024, num_layers=4,
            num_heads=8, num_kv_heads=4, vocab_size=1024,
        )
    else:
        raise ValueError(f"unknown size {size!r}: 7b, 1b or tiny")

    params = random_params(cfg, qtype=qtype)
    return cfg, params


def _param_bytes(params) -> int:
    import jax

    return sum(
        x.nbytes for x in jax.tree_util.tree_leaves(params)
        if hasattr(x, "nbytes")
    )


def run(size: str, qtype: str, n_in: int, n_out: int, batch: int,
        warm_start: bool = False):
    import jax
    import numpy as np

    from ipex_llm_tpu.generation import GenerationConfig, generate

    t0 = time.perf_counter()
    cfg, params = _build_model(size, qtype)
    build_s = time.perf_counter() - t0

    rng = np.random.default_rng(0)
    prompts = rng.integers(1, cfg.vocab_size, (batch, n_in)).astype(np.int32)
    gen = GenerationConfig(max_new_tokens=n_out, do_sample=False)

    # warmup: compile prefill + decode-loop programs
    t0 = time.perf_counter()
    res = generate(cfg, params, prompts, gen)
    compile_s = time.perf_counter() - t0
    # measured run
    res = generate(cfg, params, prompts, gen)

    decode_tok_s = batch / res.rest_token_s if res.rest_token_s > 0 else 0.0

    # effective HBM bandwidth: every decode step reads all packed weights
    # once plus the live KV (bf16) — the bandwidth-bound decode roofline
    kv_bytes = (2 * cfg.num_layers * cfg.num_kv_heads * cfg.head_dim
                * (n_in + n_out / 2) * 2 * batch)
    eff_gbs = ((_param_bytes(params) + kv_bytes) / res.rest_token_s / 1e9
               if res.rest_token_s > 0 else 0.0)

    warm_compile_s = None
    if warm_start:
        # drop in-memory executables but keep the persistent compile cache:
        # re-tracing now proves (or disproves) the warm-start story the
        # cache exists for
        jax.clear_caches()
        t0 = time.perf_counter()
        generate(cfg, params, prompts, gen)
        warm_compile_s = time.perf_counter() - t0

    return {
        "cfg": cfg,
        "params": params,
        "build_s": build_s,
        "compile_s": compile_s,
        "warm_compile_s": warm_compile_s,
        "ttft_s": res.first_token_s,
        "decode_tok_s": decode_tok_s,
        "eff_hbm_gbs": eff_gbs,
    }


def main():
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(
            f"bench: needs a TPU, but jax.devices()[0] is platform="
            f"{dev.platform!r} kind={dev.device_kind!r}")
    size = os.environ.get("BENCH_SIZE", "7b")
    qtype = os.environ.get("BENCH_QTYPE", "sym_int4")
    n_in = int(os.environ.get("BENCH_IN", "1024"))
    n_out = int(os.environ.get("BENCH_OUT", "128"))
    batch = int(os.environ.get("BENCH_BATCH", "1"))

    r = run(size, qtype, n_in, n_out, batch, warm_start=True)

    micro = []
    if os.environ.get("BENCH_MICRO", "1") == "1":
        from benchmark.microbench import collect

        micro = collect(iters=20)

    serving = []
    if os.environ.get("BENCH_SERVING", "1") == "1":
        # the north-star is a SERVING number: aggregate tok/s + TTFT under
        # concurrency through the paged engine.  Reuses the already-built
        # model (a second 7B build would double HBM residency on the chip).
        from benchmark.serving_bench import collect as serve_collect

        serving = serve_collect(cfg=r["cfg"], params=r["params"],
                                levels=(1, 4, 16), horizons=(1, 4, 8))

    baseline = 20.0  # BASELINE.md: >=20 decode tok/s/chip north-star
    line = {
        "metric": f"llama_{size}_{qtype}_decode_tok_s_{n_in}in_{n_out}out_b{batch}",
        "value": round(r["decode_tok_s"], 3),
        "unit": "tok/s",
        "vs_baseline": round(r["decode_tok_s"] / baseline, 3),
        "ttft_s": round(r["ttft_s"], 4),
        "compile_s": round(r["compile_s"], 1),
        "device": {"platform": dev.platform, "kind": dev.device_kind,
                   "count": len(jax.devices())},
        "eff_hbm_gbs": round(r["eff_hbm_gbs"], 1),
    }
    if r["warm_compile_s"] is not None:
        line["warm_compile_s"] = round(r["warm_compile_s"], 1)
    if micro:
        line["microbench"] = micro
    if serving:
        line["serving"] = serving
    print(json.dumps(line))


if __name__ == "__main__":
    main()
