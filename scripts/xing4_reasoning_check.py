#!/usr/bin/env python3
"""The builder's comparison for ``xing4.0-29b-a4b.int4.ep2`` AT THE CELL'S
SIZES (perfbench/run.py's own probes are 120 tokens and 8 decoded, under one
page): a 512-token prompt and over a thousand decoded tokens through
``ServingEngine`` (the latent pool over 12 pages, the held expert layers, four
hyper-connected streams through all 40 layers), ALONE and BESIDE 15 decoding
rows, against perfbench/references/xing4_0.py computed over the whole sequence
in query blocks; and the controls.  At the probes' length, playing the server
greedily (the second reading the limits were set from; ``--controls-only``
stops there): bf16 rounding (the configuration's own precision), the
hyper-connections' coefficients in bfloat16, and the three that have to FAIL
the reference's limits: fp8 activations, a dropped dynamic term (``P``
zeroed) and the held experts taken for their neighbours.  SERVED_PROBES more
probes of run.py's own size (120 tokens in, 8 out) go through the engine: the
first reading, eight at a call.  Every 128-position comparison also gives its
16 windows of 8 positions, the size ``correct`` compares (``windows8``: the
smallest and the largest window's mean and largest error).  The coefficients
have a comparison of their own too, at the published width:
``ipex_llm_tpu/ops/hyper.py`` on the device against the reference's equations
to COEF_TOL, which the reference's own coefficients in bfloat16 have to miss
(the TICK's program is held to float32 coefficients in tests/test_xing4.py).

    chiprun --timeout 3000 -- python3 scripts/xing4_reasoning_check.py [seed]

Needs the chip (the published widths); prints one JSON object a line and
writes them to chiprun_out/xing4_reasoning_check.jsonl."""

from __future__ import annotations

import importlib
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [os.path.join(ROOT, "perfbench"), ROOT]

import run  # noqa: E402
import trafficgen  # noqa: E402
import weights  # noqa: E402

CONFIG = "xing4.0-29b-a4b.int4.ep2"
PROMPT, NEW, BESIDE = 512, 1100, 15
COMPARED = 128              # decoded positions held to the reference: the last
SERVED_PROBES = 8           # probes of run.py's size through the engine
MUST_FAIL = ("fp8", "static_coef", "expert_shift")
COEF_TOL = 1e-4             # float32 coefficients at precision "highest"
OUT = os.path.join(ROOT, "chiprun_out", "xing4_reasoning_check.jsonl")


def say(**record):
    line = json.dumps(record)
    print(line, flush=True)
    with open(OUT, "a") as f:
        f.write(line + "\n")


def brief(res: dict) -> dict:
    out = {k: v for k, v in res.items() if k not in ("errors", "gaps")}
    errs = res["errors"]
    if len(errs) >= 16:
        wins = [errs[i:i + 8] for i in range(0, len(errs) - 7, 8)]
        means = [sum(w) / 8 for w in wins]
        out["windows8"] = {"mean_min": min(means), "mean_max": max(means),
                           "max_min": min(map(max, wins)),
                           "max_max": max(map(max, wins))}
    return out


def greedy_control(ref, hf, params, ids, lower, n):
    """``lower`` playing the server: n greedy tokens with its own
    log-probabilities, held to the float32 reference.  Every forward runs
    over the same length (the tail padded: a causal model's earlier rows do
    not see it), so each form compiles once."""
    import numpy as np

    total = len(ids) + n - 1
    tokens, lps = [], []
    for _ in range(n):
        seq = (ids + tokens + [3] * total)[:total]
        rows, _ = ref.logprobs(hf, params, seq, lower=lower)
        row = rows[len(ids) + len(tokens) - 1]
        tokens.append(int(np.argmax(row)))
        lps.append(float(row[tokens[-1]]))
    return brief(ref.compare(ref.logprobs(hf, params, ids + tokens[:-1]),
                             len(ids), tokens, lps))


def coefficient_check(ref, hf, cfg, params, seed) -> bool:
    """The program's coefficients on the device against the reference's, one
    sublayer's tensors at the published width over 512 tokens of bf16
    streams; and the control that has to miss COEF_TOL."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ipex_llm_tpu.ops import hyper

    tree, layer = params["layers"], 7
    names = [f"hc_mlp_{k}" for k in ("proj", "scale", "bias")]
    lp = [tree[name][layer] for name in names]
    x = jax.random.normal(weights.seed_key(seed + 1),
                          (cfg.hc_mult, 1, 512, cfg.hidden_size),
                          jnp.float32).astype(jnp.bfloat16)
    pre, post, res = jax.jit(lambda *a: hyper.coefficients(
        cfg, dict(zip(names, a[:3])), "mlp", a[3]))(*lp, x)
    got = (np.asarray(pre[:, 0]).T, np.asarray(post[:, 0]).T,
           np.moveaxis(np.asarray(res[:, :, 0]), -1, 0))
    xt = jnp.moveaxis(x[:, 0], 0, 1).astype(jnp.float32)
    hyper_args = ref.settings(hf, params)["hyper"]
    ok = True
    for lower in (None, "coef_bf16"):
        with jax.default_matmul_precision("highest"):
            want = ref.hyper_coefficients(xt, *lp, *hyper_args, lower)
        worst = max(float(np.max(np.abs(g - np.asarray(w))))
                    for g, w in zip(got, want))
        agrees = worst < COEF_TOL
        say(phase="coefficients", against=lower or "float32",
            worst_abs_diff=worst, tol=COEF_TOL, agrees=agrees,
            must_agree=lower is None,
            rows_sum_worst=float(np.max(np.abs(got[2].sum(2) - 1))),
            cols_sum_worst=float(np.max(np.abs(got[2].sum(1) - 1))))
        ok = ok and agrees == (lower is None)
    return ok


def main(argv) -> int:
    seeds = [a for a in argv if not a.startswith("--")]
    seed = int(seeds[0]) if seeds else 4400000101
    os.makedirs(os.path.dirname(OUT), exist_ok=True)
    device = run.require_tpu(1)
    config = run.load_json(os.path.join(ROOT, "perfbench", "configs",
                                        CONFIG + ".json"))
    hf = run.hf_config(config)
    ref = importlib.import_module("references.xing4_0")
    t0 = time.monotonic()
    model = weights.build_model(hf, config["weight_qtype"], seed)
    say(phase="model", seed=seed, seconds=time.monotonic() - t0, device=device,
        bytes=weights.tree_bytes(model.params))

    ok = coefficient_check(ref, hf, model.config, model.params, seed)

    # -- the limits' second reading, at the probes' length -------------------
    for i in range(2):
        ids = trafficgen.prompt_ids(seed, 10 ** 6 + i, run.PROBE_TOKENS,
                                    hf["vocab_size"])
        for lower in ("bf16", "coef_bf16", *MUST_FAIL):
            res = greedy_control(ref, hf, model.params, ids, lower,
                                 run.PROBE_NEW_TOKENS)
            must_fail = lower in MUST_FAIL
            say(phase="probe_control", probe=i, lower=lower,
                must_fail=must_fail, **res)
            if must_fail:
                ok = ok and not res["ok"]

    if "--controls-only" in argv:
        say(phase="done", ok=ok)
        return 0 if ok else 1
    # -- the served path at the cell's sizes ------------------------------------
    from ipex_llm_tpu.serving.engine import (EngineConfig, Request,
                                             ServingEngine, stream_tokens)

    eng = ServingEngine(model.config, model.params,
                        EngineConfig(**config["engine"])).start()
    ids = trafficgen.prompt_ids(seed, 5 * 10 ** 6, PROMPT, hf["vocab_size"])
    served, probes = {}, []
    try:
        for i in range(SERVED_PROBES):
            p_ids = trafficgen.prompt_ids(seed, 10 ** 6 + i, run.PROBE_TOKENS,
                                          hf["vocab_size"])
            req = eng.submit(Request(prompt_ids=p_ids,
                                     max_new_tokens=run.PROBE_NEW_TOKENS))
            probes.append((p_ids, list(stream_tokens(req, timeout=1500)),
                           [float(x) for x in req.logprobs]))
        t0 = time.monotonic()
        req = eng.submit(Request(prompt_ids=ids, max_new_tokens=NEW))
        served["alone"] = (list(stream_tokens(req, timeout=1500)),
                           [float(x) for x in req.logprobs])
        say(phase="served", how="alone", seconds=time.monotonic() - t0,
            tokens=len(served["alone"][0]))
        t0 = time.monotonic()
        others = [eng.submit(Request(
            prompt_ids=trafficgen.prompt_ids(seed, 6 * 10 ** 6 + j,
                                             128 + 24 * j, hf["vocab_size"]),
            max_new_tokens=1600)) for j in range(BESIDE)]
        while not all(r.output_ids for r in others):
            time.sleep(0.05)
        req = eng.submit(Request(prompt_ids=ids, max_new_tokens=NEW))
        served["beside_15"] = (list(stream_tokens(req, timeout=1500)),
                               [float(x) for x in req.logprobs])
        decoding = sum(1 for r in others if r.finish_reason is None)
        for r in others:
            r.abort()
        same = next((j for j, (a, b) in enumerate(zip(
            served["beside_15"][0], served["alone"][0])) if a != b), NEW)
        say(phase="served", how="beside_15", seconds=time.monotonic() - t0,
            rows_still_decoding=decoding, kv=eng.kv_stats(),
            same_tokens_as_alone_up_to=same,
            mhc_tokens_mixed_total=eng.metrics.get("mhc_tokens_mixed_total"))
    finally:
        eng.stop()
    eng.cache = None                      # the pool's 2.3 GB, for the reference

    for i, (p_ids, tokens, lps) in enumerate(probes):
        res = ref.compare(ref.logprobs(hf, model.params, p_ids + tokens[:-1]),
                          len(p_ids), tokens, lps)
        ok = ok and res["ok"]
        say(phase="served_probe", probe=i, **brief(res))
    # the reference over the whole sequence; the LAST ``COMPARED`` decoded
    # positions are held to it (those that crossed the most pages of pool)
    first = PROMPT + NEW - 1 - COMPARED
    for how, (tokens, lps) in served.items():
        full = ids + tokens[:-1]
        res = ref.compare(ref.logprobs(hf, model.params, full,
                                       first_row=first), 1,
                          tokens[-COMPARED:], lps[-COMPARED:])
        ok = ok and res["ok"]
        say(phase="compare", how=how, positions=COMPARED,
            from_position=first, **brief(res))
    # the controls, teacher-forced on the served tokens: the reference
    # through fp8 activations has to FAIL the float32 reference's limits
    tokens, _ = served["alone"]
    full = ids + tokens[:-1]
    exact = ref.logprobs(hf, model.params, full, first_row=first)
    for lower in ("bf16", "coef_bf16", "fp8", "expert_shift"):
        low_rows, _ = ref.logprobs(hf, model.params, full, lower=lower,
                                   first_row=first)
        low_lps = [float(low_rows[j][tok])
                   for j, tok in enumerate(tokens[-COMPARED:])]
        res = ref.compare(exact, 1, tokens[-COMPARED:], low_lps)
        say(phase="control", lower=lower, must_fail=lower in MUST_FAIL,
            **brief(res))
        if lower in MUST_FAIL:
            ok = ok and not res["ok"]
    say(phase="done", ok=ok)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
