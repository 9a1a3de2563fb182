#!/usr/bin/env python3
"""The builder's comparison for ``glm-5.2.int4.ep16`` AT THE CELL'S LENGTHS.
perfbench/run.py's own probes are 120 tokens, under ``index_topk``: they select
every token and reach NO selection.  Here a 16384-token prompt is served in
512-token chunks, then 64 decoded tokens, ALONE and BESIDE 15 decoding rows,
through ``ServingEngine`` (the sparse latent pool: three indexers scoring up to
16k cached tokens a query, the top-2048 selection shared by the layers after
each, the walk under a chunk's mask, a decode row's gather, the held expert
layers) against perfbench/references/glm_moe_dsa.py computed over the whole
sequence in query blocks; and the control that has to FAIL the reference's
limits: the reference through fp8 activations (activations rounded to bf16
are shown beside it: the configuration's own precision).  Before it, the same
controls at the probes' length, playing the server greedily, which is the
second reading the limits were set from (``--controls-only`` stops there).

    chiprun --timeout 3400 -- python3 scripts/glm52_long_document_check.py [seed]

Needs the chip (the published widths); prints one JSON object a line and
writes them to chiprun_out/glm52_long_document_check.jsonl."""

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

CONFIG = "glm-5.2.int4.ep16"
PROMPT, NEW, BESIDE = 16384, 64, 15
OUT = os.path.join(ROOT, "chiprun_out", "glm52_long_document_check.jsonl")


def say(**record):
    line = json.dumps(record)
    print(line, flush=True)
    with open(OUT, "a") as f:
        f.write(line + "\n")


def brief(res: dict) -> dict:
    return {k: v for k, v in res.items() if k not in ("errors", "gaps")}


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
        rows = ref.logprobs(hf, params, seq, lower=lower)[0]
        row = rows[len(ids) + len(tokens) - 1]
        tokens.append(int(np.argmax(row)))
        lps.append(float(row[tokens[-1]]))
    return brief(ref.compare(ref.logprobs(hf, params, ids + tokens[:-1]),
                             len(ids), tokens, lps))


def main(argv) -> int:
    seeds = [a for a in argv if not a.startswith("--")]
    seed = int(seeds[0]) if seeds else 3900000101
    os.makedirs(os.path.dirname(OUT), exist_ok=True)
    device = run.require_tpu(1)
    config = run.load_json(os.path.join(ROOT, "perfbench", "configs",
                                        CONFIG + ".json"))
    hf = run.hf_config(config)
    ref = importlib.import_module("references.glm_moe_dsa")
    t0 = time.monotonic()
    model = weights.build_model(hf, config["weight_qtype"], seed)
    say(phase="model", seconds=time.monotonic() - t0, device=device,
        bytes=weights.tree_bytes(model.params))

    # -- the limits' second reading, at the probes' length -------------------
    for i in range(2):
        ids = trafficgen.prompt_ids(seed, 10 ** 6 + i, run.PROBE_TOKENS,
                                    hf["vocab_size"])
        for lower in ("bf16", "fp8"):
            say(phase="probe_control", probe=i, lower=lower,
                **greedy_control(ref, hf, model.params, ids, lower,
                                 run.PROBE_NEW_TOKENS))

    if "--controls-only" in argv:
        return 0
    # -- the served path at the cell's lengths ---------------------------------
    from ipex_llm_tpu.serving.engine import (EngineConfig, Request,
                                             ServingEngine, stream_tokens)

    eng = ServingEngine(model.config, model.params,
                        EngineConfig(**config["engine"])).start()
    ids = trafficgen.prompt_ids(seed, 5 * 10 ** 6, PROMPT, hf["vocab_size"])
    served = {}
    try:
        t0 = time.monotonic()
        req = eng.submit(Request(prompt_ids=ids, max_new_tokens=NEW))
        served["alone"] = (list(stream_tokens(req, timeout=1500)),
                           [float(x) for x in req.logprobs])
        say(phase="served", how="alone", seconds=time.monotonic() - t0)
        t0 = time.monotonic()
        others = [eng.submit(Request(
            prompt_ids=trafficgen.prompt_ids(seed, 6 * 10 ** 6 + j, 96 + 8 * j,
                                             hf["vocab_size"]),
            max_new_tokens=2000)) for j in range(BESIDE)]
        while not all(r.output_ids for r in others):
            time.sleep(0.05)
        req = eng.submit(Request(prompt_ids=ids, max_new_tokens=NEW))
        served["beside_15"] = (list(stream_tokens(req, timeout=1500)),
                               [float(x) for x in req.logprobs])
        decoding = sum(1 for r in others if r.finish_reason is None)
        for r in others:
            r.abort()
        say(phase="served", how="beside_15", seconds=time.monotonic() - t0,
            rows_still_decoding=decoding,
            kv=eng.kv_stats(), same_tokens_as_alone=(
                served["beside_15"][0] == served["alone"][0]))
    finally:
        eng.stop()
    eng.cache = None                      # the pool's 3.4 GB, for the reference

    ok = True
    first = PROMPT - 1
    for how, (tokens, lps) in served.items():
        full = ids + tokens[:-1]
        res = ref.compare(ref.logprobs(hf, model.params, full,
                                       first_row=first), PROMPT - first,
                          tokens, lps)
        ok = ok and res["ok"]
        say(phase="compare", how=how, **brief(res))
    # the controls, teacher-forced on the served tokens: the reference
    # through fp8 activations has to FAIL the float32 reference's limits;
    # activations rounded to bf16 between operations, the configuration's
    # own precision, are shown beside it (their selections differ from the
    # float32 ones at the edge, as the served path's do)
    tokens, _ = served["alone"]
    full = ids + tokens[:-1]
    exact = ref.logprobs(hf, model.params, full, first_row=first)
    for lower in ("bf16", "fp8"):
        low_rows = ref.logprobs(hf, model.params, full, lower=lower,
                                first_row=first)[0]
        low_lps = [float(low_rows[j][tok]) for j, tok in enumerate(tokens)]
        res = ref.compare(exact, PROMPT - first, tokens, low_lps)
        say(phase="control", lower=lower, must_fail=lower == "fp8",
            **brief(res))
        if lower == "fp8":
            ok = ok and not res["ok"]
    say(phase="done", ok=ok)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
