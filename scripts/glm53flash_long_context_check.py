#!/usr/bin/env python3
"""The builder's comparison for ``glm-5.3-flash.int4.ep8`` AT THE CELL'S
LENGTHS.  perfbench/run.py's own probes are 120 tokens: under 512 blocks, so
no selection happens, and two blocks of the recurrence in one piece, so no
state crosses a tick.  Here a 9,216-token prompt is prefilled in chunks and
then decoded for 64 tokens through ``ServingEngine`` (seven KDA layers handing
their state from block to block and tick to tick, two sparse-attention layers
choosing 512 of ~2,300 pooled blocks and always their tail, four streams, held
experts) three times:

- ALONE (512-token chunks),
- BESIDE 31 decoding rows (the cell's load: every tick carries the decode
  stage, the chunk is cut the same),
- CUT DIFFERENTLY: behind two other long prompts that prefill at the same
  time (the budget then gives each row 128- or 256-token pieces, and the rows
  wait their turn),

and the three must give IDENTICAL tokens and log-probabilities (bit for bit:
the prompt grid of ops/kda.py).  The first is held to
perfbench/references/glm5_next_text.py (the recurrence token by token, the
selection by ``top_k``) over the whole sequence; the control has to FAIL the
reference's limits: the reference through fp8 activations, teacher-forced on
the served tokens (activations rounded to bf16, the configuration's own
precision, shown beside it).  Before it, the same controls at the probes'
length, playing the server greedily: the second reading the limits were set
from (``--controls-only`` stops there).

    chiprun --timeout 3400 -- python3 scripts/glm53flash_long_context_check.py [seed]

Needs the chip (the published widths); prints one JSON object a line and
writes them to chiprun_out/glm53flash_long_context_check.jsonl."""

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

CONFIG = "glm-5.3-flash.int4.ep8"
PROMPT, NEW, BESIDE = 9216, 64, 31
OUT = os.path.join(ROOT, "chiprun_out", "glm53flash_long_context_check.jsonl")


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
    seed = int(seeds[0]) if seeds else 5000000101
    os.makedirs(os.path.dirname(OUT), exist_ok=True)
    device = run.require_tpu(1)
    config = run.load_json(os.path.join(ROOT, "perfbench", "configs",
                                        CONFIG + ".json"))
    hf = run.hf_config(config)
    ref = importlib.import_module("references.glm5_next_text")
    t0 = time.monotonic()
    model = weights.build_model(hf, config["weight_qtype"], seed)
    say(phase="model", seconds=time.monotonic() - t0, device=device,
        bytes=weights.tree_bytes(model.params))

    def ids_of(idx, n):
        return trafficgen.prompt_ids(seed, idx, n, hf["vocab_size"])

    # -- the limits' second reading, at the probes' length -------------------
    for i in range(2):
        ids = ids_of(10 ** 6 + i, run.PROBE_TOKENS)
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
    ids = ids_of(5 * 10 ** 6, PROMPT)
    served = {}

    def answer(req):
        return (list(stream_tokens(req, timeout=1500)),
                [float(x) for x in req.logprobs])

    try:
        t0 = time.monotonic()
        served["alone"] = answer(eng.submit(
            Request(prompt_ids=ids, max_new_tokens=NEW)))
        say(phase="served", how="alone", seconds=time.monotonic() - t0)
        t0 = time.monotonic()
        others = [eng.submit(Request(
            prompt_ids=ids_of(6 * 10 ** 6 + j, 96 + 8 * j),
            max_new_tokens=3000)) for j in range(BESIDE)]
        while not all(r.output_ids for r in others):
            time.sleep(0.05)
        served["beside_31"] = answer(eng.submit(
            Request(prompt_ids=ids, max_new_tokens=NEW)))
        decoding = sum(1 for r in others if r.finish_reason is None)
        for r in others:
            r.abort()
        say(phase="served", how="beside_31", seconds=time.monotonic() - t0,
            rows_still_decoding=decoding, kv=eng.kv_stats())
        t0 = time.monotonic()
        # two other long prompts first, so that ours waits and is cut into
        # other pieces (512 / 3 rows -> 128-token pieces, then 256, then 512)
        ahead = [eng.submit(Request(prompt_ids=ids_of(7 * 10 ** 6 + j,
                                                      3000 + 1000 * j),
                                    max_new_tokens=8)) for j in range(2)]
        req = eng.submit(Request(prompt_ids=ids, max_new_tokens=NEW))
        served["behind_2"] = answer(req)
        for r in ahead:
            list(stream_tokens(r, timeout=600))
        say(phase="served", how="behind_2", seconds=time.monotonic() - t0,
            metrics={k: v for k, v in eng.metrics.items()
                     if k.startswith(("kda_", "dsa_", "ssm_"))})
    finally:
        eng.stop()
    # the pool and both state buffers, for the reference
    eng.cache, eng._state_free, eng._state_held = None, [], []

    ok = True
    for how in ("beside_31", "behind_2"):
        same = served[how] == served["alone"]
        say(phase="identical", how=how, tokens=served[how][0]
            == served["alone"][0], bits=same)
        ok = ok and same
    first = PROMPT - 1
    tokens, lps = served["alone"]
    full = ids + tokens[:-1]
    exact = ref.logprobs(hf, model.params, full, first_row=first)
    res = ref.compare(exact, PROMPT - first, tokens, lps)
    ok = ok and res["ok"]
    say(phase="compare", how="alone", **brief(res))
    # the controls, teacher-forced on the served tokens
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
