#!/usr/bin/env python3
"""The builder's comparison for ``falcon-h1-34b-instruct.int4.l24`` AT THE
CELL'S SIZES (perfbench/run.py's own probes are 120 tokens and 8 decoded,
under one page): through ``ServingEngine`` at the published widths and the
cell's engine sizes, among 15 other decoding rows (14 and each other),

(a) a 512-token prompt of ONE chunk and
(b) a 600-token prompt cut into TWO chunks (512 + 88),

each decoded to the cell's longest output (1536 tokens: the state has then
been advanced ~2,000 times and the row's cache crosses 16 pages), the
server's log-probabilities against perfbench/references/falcon_h1.py at
COMPARED positions spread over the whole answer (windows of 8, the size
``correct`` compares); SERVED_PROBES probes of run.py's own size; the same
probe ALONE and UNDER LOAD returning identical tokens; and the controls, the
reference against itself at another precision, teacher-forced on the served
tokens: ``bf16`` (the configuration's own: has to pass in every window) and
the two that have to FAIL the reference's limits over the 64 positions,
``fp8`` (activations one precision down) and ``state_fp8``; ``state_bf16``
is printed and NOT held to anything:
a bfloat16 state is one more rounding of 2**-9 among the dozen a block makes
and no log-probability tells it from float32 (references/falcon_h1.py).
The state's precision is held where it can be seen: the kernel on the device
at the published shape against the float32 recurrence over the same inputs
(STATE_TOL), which a bfloat16 state has to miss.

    chiprun --timeout 3000 -- python3 scripts/falconh1_reasoning_check.py [seed]

Needs the chip (the published widths); prints one JSON object a line and
writes them to chiprun_out/falconh1_reasoning_check.jsonl."""

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

CONFIG = "falcon-h1-34b-instruct.int4.l24"
NEW, BESIDE = 1536, 14
PROMPTS = {"one_chunk": 512, "two_chunks": 600}
WINDOWS = 8                 # windows of 8 decoded positions held to the reference
SERVED_PROBES = 4           # probes of run.py's size through the engine
MUST_FAIL = ("fp8", "state_fp8")
STATE_TOL = 1e-4            # the kernel's state against float32, relative
OUT = os.path.join(ROOT, "chiprun_out", "falconh1_reasoning_check.jsonl")


def say(**record):
    line = json.dumps(record)
    print(line, flush=True)
    with open(OUT, "a") as f:
        f.write(line + "\n")


def brief(res: dict) -> dict:
    return {k: v for k, v in res.items() if k != "errors"}


def state_check(ref, cfg, seed: int) -> bool:
    """``ops/ssm.py``'s recurrence as the chip runs it (the ``ssm_scan``
    kernel, one block's stack of 16 rows at the published shape, 64 tokens)
    against the reference's recurrence over the same inputs in float32, and
    with the state and decay through bfloat16, which has to miss
    STATE_TOL."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ipex_llm_tpu.ops import ssm

    heads, p = cfg.ssm_num_heads, cfg.ssm_head_dim
    groups, n = cfg.ssm_n_groups, cfg.ssm_state_size
    rows, t = 16, 64
    k = jax.random.split(weights.seed_key(seed + 2), 5)
    x = jax.nn.silu(jax.random.normal(k[0], (rows, t, heads, p)))
    b = jax.nn.silu(jax.random.normal(k[1], (rows, t, groups, n)))
    c = jax.nn.silu(jax.random.normal(k[2], (rows, t, groups, n)))
    dt = jnp.exp(jax.random.uniform(k[3], (rows, t, heads), jnp.float32,
                                    jnp.log(1e-3), jnp.log(1e-1)))
    a = -jax.random.uniform(k[4], (heads,), jnp.float32, 1.0, 16.0)
    stack = jnp.zeros((1, rows, heads, p, n), jnp.float32)
    lens = jnp.full((rows,), t, jnp.int32)
    y, out = jax.jit(lambda s, *v: ssm._scan(
        cfg, s, s, jnp.int32(0), None, None, *v))(stack, x, dt, a, b, c, lens)
    ok = True
    for state in (None, "bf16"):
        want_y, want_h = jax.jit(
            lambda *v: jax.vmap(lambda x_, b_, c_, dt_: ref.recurrence(
                x_, b_, c_, dt_, v[4], state))(*v[:4]))(x, b, c, dt, a)
        err_h = float(jnp.max(jnp.abs(out[0] - want_h))
                      / jnp.max(jnp.abs(want_h)))
        err_y = float(jnp.max(jnp.abs(y - want_y)) / jnp.max(jnp.abs(want_y)))
        agrees = max(err_h, err_y) < STATE_TOL
        say(phase="state", against=state or "float32", rel_err_h=err_h,
            rel_err_y=err_y, tol=STATE_TOL, agrees=agrees,
            must_agree=state is None,
            state_dtype=str(np.asarray(out).dtype))
        ok = ok and agrees == (state is None)
    return ok


def main(argv) -> int:
    seeds = [a for a in argv if not a.startswith("--")]
    seed = int(seeds[0]) if seeds else 4600000101
    os.makedirs(os.path.dirname(OUT), exist_ok=True)
    device = run.require_tpu(1)
    config = run.load_json(os.path.join(ROOT, "perfbench", "configs",
                                        CONFIG + ".json"))
    hf = run.hf_config(config)
    ref = importlib.import_module("references.falcon_h1")
    t0 = time.monotonic()
    model = weights.build_model(hf, config["weight_qtype"], seed)
    say(phase="model", seed=seed, seconds=time.monotonic() - t0, device=device,
        bytes=weights.tree_bytes(model.params))
    ok = state_check(ref, model.config, seed)

    from ipex_llm_tpu.serving.engine import (EngineConfig, Request,
                                             ServingEngine, stream_tokens)

    vocab = hf["vocab_size"]
    eng = ServingEngine(model.config, model.params,
                        EngineConfig(**config["engine"])).start()
    served, probes = {}, []
    try:
        for i in range(SERVED_PROBES):
            p_ids = trafficgen.prompt_ids(seed, 10 ** 6 + i, run.PROBE_TOKENS,
                                          vocab)
            req = eng.submit(Request(prompt_ids=p_ids,
                                     max_new_tokens=run.PROBE_NEW_TOKENS))
            probes.append((p_ids, list(stream_tokens(req, timeout=1500)),
                           [float(x) for x in req.logprobs]))
        t0 = time.monotonic()
        others = [eng.submit(Request(
            prompt_ids=trafficgen.prompt_ids(seed, 6 * 10 ** 6 + j,
                                             128 + 24 * j, vocab),
            max_new_tokens=2000 - 24 * j)) for j in range(BESIDE)]
        while not all(r.output_ids for r in others):
            time.sleep(0.05)
        say(phase="beside", rows=BESIDE, seconds=time.monotonic() - t0)
        # the first probe again, UNDER LOAD: the same tokens as alone
        p_ids, alone, _ = probes[0]
        req = eng.submit(Request(prompt_ids=p_ids,
                                 max_new_tokens=run.PROBE_NEW_TOKENS))
        loaded = list(stream_tokens(req, timeout=1500))
        say(phase="batch_invariance", alone=alone, under_load=loaded,
            identical=loaded == alone)
        ok = ok and loaded == alone
        # both at once: each decodes among 15 other rows (14 and the other)
        t0 = time.monotonic()
        sent = {}
        for how, length in PROMPTS.items():
            ids = trafficgen.prompt_ids(seed, 5 * 10 ** 6 + length, length,
                                        vocab)
            sent[how] = (ids, eng.submit(Request(prompt_ids=ids,
                                                 max_new_tokens=NEW)))
        for how, (ids, req) in sent.items():
            toks = list(stream_tokens(req, timeout=1500))
            served[how] = (ids, toks, [float(x) for x in req.logprobs])
            say(phase="served", how=how, prompt=len(ids), tokens=len(toks),
                seconds=time.monotonic() - t0,
                rows_still_decoding=sum(1 for r in others
                                        if r.finish_reason is None),
                kv=eng.kv_stats())
        for r in others:
            r.abort()
    finally:
        eng.stop()
    eng.cache = None        # the pool and the state, for the reference
    eng._state_free = eng._state_held = []

    for i, (p_ids, tokens, lps) in enumerate(probes):
        full = p_ids + tokens[:-1]
        exact = ref.logprobs(hf, model.params, full)
        res = ref.compare(exact, len(p_ids), tokens, lps)
        ok = ok and res["ok"]
        say(phase="served_probe", probe=i, **brief(res))
        # the limits' second reading, at the size ``correct`` compares
        for lower in ("bf16", "state_bf16", *MUST_FAIL):
            low = ref.logprobs(hf, model.params, full, lower=lower)
            res = ref.compare(exact, len(p_ids), tokens, [
                float(low[len(p_ids) - 1 + j][t])
                for j, t in enumerate(tokens)])
            say(phase="probe_control", probe=i, lower=lower,
                must_fail=lower in MUST_FAIL, **brief(res))
            if lower in MUST_FAIL:
                ok = ok and not res["ok"]
    for how, (ids, tokens, lps) in served.items():
        full = ids + tokens[:-1]
        # WINDOWS windows of 8 decoded positions, spread from the first
        # decoded token to the last
        starts = [round(i * (len(tokens) - 8) / (WINDOWS - 1))
                  for i in range(WINDOWS)]
        at = [s + j for s in starts for j in range(8)]
        rows = [len(ids) - 1 + j for j in at]
        exact = ref.logprobs(hf, model.params, full, rows=rows)
        for w, s in enumerate(starts):
            res = ref.compare(exact[8 * w: 8 * w + 8], 1, tokens[s: s + 8],
                              lps[s: s + 8])
            ok = ok and res["ok"]
            say(phase="compare", how=how, decoded_from=s,
                cache_tokens=len(ids) + s, **brief(res))
        # the controls, teacher-forced on the served tokens
        for lower in ("bf16", "state_bf16", *MUST_FAIL):
            low = ref.logprobs(hf, model.params, full, lower=lower, rows=rows)
            low_lps = [float(low[i][tokens[j]]) for i, j in enumerate(at)]
            # over all the positions at once, and a window of 8 at a time
            whole = ref.compare(exact, 1, [tokens[j] for j in at], low_lps)
            wins = [ref.compare(exact[8 * w: 8 * w + 8], 1, tokens[s: s + 8],
                                low_lps[8 * w: 8 * w + 8])
                    for w, s in enumerate(starts)]
            means = [r["logprob_err_mean"] for r in wins]
            say(phase="control", how=how, lower=lower,
                must_fail=lower in MUST_FAIL, positions=len(at),
                windows_failed=sum(1 for r in wins if not r["ok"]),
                window_mean_min=min(means), window_mean_max=max(means),
                **brief(whole))
            if lower in MUST_FAIL:
                ok = ok and not whole["ok"]
            elif lower == "bf16":
                ok = ok and all(r["ok"] for r in wins)
    say(phase="done", ok=ok)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
