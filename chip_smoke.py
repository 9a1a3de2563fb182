#!/usr/bin/env python3
"""chip_smoke.py — the standing proof that the serving path starts on the chip.

    python3 chip_smoke.py          (from the repo root, on a machine with a TPU)

One process drives the system's main path once, through the entry points a
user calls: ``build_server`` -> ``ServingEngine`` -> the aiohttp app on a
loopback port, answered over HTTP.  The model is Mistral-7B-v0.3 at its full
published width and depth with sym_int4 weights, random from a seed, built
through the real quantize-on-load path (``random_params`` -> ``build_params``);
only the checkpoint reader is skipped.  Around the server it checks that every
Pallas kernel family on the serving and ``generate()`` paths was compiled by
Mosaic and agrees with its XLA reference at this model's shapes.

There is no CPU mode, size switch or environment override: without a TPU the
script exits non-zero and prints no result.  Tests import the phase functions
below with a tiny configuration instead (tests/test_chip_smoke.py).  Progress
goes to stderr.  Stdout is two JSON lines: the record of the run (versions,
set-up times, kernel errors, health, tokens), then, last, the result
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}`` with
exactly those keys.  Times in the record are set-up times (build, compile),
not performance.
"""

from __future__ import annotations

import asyncio
import json
import sys
import time

# The language-model keys of Mistral-7B-v0.3's published config.json
# (https://huggingface.co/mistralai/Mistral-7B-v0.3/blob/main/config.json).
# Nothing is cut: 32 layers, every width as published.  ``eos_token_id`` (2)
# is left out on purpose: random weights give EOS no meaning, and without it
# "length" is the only legitimate way for a request to finish, so a request
# that ends any other way is a failure the smoke can see.
MISTRAL_7B_V03 = {
    "model_type": "mistral",
    "hidden_size": 4096,
    "intermediate_size": 14336,
    "num_hidden_layers": 32,
    "num_attention_heads": 32,
    "num_key_value_heads": 8,
    "hidden_act": "silu",
    "max_position_embeddings": 32768,
    "rms_norm_eps": 1e-05,
    "rope_theta": 1000000.0,
    "sliding_window": None,
    "tie_word_embeddings": False,
    "vocab_size": 32768,
}
WEIGHT_QTYPE = "sym_int4"
SEED = 0
# 16 rows x 4096 slots in 128-slot pages: the auto-sized pool is 256 pages
# of 16 MiB = 4 GiB of bf16 KV beside ~4.1 GB of packed weights on a 16 GB chip
ENGINE = {"max_rows": 16, "max_seq_len": 4096, "page_size": 128}
# prompt lengths (tokens) of the traffic: [0] is streamed and repeated in the
# wave, [1] is sent alone and repeated in the wave (two full pages -> a
# prefix-cache hit), the rest are the wave's own
PROMPT_LENS = (100, 300, 260, 520, 900, 700)
MAX_TOKENS = (32, 48, 40, 64, 32, 56)

# Kernel-vs-reference tolerances, as a fraction of the reference's largest
# magnitude.  qmatmul: both sides multiply the same bf16 operands and
# accumulate in f32, so they differ by summation order and one rounding of
# the result to bf16 (2^-8 relative); two bf16 ulps of the largest output
# bounds that, while a wrong nibble order, scale or tile gives O(1).
QMATMUL_TOL = 2.0 ** -7
# attention: the kernels round the softmax weights to bf16 before the PV
# matmul and the result to bf16; the reference (float32, precision "highest")
# does neither.  Each is <= 2^-8 relative, so four bf16 ulps of the largest
# output bounds the sum; a wrong mask, page or head mapping gives O(1).
ATTN_TOL = 2.0 ** -6

_T0 = time.perf_counter()


def log(msg: str) -> None:
    print(f"[chip_smoke +{time.perf_counter() - _T0:6.1f}s] {msg}",
          file=sys.stderr, flush=True)


class SmokeFailure(AssertionError):
    """A phase found the system wrong; main() turns it into exit code 1."""


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


# -- phase 0: the device ------------------------------------------------------

def require_tpu() -> dict:
    """The device as JAX reports it; refuses anything but a TPU."""
    import jax

    devs = jax.devices()
    d = devs[0]
    if d.platform != "tpu":
        raise SystemExit(
            f"chip_smoke: needs a TPU, but jax.devices()[0] is platform="
            f"{d.platform!r} kind={d.device_kind!r} ({len(devs)} device(s)); "
            "run it through the chip tool")
    return {"platform": d.platform, "kind": d.device_kind, "count": len(devs)}


# -- phase 1: the model --------------------------------------------------------

def build_native_quantizer() -> str:
    """Compile csrc/quantize.cpp on THIS machine (forced, so a library that
    came along with a copied tree is never the one loaded); no compiler is
    a failure, not a reason to quantize another way."""
    from ipex_llm_tpu.native import quantizer

    path = quantizer.build(force=True)
    check(quantizer.available(), f"native quantizer built at {path} did "
                                 "not load")
    return path


def build_model(hf_config: dict, qtype: str = WEIGHT_QTYPE, seed: int = SEED):
    """Random weights from ``seed`` through the real family parser and the
    real quantize-on-load path, wrapped as the object ``from_pretrained``
    returns."""
    from ipex_llm_tpu.models.families import FAMILIES
    from ipex_llm_tpu.models.random_init import random_params
    from ipex_llm_tpu.transformers.model import TPUModelForCausalLM

    cfg = FAMILIES[hf_config["model_type"]].to_config(hf_config)
    params = random_params(cfg, qtype=qtype, seed=seed)
    return TPUModelForCausalLM(cfg, params, hf_config, qtype)


def placement(tree, platform: str) -> dict:
    """Bytes each device holds of ``tree``; fails unless every array lives
    on ``platform`` devices."""
    import jax

    per_dev: dict[int, int] = {}
    total = 0
    for leaf in jax.tree_util.tree_leaves(tree):
        if not isinstance(leaf, jax.Array):
            continue
        total += leaf.nbytes
        for sh in leaf.addressable_shards:
            check(sh.device.platform == platform,
                  f"array of shape {leaf.shape} lives on {sh.device}, not on "
                  f"a {platform} device")
            per_dev[sh.device.id] = per_dev.get(sh.device.id, 0) + sh.data.nbytes
    return {"global_bytes": total,
            "bytes_per_device": dict(sorted(per_dev.items()))}


def check_spread(place: dict, tp: int, what: str) -> None:
    """Under tp, ``what`` must sit on tp distinct devices with about 1/tp of
    the bytes each (the slack covers the replicated embedding and norms)."""
    per = place["bytes_per_device"]
    check(len(per) == tp, f"{what}: on devices {sorted(per)}, expected {tp}")
    cap = place["global_bytes"] * (1.0 / tp + 0.1)
    check(max(per.values()) <= cap,
          f"{what}: a device holds {max(per.values())} bytes of "
          f"{place['global_bytes']}; tp={tp} should leave about 1/{tp} each")


# -- phase 2: kernels against their references ---------------------------------

def _rel_err(got, want) -> float:
    import numpy as np

    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    check(got.shape == want.shape, f"shape {got.shape} != {want.shape}")
    check(bool(np.isfinite(got).all()), "kernel output is not finite")
    return float(np.abs(got - want).max() / max(np.abs(want).max(), 1e-30))


def kernel_cases(cfg, params, engine: dict, chunk: int = 128,
                 flash_len: int = 1024):
    """Every Pallas kernel family against its XLA reference, on this
    backend, at this model's shapes: the model's own packed weight planes
    for qmatmul, its head counts / head size / page size for attention.
    Yields ``(name, tolerance, run)``; ``run()`` returns the worst error
    as a fraction of the reference's largest magnitude."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ipex_llm_tpu.kv import KV_STORAGE_DTYPES, PagedKVCache
    from ipex_llm_tpu.ops.attention import sdpa_reference
    from ipex_llm_tpu.ops.linear import qmatmul_reference
    from ipex_llm_tpu.ops.pallas.decode_attention import decode_sdpa
    from ipex_llm_tpu.ops.pallas.flash_attention import flash_sdpa
    from ipex_llm_tpu.ops.pallas.qmatmul import qmatmul_pallas
    from ipex_llm_tpu.ops.pallas.ragged_paged_attention import ragged_paged_sdpa

    rng = np.random.default_rng(SEED + 1)

    def bf16(*shape):
        return jnp.asarray(rng.standard_normal(shape, dtype=np.float32),
                           jnp.bfloat16)

    # fused dequant-matmul: layer 0's plane of every stacked linear weight
    # plus the lm head, at decode (1, rows) and prefill-chunk widths
    layer0 = jax.tree_util.tree_map(lambda x: x[0], params["layers"])
    planes = {k: layer0[k] for k in ("qkv", "o", "gate_up", "down")}
    planes["lm_head"] = params["lm_head"]
    for name, qt in planes.items():
        for m in (1, engine["max_rows"], chunk):
            def qmatmul(qt=qt, m=m):
                x = bf16(m, qt.in_features)
                return _rel_err(qmatmul_pallas(x, qt),
                                qmatmul_reference(x, qt))
            yield (f"qmatmul_{qt.qtype}.{name}[{qt.in_features}x"
                   f"{qt.out_features}].m{m}", QMATMUL_TOL, qmatmul)

    # ragged paged attention: every row owns a shuffled set of pages; rows
    # mix long and short histories, one row is idle (chunk_len 0); then
    # short rows in the same wide table with several dead rows among them
    r, ps = engine["max_rows"], engine["page_size"]
    maxp = engine["max_seq_len"] // ps
    hq, hkv, d = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    tables = (1 + rng.permutation(r * maxp)).reshape(r, maxp).astype(np.int32)
    def full_range(c):
        kv_len = rng.integers(c, maxp * ps + 1, r).astype(np.int32)
        kv_len[0] = maxp * ps              # one row at full capacity
        chunk_len = rng.integers(1, c + 1, r).astype(np.int32)
        chunk_len[1] = c
        chunk_len[-1] = 0                  # idle row
        return kv_len, chunk_len

    def short_wide(c):
        # a dense model's decode stage: rows a few pages long in the
        # full-width table, one ending exactly on a page, one of a single
        # token, every third row dead at the scratch sentinel
        kv_len = rng.integers(1, min(4, maxp) * ps + 1, r).astype(np.int32)
        kv_len[0], kv_len[1] = ps, 1
        chunk_len = np.where(np.arange(r) % 3 == 2, 0, c).astype(np.int32)
        return np.where(chunk_len > 0, kv_len, maxp * ps), chunk_len

    mixes = [(f"c{c}", c, full_range) for c in (1, chunk)]
    mixes.append(("short_wide", 1, short_wide))
    for mix, c, lengths in mixes:
        for storage, dtype in KV_STORAGE_DTYPES.items():
            def ragged(c=c, storage=storage, dtype=dtype, lengths=lengths):
                kv_len, chunk_len = lengths(c)
                q = bf16(r, c, hq, d)
                kp = bf16(r * maxp + 1, hkv, ps, d).astype(dtype)
                vp = bf16(r * maxp + 1, hkv, ps, d).astype(dtype)
                got = np.asarray(ragged_paged_sdpa(
                    q, kp, vp, jnp.asarray(tables), jnp.asarray(kv_len),
                    jnp.asarray(chunk_len)), np.float32)
                cache = PagedKVCache(kp[None], vp[None], jnp.asarray(tables),
                                     jnp.zeros((), jnp.int32),
                                     storage=storage)
                kd = cache.gather_layer(kp).astype(jnp.bfloat16)
                vd = cache.gather_layer(vp).astype(jnp.bfloat16)
                qpos = (kv_len - chunk_len)[:, None] + np.arange(c)[None]
                with jax.default_matmul_precision("highest"):
                    want = np.asarray(sdpa_reference(
                        q, kd.transpose(0, 2, 1, 3), vd.transpose(0, 2, 1, 3),
                        causal=True, q_positions=jnp.asarray(qpos),
                        kv_len=jnp.asarray(kv_len)), np.float32)
                check(not got[chunk_len == 0].any(),
                      "ragged: an idle row is not zero")
                # pad queries past a row's chunk_len are garbage by contract
                valid = (np.arange(c)[None] < chunk_len[:, None]
                         )[:, :, None, None]
                return _rel_err(np.where(valid, got, 0),
                                np.where(valid, want, 0))
            yield f"ragged_paged_sdpa.{storage}.{mix}", ATTN_TOL, ragged

    # the in-place write: the same rows into a two-layer stack by the kernel
    # and by the XLA scatter, every byte of the live pages alike (row 0's
    # chunk crosses a page boundary, the last row is idle at the sentinel
    # slot, whose rows go to scratch page 0 in both)
    for c in (1, chunk):
        for storage, dtype in KV_STORAGE_DTYPES.items():
            def write(c=c, storage=storage, dtype=dtype):
                from ipex_llm_tpu.ops.pallas.paged_kv_write import (
                    paged_kv_write)

                pos = rng.integers(0, (maxp - 1) * ps - c, r).astype(np.int32)
                pos[0] = ps - 1
                pos[-1] = maxp * ps
                stack = (2, r * maxp + 1, hkv, ps, d)
                cache = PagedKVCache(
                    bf16(*stack).astype(dtype), bf16(*stack).astype(dtype),
                    jnp.asarray(tables), jnp.zeros((), jnp.int32),
                    storage=storage)
                args = (cache.k, cache.v, jnp.asarray(1), bf16(r, c, hkv, d),
                        bf16(r, c, hkv, d), jnp.asarray(pos))
                got = paged_kv_write(cache, *args)
                want = cache.write_layer(*args)
                return max(_rel_err(g[:, 1:], w[:, 1:])
                           for g, w in zip(got, want))
            yield f"paged_kv_write.{storage}.c{c}", 0.0, write

    # generate() path: flash prefill over a dense cache, then the T=1 decode
    # kernel over the head-major cache in both storage widths
    def flash():
        t = flash_len
        q, k, v = bf16(1, t, hq, d), bf16(1, t, hkv, d), bf16(1, t, hkv, d)
        with jax.default_matmul_precision("highest"):
            want = sdpa_reference(q, k, v, causal=True)
        return _rel_err(flash_sdpa(q, k, v, causal=True), want)
    yield f"flash_sdpa.t{flash_len}", ATTN_TOL, flash

    s = engine["max_seq_len"]
    for storage, dtype in KV_STORAGE_DTYPES.items():
        def decode(dtype=dtype):
            q = bf16(2, 1, hq, d)
            kr = bf16(2, hkv, s, d).astype(dtype)
            vr = bf16(2, hkv, s, d).astype(dtype)
            kv_len = jnp.asarray([s, s // 3], jnp.int32)
            with jax.default_matmul_precision("highest"):
                want = sdpa_reference(
                    q, kr.astype(jnp.bfloat16).transpose(0, 2, 1, 3),
                    vr.astype(jnp.bfloat16).transpose(0, 2, 1, 3),
                    causal=True, q_positions=(kv_len - 1)[:, None],
                    kv_len=kv_len)
            return _rel_err(decode_sdpa(q, kr, vr, kv_len=kv_len), want)
        yield f"decode_sdpa.{storage}.s{s}", ATTN_TOL, decode


def check_kernels(cfg, params, engine: dict, **sizes) -> dict:
    """Run :func:`kernel_cases`; a kernel the compiler refuses raises here
    with the compiler's own message, a wrong one fails its tolerance."""
    out = {}
    for name, tol, run in kernel_cases(cfg, params, engine, **sizes):
        err = run()
        out[name] = round(err, 6)
        log(f"kernel {name}: err {err:.2e} (tol {tol:.2e})")
        check(err <= tol, f"{name}: differs from its reference by {err:.3e} "
                          f"of the largest magnitude, tolerance {tol:.3e}")
    return out


# -- phase 3: the server, over HTTP --------------------------------------------

class IdTokenizer:
    """Text is space-separated token ids, so a prompt has exactly the length
    asked for and equal text means equal tokens.  No EOS (see the config
    note above)."""

    eos_token_id = None

    def __call__(self, text: str) -> dict:
        return {"input_ids": [int(t) for t in text.split()]}

    def decode(self, ids) -> str:
        return " ".join(str(int(i)) for i in ids)


def traffic(vocab: int, lens=PROMPT_LENS, max_tokens=MAX_TOKENS) -> dict:
    """A streamed request, a plain one, then a concurrent wave that repeats
    both beside four prompts of its own — so the first-compile tick, the
    mixed prefill+decode tick, the steady decode tick and a prefix-cache
    hit all run.  Greedy throughout."""
    import numpy as np

    rng = np.random.default_rng(SEED + 2)
    tok = IdTokenizer()
    bodies = [{"prompt": tok.decode(rng.integers(3, vocab, n)),
               "max_tokens": m, "temperature": 0.0}
              for n, m in zip(lens, max_tokens)]
    return {"stream": {**bodies[0], "stream": True},
            "single": bodies[1],
            "wave": bodies}


async def _complete(session, base: str, body: dict) -> dict:
    """POST /v1/completions; returns status, tokens, finish_reason."""
    async with session.post(f"{base}/v1/completions", json=body) as resp:
        if not body.get("stream"):
            data = await resp.json()
            if resp.status != 200:
                return {"status": resp.status, "error": data}
            ch = data["choices"][0]
            return {"status": resp.status, "finish": ch["finish_reason"],
                    "tokens": [int(t) for t in ch["text"].split()]}
        text, finish, error = "", None, None
        async for raw in resp.content:
            line = raw.decode().strip()
            if not line.startswith("data: ") or line == "data: [DONE]":
                continue
            event = json.loads(line[len("data: "):])
            if "error" in event:
                error = event
                continue
            ch = event["choices"][0]
            text += ch["text"]
            finish = ch["finish_reason"] or finish
        return {"status": resp.status, "finish": finish, "error": error,
                "tokens": [int(t) for t in text.split()]}


async def _serve_and_drive(server, plan: dict) -> dict:
    import aiohttp
    from aiohttp import web

    runner = web.AppRunner(server.app)
    await runner.setup()
    try:
        site = web.TCPSite(runner, "127.0.0.1", 0)
        await site.start()
        port = site._server.sockets[0].getsockname()[1]
        base = f"http://127.0.0.1:{port}"
        timeout = aiohttp.ClientTimeout(total=None)
        async with aiohttp.ClientSession(timeout=timeout) as session:
            t0 = time.perf_counter()
            stream = await _complete(session, base, plan["stream"])
            first_s = time.perf_counter() - t0
            log(f"streamed request answered in {first_s:.1f}s (cold compile)")
            single = await _complete(session, base, plan["single"])
            log("single request answered")
            wave = await asyncio.gather(
                *[_complete(session, base, b) for b in plan["wave"]])
            log("wave answered")
            async with session.get(f"{base}/health") as resp:
                health = await resp.json()
                health_status = resp.status
            async with session.get(f"{base}/metrics?format=json") as resp:
                metrics = (await resp.json())["metrics"]
    finally:
        # the app's on_shutdown drains and stops the engine thread
        await runner.cleanup()
    return {"stream": stream, "single": single, "wave": wave,
            "health": health, "health_status": health_status,
            "metrics": metrics, "first_request_s": round(first_s, 1)}


def serve(model, engine: dict, plan: dict, tensor_parallel_size: int = 1):
    """Build the server through ``build_server`` and answer ``plan`` over
    HTTP.  Returns (what came back, engine); the engine is stopped but its
    device state is still there for :func:`check_compiled_tick`."""
    from ipex_llm_tpu.serving.api_server import build_server
    from ipex_llm_tpu.serving.engine import EngineConfig

    server = build_server(
        "chip-smoke", engine_config=EngineConfig(**engine), model=model,
        tokenizer=IdTokenizer(), tensor_parallel_size=tensor_parallel_size)
    return asyncio.run(_serve_and_drive(server, plan)), server.engine


def check_served(plan: dict, out: dict, eng,
                 tensor_parallel_size: int = 1) -> dict:
    """Every response and the engine's health against what was asked."""
    bodies = [plan["stream"], plan["single"], *plan["wave"]]
    answers = [out["stream"], out["single"], *out["wave"]]
    # a request the engine isolated carries no cause; the flight recorder's
    # postmortem dumps do (the compiler's own message, for a kernel that
    # would not compile)
    causes = [d.get("error") for d in eng.flight.view()["dumps"]]
    for i, (body, ans) in enumerate(zip(bodies, answers)):
        check(ans["status"] == 200 and not ans.get("error"),
              f"request {i}: HTTP {ans['status']} {ans.get('error')}; the "
              f"engine recorded: {causes}")
        check(len(ans["tokens"]) == body["max_tokens"],
              f"request {i}: {len(ans['tokens'])} tokens, asked for "
              f"{body['max_tokens']}")
        check(ans["finish"] == "length",
              f"request {i}: finish_reason {ans['finish']!r}, not 'length'")
    check(out["wave"][0]["tokens"] == out["stream"]["tokens"],
          "the streamed prompt repeated in the wave gave different tokens")
    check(out["wave"][1]["tokens"] == out["single"]["tokens"],
          "the single prompt repeated in the wave gave different tokens")

    h, m = out["health"], out["metrics"]
    check(out["health_status"] == 200 and h["status"] == "ok",
          f"/health says {h.get('status')!r}: {h.get('last_error')}")
    fd = h["fault_domain"]
    # the engine's fault isolation would otherwise turn a kernel that does
    # not compile into requests that "finish" while the server stays up
    check(fd["retries"] == 0 and fd["errors_isolated"] == 0,
          f"the engine retried or isolated faults: {fd}")
    sentinel = h["perf"]["sentinel"]
    check(sentinel["compiles_warm"] == 0,
          f"{sentinel['compiles_warm']} recompiles of an already compiled "
          "tick program")
    check(m["mixed_steps"] >= 1, "no mixed prefill+decode tick ran")
    check(m["prefix_hits"] >= 1, "the repeated prompt hit no cached prefix")
    if tensor_parallel_size > 1:
        par = h["parallel"]
        check(par["tp_manual"] is True and par["tp_fallback_reason"] is None,
              f"the manual tp tick was not taken: {par}")
    return {
        "first_request_s": out["first_request_s"],
        "requests": len(answers),
        "tokens": [a["tokens"] for a in answers],
        "health": {"status": h["status"], **fd,
                   "compiles_total": sentinel["compiles_total"],
                   "compiles_warm": sentinel["compiles_warm"],
                   "compiles_out_of_grid": sentinel["compiles_out_of_grid"],
                   "mixed_steps": m["mixed_steps"],
                   "prefix_hits": m["prefix_hits"],
                   "parallel": h.get("parallel")},
    }


# -- phase 4: what the tick compiled to -----------------------------------------

def check_compiled_tick(eng, expect_mosaic: bool) -> dict:
    """Lower and compile the engine's steady decode tick from its own live
    state, twice.  The program must hold the Mosaic custom calls of the
    qmatmul and ragged-attention kernels (``expect_mosaic``: on a TPU), and
    the second compile must come out of the persistent compile cache when
    one is configured."""
    import jax

    from ipex_llm_tpu.ops.pallas import _compat
    from ipex_llm_tpu.serving.engine import _ragged_tick

    def sds(tree):
        return jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype,
                                           sharding=x.sharding)
            if isinstance(x, jax.Array) else x, tree)

    dev = eng._dev
    args = (eng.cfg, sds(eng.params), sds(eng.cache), sds(dev["toks"]),
            sds(dev["row_lens"]), sds(dev["active"]), sds(dev["temps"]),
            sds(dev["top_ps"]), sds(eng.key), sds(dev["seeds"]),
            sds(dev["steps"]), sds(dev["top_ks"]), sds(dev["eos"]),
            sds(dev["remain"]))
    def lower():
        return _ragged_tick.lower(
            *args, prefill=None, horizon=1, mesh=eng.mesh,
            tp_manual=eng._tp_manual,
            collective_qtype=eng._collective_qtype)

    hits = []

    def on_event(event, **_kw):
        if event == "/jax/compilation_cache/cache_hits":
            hits.append(event)

    jax.monitoring.register_event_listener(on_event)
    try:
        secs, seen = [], []
        for _ in range(2):
            # drop what jax keeps in memory (the engine is stopped), so the
            # second compile can only come from the persistent cache
            jax.clear_caches()
            lowered = lower()
            t0 = time.perf_counter()
            compiled = lowered.compile()
            secs.append(round(time.perf_counter() - t0, 2))
            seen.append(len(hits))
    finally:
        jax.monitoring.unregister_event_listener(on_event)
    stablehlo, hlo = lowered.as_text(), compiled.as_text()
    kernels = {name: stablehlo.count(f'kernel_name = "{name}"')
               for name in ("qmatmul", "ragged_paged_attention")}
    out = {"interpret": _compat.interpret(),
           "mosaic_calls_in_stablehlo": kernels,
           "tpu_custom_calls_in_hlo": hlo.count("tpu_custom_call"),
           "compile_s": secs,
           "second_compile_cache_hit": seen[1] > seen[0],
           "compile_cache_dir": jax.config.jax_compilation_cache_dir}
    log(f"compiled tick: {out}")
    if expect_mosaic:
        check(not out["interpret"], "Pallas kernels are in interpret mode")
        check(all(kernels.values()) and out["tpu_custom_calls_in_hlo"] > 0,
              f"the compiled tick lacks Mosaic custom calls: {out}")
    if out["compile_cache_dir"]:
        check(out["second_compile_cache_hit"],
              "the second compile of the same tick missed the persistent "
              f"compile cache at {out['compile_cache_dir']}")
    return out


# -- the run --------------------------------------------------------------------

def run(hf_config: dict, engine: dict, tensor_parallel_size: int = 1) -> dict:
    """Every phase in order on the device JAX gives; raises on the first
    failure.  ``main`` is this at the published size on one chip; the
    four-chip check is this with ``tensor_parallel_size=4``."""
    import jax
    import jaxlib

    device = require_tpu()
    log(f"device {device}")
    lib = build_native_quantizer()
    t0 = time.perf_counter()
    model = build_model(hf_config)
    build_s = round(time.perf_counter() - t0, 1)
    weights = placement(model.params, device["platform"])
    log(f"model built in {build_s}s, {weights['global_bytes'] / 1e9:.2f} GB")

    kernels = check_kernels(model.config, model.params, engine)
    plan = traffic(model.config.vocab_size)
    out, eng = serve(model, engine, plan, tensor_parallel_size)
    served = check_served(plan, out, eng, tensor_parallel_size)
    weights = placement(eng.params, device["platform"])
    pool = placement((eng.cache.k, eng.cache.v), device["platform"])
    if tensor_parallel_size > 1:
        check_spread(weights, tensor_parallel_size, "weights")
        check_spread(pool, tensor_parallel_size, "kv pool")
    tick = check_compiled_tick(eng, expect_mosaic=True)
    try:
        import libtpu
        libtpu_version = libtpu.__version__
    except ImportError:
        libtpu_version = None
    return {
        "device": device,
        "versions": {"jax": jax.__version__, "jaxlib": jaxlib.__version__,
                     "libtpu": libtpu_version},
        "model": {"source": "mistralai/Mistral-7B-v0.3 config.json",
                  "layers": model.config.num_layers,
                  "weight_qtype": WEIGHT_QTYPE, "seed": SEED,
                  "tensor_parallel_size": tensor_parallel_size},
        "setup_s": {"model_build": build_s,
                    "first_request": served["first_request_s"],
                    "tick_compile": tick["compile_s"]},
        "native_quantizer": lib,
        "weights": weights, "kv_pool": pool,
        "kernels_max_err": kernels, "tick": tick,
        "served": {k: v for k, v in served.items() if k != "tokens"},
        "tokens": served["tokens"],
    }


def main() -> int:
    device = require_tpu()
    try:
        record = run(MISTRAL_7B_V03, ENGINE)
    except SmokeFailure as e:
        log(f"FAILED: {e}")
        print(json.dumps({"ok": False, "device": device}), flush=True)
        return 1
    print(json.dumps(record), flush=True)
    # the result, last and alone on its line: these keys and no others
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
