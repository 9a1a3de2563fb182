#!/usr/bin/env python3
"""perfbench/run.py - one cell of BENCHMARK.json, once, in a new process.

    python3 perfbench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Refuses anything but a TPU with the chips the cell asks for (exit non-zero,
no result line, no CPU fallback).  Builds the cell's model on the device from
the seed, starts the real server (build_server -> ServingEngine -> aiohttp on
loopback), checks correctness against the plain reference, warms the cell's
closed set of tick shapes over HTTP, then lets the load generator (a child
process that never imports jax) drive the measured window.  Progress goes to
stderr; stdout carries earlier lines as it likes and LAST the one JSON object
of the contract: correct, attempted, failed, metrics, device (+ breakdown
under --trace 1).

Everything that belongs to one configuration, traffic mix, per-layer metric
or reader kind is a file found by name (see perfbench/README.md); this file
knows none of them.
"""

from __future__ import annotations

import argparse
import asyncio
import importlib
import itertools
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
# the benchmark's own modules, then the checkout's package (not installed)
sys.path[:0] = [HERE, os.path.dirname(HERE)]

import stats  # noqa: E402
import trafficgen  # noqa: E402
from failure import BenchFailure  # noqa: E402

_T0 = time.monotonic()
# keys of a configuration file that are the benchmark's, not the model's
BENCH_KEYS = ("source", "reduced", "assumed", "deployment", "withheld",
              "weight_qtype", "chips", "tensor_parallel_size", "engine",
              "notes", "rehearsal")
PROBE_NEW_TOKENS = 8


def log(msg: str) -> None:
    print(f"[perfbench +{time.monotonic() - _T0:6.1f}s] {msg}",
          file=sys.stderr, flush=True)


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


class IdTokenizer:
    """Text is space-separated token ids: a prompt has exactly the length
    asked for.  No EOS, so 'length' is the only legitimate finish."""

    eos_token_id = None

    def __call__(self, text: str) -> dict:
        return {"input_ids": [int(t) for t in text.split()]}

    def decode(self, ids) -> str:
        return " ".join(str(int(i)) for i in ids)


# -- what the cell is ------------------------------------------------------------

def resolve_cell(root: str, workload: str) -> dict:
    """The cell's entry, configuration, traffic, metrics and readers, each
    found by the name BENCHMARK.json gives."""
    bench = load_json(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise BenchFailure(f"no workload {workload!r} in BENCHMARK.json "
                           f"(has: {sorted(cells)})")
    cell = cells[workload]
    cfg_entry = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    data = os.path.join(root, bench["paths"][0])
    config = load_json(os.path.join(root, cfg_entry["file"]))
    traffic_path = os.path.join(data, "traffic", cell["traffic"] + ".json")
    traffic = load_json(traffic_path)

    def in_cell(metric: dict) -> bool:
        return "workloads" not in metric or workload in metric["workloads"]

    e2e = [m for m in bench["end_to_end"] if in_cell(m)]
    layers = []
    for m in bench["per_layer"]:
        if in_cell(m):
            spec = load_json(os.path.join(data, "layer_metrics",
                                          m["name"] + ".json"))
            layers.append({**m, "reader": spec["reader"]})
    return {"cell": cell, "config": config, "traffic": traffic,
            "traffic_path": traffic_path, "end_to_end": e2e,
            "per_layer": layers}


def hf_config(config: dict) -> dict:
    """The model's own keys, as handed to the program."""
    return {k: v for k, v in config.items()
            if k not in BENCH_KEYS and k not in config.get("withheld", {})}


def require_tpu(chips: int) -> dict:
    """The device as jax reports it; anything but a TPU with ``chips`` chips
    ends the process with no result."""
    import jax

    devs = jax.devices()
    d = devs[0]
    if d.platform != "tpu" or len(devs) < chips:
        raise SystemExit(
            f"perfbench: the cell needs {chips} TPU chip(s), but jax gives "
            f"{len(devs)} device(s) of platform={d.platform!r} "
            f"kind={d.device_kind!r}; there is no CPU mode")
    return device_info()


def device_info() -> dict:
    import jax

    d = jax.devices()[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(jax.devices())}


def memory_peak_bytes() -> int:
    import jax

    peak = 0
    for d in jax.devices():
        st = d.memory_stats() or {}
        peak = max(peak, int(st.get("peak_bytes_in_use", 0)))
    return peak


# -- the load generator, as a child ------------------------------------------------

async def loadgen(mode: str, port: int, out: str, *args: str,
                  on_line=None) -> dict:
    """Run perfbench/loadgen.py to its end; its record is what it wrote."""
    if os.path.exists(out):
        os.remove(out)
    proc = await asyncio.create_subprocess_exec(
        sys.executable, os.path.join(HERE, "loadgen.py"), mode,
        "--port", str(port), "--out", out, *args,
        stdout=asyncio.subprocess.PIPE)
    try:
        async for raw in proc.stdout:
            if on_line is not None:
                on_line(raw.decode().strip())
        rc = await proc.wait()
    finally:
        if proc.returncode is None:
            proc.kill()
            await proc.wait()
    if rc != 0 or not os.path.exists(out):
        raise BenchFailure(f"the load generator ({mode}) exited with {rc}")
    return load_json(out)


def compiles_total(counters: dict) -> int:
    return int(counters["health"]["perf"]["sentinel"]["compiles_total"])


def check_health(counters: dict, what: str) -> None:
    h = counters["health"]
    fd = h.get("fault_domain", {})
    if h.get("status") != "ok" or fd.get("retries") or fd.get("errors_isolated"):
        raise BenchFailure(f"after {what}: /health says {h.get('status')!r}, "
                           f"fault domain {fd}, last error "
                           f"{h.get('last_error')}")


# -- phases --------------------------------------------------------------------------

PROBE_TOKENS = 120


def probe_prompts(seed: int, vocab: int) -> list[dict]:
    """Two seeded probes of 120 tokens: under one 128-token page, so the
    second sending (under the window's load) recomputes the prompt and does
    not ride the prefix cache; and one length for every seed, so the
    reference compiles one shape, once."""
    return [{"name": f"probe{i}", "max_tokens": PROBE_NEW_TOKENS,
             "logprobs": True,
             "ids": trafficgen.prompt_ids(seed, 10 ** 6 + i, PROBE_TOKENS,
                                          vocab)}
            for i in range(2)]


def warmup_plan(traffic: dict, engine: dict, seed: int, vocab: int) -> dict:
    """The closed warm-up: for each p_b the cell can meet, ``p_b`` distinct
    prompts of the traffic's longest length at once - on an idle engine, and
    beside one row that is already decoding - so each wave walks every
    table-width bucket up to the cell's largest, without and with a decode
    stage.  p_b = max_rows leaves no room for a row beside it: there the
    second wave is ``p_b - 1`` longest prompts and one short one with a long
    output, which finishes its prompt in the first tick and decodes while
    the others (still padded to p_b) walk the buckets."""
    w = traffic["warmup"]
    longest = int(w["longest_prompt"])
    ids = (trafficgen.prompt_ids(seed, 2 * 10 ** 6 + i, longest, vocab)
           for i in itertools.count())
    decoder = {"ids": trafficgen.prompt_ids(seed, 3 * 10 ** 6, 16, vocab),
               "max_tokens": engine["max_seq_len"] - 32}
    steps = []
    for p_b in w["p_b"]:
        full = p_b >= engine["max_rows"]
        steps.append({"name": f"p_b={p_b}", "burst": [
            {"ids": next(ids), "max_tokens": 2} for _ in range(p_b)]})
        step = {"name": f"p_b={p_b}+decode", "burst": [
            {"ids": next(ids), "max_tokens": 2}
            for _ in range(p_b - 1 if full else p_b)]}
        step["beside"] = decoder          # cut when the burst is done
        if full:
            step["beside_with_burst"] = True
        steps.append(step)
    return {"steps": steps}


async def correctness(port: int, tmp: str, cell: dict, model, seed: int,
                      loop) -> tuple[dict, list[dict]]:
    """The probes alone through the served path, against the plain
    reference's full forward over prompt + emitted tokens."""
    hf = hf_config(cell["config"])
    probes = probe_prompts(seed, hf["vocab_size"])
    plan = {"steps": [{"name": p["name"], "burst": [p]} for p in probes]}
    plan_path = os.path.join(tmp, "probe_plan.json")
    with open(plan_path, "w") as f:
        json.dump(plan, f)
    rec = await loadgen("requests", port, os.path.join(tmp, "probe.json"),
                        "--plan", plan_path)
    reference = importlib.import_module(f"references.{hf['model_type']}")
    results = []
    for probe, step in zip(probes, rec["steps"]):
        r = step["requests"][0]
        if (r.get("status") != 200 or r.get("finish") != "length"
                or len(r.get("tokens", ())) != PROBE_NEW_TOKENS
                or len(r.get("logprobs") or ()) != PROBE_NEW_TOKENS):
            raise BenchFailure(f"{probe['name']}: the server answered {r}")
        probe["tokens"] = r["tokens"]
        ids = probe["ids"] + r["tokens"][:-1]
        log(f"{probe['name']} answered in {step['seconds']:.1f}s")
        ref = await loop.run_in_executor(
            None, reference.logprobs, hf, model.params, ids)
        res = reference.compare(ref, len(probe["ids"]), r["tokens"],
                                r["logprobs"])
        res["name"] = probe["name"]
        results.append(res)
        log(f"correctness {probe['name']}: {res}")
    return {"ok": all(r["ok"] for r in results), "probes": results}, probes


async def warm_up(port: int, tmp: str, cell: dict, seed: int) -> dict:
    traffic, engine = cell["traffic"], cell["config"]["engine"]
    vocab = hf_config(cell["config"])["vocab_size"]
    plan_path = os.path.join(tmp, "warm_plan.json")
    with open(plan_path, "w") as f:
        json.dump(warmup_plan(traffic, engine, seed, vocab), f)
    t0 = time.monotonic()
    rec = await loadgen("requests", port, os.path.join(tmp, "warm.json"),
                        "--plan", plan_path)
    for step in rec["steps"]:
        for r in step["requests"]:
            if r.get("status") != 200 or r.get("finish") != "length":
                raise BenchFailure(f"warm-up wave {step['name']}: {r}")
    check_health(rec["counters"]["end"], "the warm-up waves")
    waves_s = time.monotonic() - t0
    compiled = [compiles_total(rec["counters"]["end"])
                - compiles_total(rec["counters"]["start"])]
    log(f"warm-up waves: {len(rec['steps'])} in {waves_s:.1f}s, "
        f"{compiled[0]} programs compiled or loaded; "
        + ", ".join(f"{s['name']} {s['seconds']:.1f}s" for s in rec["steps"]))
    w = traffic["warmup"]
    for rnd in range(int(w["max_rounds"])):
        r = await loadgen(
            "window", port, os.path.join(tmp, "replay.json"),
            "--traffic", cell["traffic_path"], "--seed", str(seed + 1 + rnd),
            "--seconds", str(w["replay_s"]), "--ramp", "0",
            "--vocab", str(vocab), "--abort-at-end")
        new = (compiles_total(r["counters"]["end"])
               - compiles_total(r["counters"]["start"]))
        compiled.append(new)
        log(f"warm-up replay round {rnd}: {new} new programs")
        if new == 0:
            break
    else:
        raise BenchFailure(f"the warm-up did not converge: programs per "
                           f"round {compiled}")
    return {"waves_s": waves_s, "rounds": len(compiled) - 1,
            "programs": compiled, "total": compiles_total(r["counters"]["end"])}


class Tracer:
    """A few seconds of the steady window under jax.profiler."""

    def __init__(self, logdir: str, spec: dict):
        self.logdir, self.spec = logdir, spec
        self.task = None
        self.span = None

    def on_line(self, line: str) -> None:
        try:
            ev = json.loads(line)
        except ValueError:
            return
        if ev.get("event") == "window":
            self.task = asyncio.ensure_future(self._trace(ev["t0"]))

    async def _trace(self, t0: float) -> None:
        import jax

        loop = asyncio.get_running_loop()
        await asyncio.sleep(max(0.0, t0 + self.spec["start_s"]
                                - time.monotonic()))
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        a = time.monotonic()
        await loop.run_in_executor(
            None, lambda: jax.profiler.start_trace(
                self.logdir, profiler_options=opts))
        await asyncio.sleep(self.spec["seconds"])
        await loop.run_in_executor(None, jax.profiler.stop_trace)
        self.span = (a, time.monotonic())


async def run_cell_async(cell: dict, model, server, seed: int,
                         seconds: float, trace: bool, tmp: str,
                         setup: dict) -> dict:
    from aiohttp import web

    loop = asyncio.get_running_loop()
    runner = web.AppRunner(server.app)
    await runner.setup()
    try:
        site = web.TCPSite(runner, "127.0.0.1", 0)
        await site.start()
        port = site._server.sockets[0].getsockname()[1]
        vocab = hf_config(cell["config"])["vocab_size"]

        t = time.monotonic()
        correct, probes = await correctness(port, tmp, cell, model, seed, loop)
        setup["correctness_s"] = time.monotonic() - t
        t = time.monotonic()
        warm = await warm_up(port, tmp, cell, seed)
        setup["warmup_s"] = time.monotonic() - t
        setup["warmup"] = warm

        probe_path = os.path.join(tmp, "window_probe.json")
        with open(probe_path, "w") as f:
            json.dump({"ids": probes[0]["ids"],
                       "max_tokens": PROBE_NEW_TOKENS}, f)
        tracer = Tracer(os.path.join(tmp, "trace"),
                        cell["traffic"]["trace"]) if trace else None
        # set-up ends where the generator's ramp begins
        setup["setup_s"] = time.monotonic() - _T0
        log(f"set-up {setup['setup_s']:.1f}s: "
            + json.dumps({k: round(v, 2) for k, v in setup.items()
                          if isinstance(v, float)}))
        record = await loadgen(
            "window", port, os.path.join(tmp, "window.json"),
            "--traffic", cell["traffic_path"], "--seed", str(seed),
            "--seconds", str(seconds), "--vocab", str(vocab),
            "--probe", probe_path,
            on_line=tracer.on_line if tracer else None)
        if tracer is not None and tracer.task is not None:
            await tracer.task
        check_health(record["counters"]["end"], "the window")
        escaped = compiles_total(record["counters"]["end"]) - warm["total"]
        setup["compiles_after_warmup"] = escaped
        if escaped:
            log(f"WARNING: {escaped} programs compiled or loaded during the "
                "ramp or the window: the warm-up's closed set missed a "
                "variant, and this window holds the stall")
        under_load = record.get("probe") or {}
        same = under_load.get("tokens") == probes[0]["tokens"]
        log(f"probe under load: tokens {'identical' if same else 'DIFFER'} "
            f"({under_load.get('tokens')} vs {probes[0]['tokens']})")
        correct["batch_invariant"] = bool(same)
        correct["ok"] = bool(correct["ok"] and same)
    finally:
        # the app's on_shutdown drains and stops the engine thread
        await runner.cleanup()
    return {"record": record, "correct": correct,
            "trace_span": tracer.span if tracer else None}


# -- the run ---------------------------------------------------------------------------

def read_layer_metrics(cell: dict, ctx: dict) -> dict:
    out = {}
    for m in cell["per_layer"]:
        reader = importlib.import_module(f"readers.{m['reader']['kind']}")
        value = reader.read(m["reader"], ctx)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def run_cell(root: str, cell: dict, seed: int, seconds: float,
             trace: bool, device: dict) -> dict:
    """One run of one resolved cell on the device jax gives; the result
    object."""
    import weights

    workload = cell["cell"]["name"]
    config = cell["config"]
    tmp = os.path.join(root, ".perfbench_tmp", workload)
    os.makedirs(tmp, exist_ok=True)
    setup: dict = {}

    # the repo's one rule for the persistent compile cache (in the checkout
    # unless JAX_COMPILATION_CACHE_DIR is set) is applied by this import
    import ipex_llm_tpu
    from ipex_llm_tpu.serving.api_server import build_server
    from ipex_llm_tpu.serving.engine import EngineConfig

    log(f"cell {workload}: config {cell['cell']['config']}, traffic "
        f"{cell['cell']['traffic']}, compile cache "
        f"{ipex_llm_tpu.compile_cache_dir() or os.environ.get('JAX_COMPILATION_CACHE_DIR')}")
    if config.get("deployment"):
        # the cut this configuration stands for (README: `deployment`)
        log(f"deployment: {json.dumps(config['deployment'])}")
    t = time.monotonic()
    model = weights.build_model(hf_config(config), config["weight_qtype"], seed)
    setup["model_build_s"] = time.monotonic() - t
    log(f"model on the device in {setup['model_build_s']:.1f}s, "
        f"{weights.tree_bytes(model.params) / 1e9:.2f} GB")
    t = time.monotonic()
    server = build_server(
        "perfbench", engine_config=EngineConfig(**config["engine"]),
        model=model, tokenizer=IdTokenizer(),
        tensor_parallel_size=int(config["tensor_parallel_size"]))
    setup["server_start_s"] = time.monotonic() - t

    got = asyncio.run(run_cell_async(cell, model, server, seed, seconds,
                                     trace, tmp, setup))
    record = got["record"]
    e2e = stats.end_to_end(record)
    values = stats.metric_values(e2e)
    values["setup_s"] = setup["setup_s"]
    log(f"window: attempted {e2e['attempted']}, failed {e2e['failed']} "
        f"{e2e['why_failed']}, {len(e2e['ttft_ms'])} TTFTs, "
        f"{len(e2e['itl_ms'])} gaps, all metrics "
        + json.dumps({k: round(v, 3) for k, v in values.items()}))
    device = dict(device, memory_peak_bytes=memory_peak_bytes())
    result = {"correct": got["correct"]["ok"], "attempted": e2e["attempted"],
              "failed": e2e["failed"], "metrics": {}, "device": device}
    if not trace:
        for m in cell["end_to_end"]:
            if m["name"] not in values:
                raise BenchFailure(
                    f"{m['name']}: the window's sample cannot carry it "
                    f"({len(e2e['ttft_ms'])} TTFTs, {len(e2e['itl_ms'])} gaps)")
            result["metrics"][m["name"]] = {"value": values[m["name"]],
                                            "unit": m["unit"]}
    else:
        import trace_reduce

        reduced = trace_reduce.reduce_dir(os.path.join(tmp, "trace"))
        ctx = {"record": record, "trace": reduced, "setup": setup,
               "config": config, "hf_config": hf_config(config),
               "traffic": cell["traffic"], "device": device,
               "peaks": load_json(os.path.join(HERE, "peaks.json")),
               "e2e": e2e}
        result["metrics"] = read_layer_metrics(cell, ctx)
        if reduced is not None:
            with open(os.path.join(tmp, "reduced.json"), "w") as f:
                json.dump({"reduced": reduced, "notes": ctx.get("notes")}, f)
        if reduced is None or reduced["busy_s"] <= 0:
            raise BenchFailure("the traced window shows no device operation")
        result["device"]["busy_s"] = reduced["busy_s"]
        result["device"]["window_s"] = reduced["window_s"]
        result["breakdown"] = trace_reduce.breakdown(reduced)
    print(json.dumps({"setup_parts": setup, "correctness": got["correct"],
                      "all_values": values}), flush=True)
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--root", default=os.path.dirname(HERE),
                    help="the checkout (tests point it at a copy)")
    args = ap.parse_args(argv)
    try:
        cell = resolve_cell(args.root, args.workload)
        device = require_tpu(int(cell["cell"]["chips"]))
        result = run_cell(args.root, cell, args.seed, args.seconds,
                          bool(args.trace), device)
    except BenchFailure as e:
        log(f"FAILED: {e}")
        return 1
    # the result, last and alone on its line
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
