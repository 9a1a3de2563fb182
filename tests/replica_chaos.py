"""The replica chaos gate's harness: real replica PROCESSES behind the
router, one of them SIGKILLed mid-wave (tests/test_serving_router.py
``test_chaos_gate_sigkill_one_of_three``).

Also the replica process itself: ``python -m tests.replica_chaos
--serve-replica PORT`` serves the seeded tiny model on 127.0.0.1:PORT.
"""

from __future__ import annotations

import os
import sys
import threading
import time

import numpy as np


class _IdTok:
    """Deterministic int tokenizer for the replica processes: prompts
    are space-separated token ids, so every replica process maps a prompt
    to the identical id sequence (the cross-replica bit-identity the
    chaos gate asserts rides on it)."""

    eos_token_id = None
    chat_template = None

    def __init__(self, vocab_size: int):
        self.vocab_size = vocab_size

    def __call__(self, text):
        return {"input_ids": [int(x) % self.vocab_size
                              for x in str(text).split()]}

    def decode(self, ids):
        return " ".join(str(int(i)) for i in ids)



class _RouterFleet:
    """A replica fleet + router + router HTTP app on a dedicated
    event-loop thread, so the (synchronous) gate drives it exactly the
    way clients do: over the router port."""

    def __init__(self, backends_factory, router_config):
        import asyncio

        from aiohttp import web

        from ipex_llm_tpu.serving.router import Router

        self.loop = asyncio.new_event_loop()
        started = threading.Event()
        holder: dict = {}

        async def boot():
            backends = await backends_factory()
            holder["router"] = Router(backends, router_config)
            await holder["router"].start()
            runner = web.AppRunner(holder["router"].build_app())
            await runner.setup()
            site = web.TCPSite(runner, "127.0.0.1", 0)
            await site.start()
            holder["runner"] = runner
            holder["port"] = site._server.sockets[0].getsockname()[1]

        def run():
            asyncio.set_event_loop(self.loop)
            try:
                self.loop.run_until_complete(boot())
            except BaseException as e:  # surface the REAL boot failure
                holder["error"] = e
                started.set()
                return
            started.set()
            self.loop.run_forever()

        threading.Thread(target=run, daemon=True).start()
        if not started.wait(300):
            raise RuntimeError("router fleet failed to start (timeout)")
        if "error" in holder:
            raise RuntimeError("router fleet failed to start") \
                from holder["error"]
        self.router = holder["router"]
        self.port = holder["port"]
        self._runner = holder["runner"]

    def stop(self):
        import asyncio

        async def teardown():
            await self.router.close()
            await self._runner.cleanup()

        fut = asyncio.run_coroutine_threadsafe(teardown(), self.loop)
        try:
            fut.result(timeout=60)
        finally:
            self.loop.call_soon_threadsafe(self.loop.stop)


def _sse_request(port: int, body: dict, timeout: float,
                 on_event=None) -> dict:
    """One streaming completion through the router; returns the client-side
    outcome: text delivered, terminal error object (if any), [DONE] seen,
    and whether the stream hung (socket starved past ``timeout``)."""
    import json as _json
    import urllib.error
    import urllib.request

    out = {"text": "", "error": None, "done": False, "hang": False}
    data = _json.dumps(dict(body, stream=True)).encode()
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}/v1/completions", data=data,
        headers={"Content-Type": "application/json"})
    pieces = []
    try:
        resp = urllib.request.urlopen(req, timeout=timeout)
        for line in resp:
            line = line.decode().strip()
            if not line.startswith("data: "):
                continue
            if on_event is not None:
                on_event()
            if line == "data: [DONE]":
                out["done"] = True
                continue
            j = _json.loads(line[6:])
            if "error" in j:
                out["error"] = j
            elif j.get("choices") and j["choices"][0].get("text"):
                pieces.append(j["choices"][0]["text"])
    except urllib.error.HTTPError as e:
        # a well-formed terminal ERROR response (router shed / timeout /
        # failover exhausted) — a visible outcome, emphatically NOT a
        # hang; the gate judges it as a zero-token casualty
        try:
            out["error"] = _json.loads(e.read())
        except Exception:
            out["error"] = {"error": {"message": str(e)}}
    except Exception:
        # socket starved / reset with no terminal event: a HANG — the
        # exact failure class the router exists to prevent
        out["hang"] = True
    out["text"] = "".join(pieces)
    return out


def _router_wave(port: int, prompts, n_out: int, concurrency: int,
                 stream_timeout_s: float, on_event=None,
                 mid_wave=None) -> list[dict]:
    """Drive one concurrent wave of streaming requests through the
    router; ``mid_wave`` (optional) is called once from the driver thread
    after the wave is in flight (the chaos hook)."""
    outs: list[dict | None] = [None] * len(prompts)
    sem = threading.Semaphore(concurrency)

    def run_one(i):
        try:
            outs[i] = _sse_request(
                port, {"prompt": prompts[i], "max_tokens": n_out,
                 "temperature": 0.0}, stream_timeout_s, on_event=on_event)
        finally:
            sem.release()

    threads = []
    for i in range(len(prompts)):
        sem.acquire()
        th = threading.Thread(target=run_one, args=(i,))
        th.start()
        threads.append(th)
        if mid_wave is not None and i == len(prompts) // 2 - 1:
            mid_wave()
    for th in threads:
        th.join(timeout=stream_timeout_s + 30)
    return [o if o is not None else
            {"text": "", "error": None, "done": False, "hang": True}
            for o in outs]


def _free_port() -> int:
    import socket

    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def _replica_serve(port: int):
    """``--serve-replica`` subprocess entry: ONE api_server replica over
    the SEEDED tiny model (identical params in every replica process —
    what makes the chaos gate's bit-identity assertions possible) on
    127.0.0.1:``port``.  A CPU replica by design: the parent spawns it
    with ``JAX_PLATFORMS=cpu`` in its environment, the one mechanism that
    keeps it off the chip."""
    from aiohttp import web

    from ipex_llm_tpu.models.random_init import llama_config, random_params
    from ipex_llm_tpu.serving.api_server import OpenAIServer
    from ipex_llm_tpu.serving.engine import EngineConfig, ServingEngine

    cfg = llama_config(hidden_size=256, intermediate_size=1024, num_layers=4,
                       num_heads=8, num_kv_heads=4, vocab_size=1024)
    params = random_params(cfg, qtype="sym_int4")
    ec = EngineConfig(max_rows=4, max_seq_len=256, page_size=32,
                      prefill_bucket=32, retry_backoff_s=0.005)
    eng = ServingEngine(cfg, params, ec).start()
    srv = OpenAIServer(eng, _IdTok(cfg.vocab_size), "tiny",
                       drain_timeout_s=10.0)
    web.run_app(srv.app, host="127.0.0.1", port=port, print=None)


def chaos_replicas(n_replicas: int = 3, n_reqs: int = 8, n_out: int = 24,
                   stream_timeout_s: float = 120.0,
                   startup_timeout_s: float = 300.0) -> tuple[dict, bool]:
    """The replica chaos GATE: spawn ``n_replicas``
    REAL replica processes, front them with the router, and SIGKILL the
    busiest one mid-wave.  The gate passes only when the blast radius
    held: every stream reached a terminal state (zero hangs), every
    zero-token request completed via failover with the exact reference
    text (zero duplicated or corrupted tokens), every mid-stream casualty
    got a terminal error object over a strict prefix of the reference,
    the kill visibly impacted the wave (failover or casualty — the kill
    was really mid-wave), and the restarted replica REINSTATED through
    the router's probe loop with the ejection visible in the aggregated
    health view.  Returns (report_row, passed)."""
    import json as _json
    import signal
    import subprocess
    import urllib.request

    from ipex_llm_tpu.serving.router import HTTPBackend, RouterConfig

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    ports = [_free_port() for _ in range(n_replicas)]

    def spawn(port):
        return subprocess.Popen(
            [sys.executable, "-m", "tests.replica_chaos",
             "--serve-replica", str(port)],
            cwd=repo, env=env, stdout=subprocess.DEVNULL,
            stderr=subprocess.DEVNULL)

    procs = [spawn(p) for p in ports]
    fleet = None
    row: dict = {"workload": "replica_chaos_gate", "replicas": n_replicas,
                 "n_reqs": n_reqs, "n_out": n_out}
    try:
        # wait for every replica's /health (cold jax import + tiny build)
        deadline = time.monotonic() + startup_timeout_s
        for port in ports:
            while True:
                try:
                    urllib.request.urlopen(
                        f"http://127.0.0.1:{port}/health", timeout=2)
                    break
                except Exception:
                    if time.monotonic() > deadline:
                        raise RuntimeError(
                            f"replica on :{port} never became healthy")
                    time.sleep(0.5)

        # per-prompt greedy references from replica 0, and the fleet
        # bit-identity precondition: every replica must answer each
        # warm-up prompt with the SAME text (seeded identical params)
        rng = np.random.default_rng(31)
        prompts = [" ".join(str(x) for x in rng.integers(1, 1024, 8))
                   for _ in range(n_reqs)]

        def ref_of(port, prompt):
            body = _json.dumps({"prompt": prompt, "max_tokens": n_out,
                                "temperature": 0.0}).encode()
            req = urllib.request.Request(
                f"http://127.0.0.1:{port}/v1/completions", data=body,
                headers={"Content-Type": "application/json"})
            resp = _json.loads(urllib.request.urlopen(
                req, timeout=stream_timeout_s).read())
            return resp["choices"][0]["text"]

        refs = {p: ref_of(ports[0], p) for p in prompts}
        for port in ports[1:]:     # also compiles every replica's engine
            assert ref_of(port, prompts[0]) == refs[prompts[0]], (
                "replicas disagree on a greedy stream — params not seeded"
                " identically; the gate's bit-identity maths is void")

        async def mk_backends():
            return [HTTPBackend(f"http://127.0.0.1:{p}") for p in ports]

        fleet = _RouterFleet(mk_backends, RouterConfig(
            probe_interval_s=0.3, probe_timeout_s=2.0, eject_after=2,
            probe_backoff_s=0.3, probe_backoff_max_s=4.0,
            max_attempts=4, stall_timeout_s=15.0))

        events_seen = [0]
        victim = [-1]

        def kill_busiest():
            # mid-wave trigger: wait until streams are visibly flowing,
            # then SIGKILL the replica carrying the most of them
            t_end = time.monotonic() + stream_timeout_s
            while events_seen[0] < 3 and time.monotonic() < t_end:
                time.sleep(0.002)
            loads = [r.inflight for r in fleet.router.replicas]
            victim[0] = int(np.argmax(loads))
            os.kill(procs[victim[0]].pid, signal.SIGKILL)

        t0 = time.perf_counter()
        outs = _router_wave(
            fleet.port, prompts, n_out, concurrency=n_reqs,
            stream_timeout_s=stream_timeout_s,
            on_event=lambda: events_seen.__setitem__(0,
                                                     events_seen[0] + 1),
            mid_wave=kill_busiest)
        wall = time.perf_counter() - t0

        completed = lost = casualties = dups = 0
        for prompt, o in zip(prompts, outs):
            ref = refs[prompt]
            if o["hang"]:
                lost += 1
            elif o["error"] is not None:
                casualties += 1
                # a casualty must keep every delivered token exactly once
                # (strict prefix) — and a ZERO-token "casualty" is a
                # failover the router failed to perform
                if not o["text"] or not ref.startswith(o["text"]):
                    dups += 1
            elif o["done"] and o["text"] == ref:
                completed += 1
            else:
                lost += 1      # truncated-200 / wrong text: a lost stream

        c = fleet.router.counters
        # restart the victim and wait for the probe loop to reinstate it
        procs[victim[0]] = spawn(ports[victim[0]])
        reinstated = False
        view = None
        r_deadline = time.monotonic() + startup_timeout_s
        while time.monotonic() < r_deadline:
            try:
                view = _json.loads(urllib.request.urlopen(
                    f"http://127.0.0.1:{fleet.port}/health",
                    timeout=5).read())
                if view["replicas"][victim[0]]["state"] == "healthy":
                    reinstated = True
                    break
            except Exception:
                pass
            time.sleep(0.5)
        hops = ([(t["from"], t["to"]) for t in
                 view["replicas"][victim[0]]["transitions"]]
                if view is not None else [])

        row.update({
            "wall_s": round(wall, 2),
            "victim": victim[0],
            "faults_injected": 1,          # the SIGKILL
            "failovers": c["failovers"],
            "midstream_errors": c["midstream_errors"],
            "errors_visible": casualties,
            "completed": completed,
            "hangs": sum(1 for o in outs if o["hang"]),
            "lost": lost,
            "duplicated_or_corrupt": dups,
            "ejections": c["ejections"],
            "reinstated": reinstated,
            "victim_transitions": hops,
        })
        passed = (lost == 0
                  and row["hangs"] == 0
                  and dups == 0
                  and completed + casualties == n_reqs
                  # the kill really landed mid-wave: somebody failed over
                  # or somebody got a terminal error
                  and (c["failovers"] > 0 or casualties > 0)
                  and ("ejected", "probing") in hops
                  and reinstated)
        row["gate"] = "PASS" if passed else "FAIL"
        return row, passed
    finally:
        if fleet is not None:
            fleet.stop()
        for p in procs:
            if p.poll() is None:
                p.kill()


if __name__ == "__main__":
    import argparse

    ap = argparse.ArgumentParser("replica chaos gate")
    ap.add_argument("--serve-replica", type=int, required=True,
                    metavar="PORT",
                    help="run one tiny-model replica api_server on "
                         "127.0.0.1:PORT (CPU, seeded params identical "
                         "across replicas)")
    _replica_serve(ap.parse_args().serve_replica)
