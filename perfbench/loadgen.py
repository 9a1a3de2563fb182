#!/usr/bin/env python3
"""The load generator: a process of its own that never imports jax.

The server process holds the chip and its engine thread needs the GIL; 16 SSE
streams parsed in that process would be read as a slow server.  So run.py
starts this file as a child, gives it the port, and reads the record it
writes.  Two modes:

  window    one run of a traffic file under a seed: a ramp, then the measured
            window, then a drain (or, with --abort-at-end, every stream is
            cut at the window's end: the warm-up's replay rounds).
  requests  an explicit plan (warm-up waves, correctness probes): steps run
            one after another, each a burst of requests sent at once,
            optionally beside one long decoding row.

Clocks: ``time.monotonic()`` (CLOCK_MONOTONIC, shared by every process of the
machine).  Open-loop requests are timed from when they were DUE, closed-loop
ones from when they were SENT.  The generator reports how late it ran: the
event loop's own lag, sampled all through the window, and (open loop) each
request's sent - due.
"""

from __future__ import annotations

import argparse
import asyncio
import itertools
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import trafficgen  # noqa: E402

now = time.monotonic


async def _get_json(session, base: str, path: str) -> dict:
    async with session.get(base + path) as resp:
        return await resp.json()


async def counters(session, base: str) -> dict:
    """The program's own counters, as its HTTP surface gives them."""
    health = await _get_json(session, base, "/health")
    metrics = await _get_json(session, base, "/metrics?format=json")
    return {"t": now(), "health": health, "metrics": metrics["metrics"]}


async def complete(session, base: str, rec: dict, ids: list[int],
                   stream: bool = True, logprobs: bool = False) -> dict:
    """POST /v1/completions and fill ``rec`` as events arrive (so that a
    request cut short still carries what it saw)."""
    body = {"prompt": trafficgen.prompt_text(ids),
            "max_tokens": rec["max_tokens"], "temperature": 0.0}
    if stream:
        body["stream"] = True
    if logprobs:
        body["logprobs"] = 1
    rec.update(sent=now(), token_t=[], done=False, status=None, finish=None)
    try:
        async with session.post(f"{base}/v1/completions", json=body) as resp:
            rec["status"] = resp.status
            if not stream:
                data = await resp.json()
                if resp.status == 200:
                    ch = data["choices"][0]
                    rec["finish"] = ch["finish_reason"]
                    rec["tokens"] = [int(t) for t in ch["text"].split()]
                    rec["token_t"] = [now()] * len(rec["tokens"])
                    if ch.get("logprobs"):
                        rec["logprobs"] = ch["logprobs"]["token_logprobs"]
                else:
                    rec["error"] = json.dumps(data)[:300]
                rec["done"] = True
                return rec
            async for raw in resp.content:
                t = now()
                line = raw.strip()
                if not line.startswith(b"data: ") or line == b"data: [DONE]":
                    continue
                event = json.loads(line[6:])
                if "error" in event:
                    rec["error"] = json.dumps(event)[:300]
                    continue
                ch = event["choices"][0]
                # one event carries one token's text (the id tokenizer's
                # pieces are never empty); the final event carries none
                rec["token_t"].extend([t] * len(ch["text"].split()))
                rec["finish"] = ch["finish_reason"] or rec["finish"]
            rec["done"] = True
    except asyncio.CancelledError:
        rec["cut"] = True
        raise
    except Exception as e:  # a refused or reset connection is a failed request
        rec["error"] = f"{type(e).__name__}: {e}"[:300]
    return rec


async def lag_sampler(samples: list, stop: asyncio.Event, period: float = 0.005):
    """How late this process's event loop wakes: (t, oversleep)."""
    while not stop.is_set():
        t = now()
        await asyncio.sleep(period)
        samples.append((now(), now() - t - period))


async def run_window(args) -> dict:
    import aiohttp

    with open(args.traffic) as f:
        traffic = json.load(f)
    ramp = float(traffic.get("ramp_s", 0.0)) if args.ramp is None else args.ramp
    drain = float(traffic.get("drain_s", 60.0))
    sched = trafficgen.schedule(traffic, args.seed, args.seconds, ramp)
    probe = None
    if args.probe:
        with open(args.probe) as f:
            probe = json.load(f)
    base = f"http://127.0.0.1:{args.port}"
    records: list[dict] = []
    lag: list = []
    stop_lag = asyncio.Event()
    timeout = aiohttp.ClientTimeout(total=None)
    conn = aiohttp.TCPConnector(limit=0)
    async with aiohttp.ClientSession(timeout=timeout, connector=conn) as session:
        t_ramp = now() + 0.2
        t0, t1 = t_ramp + ramp, t_ramp + ramp + args.seconds
        print(json.dumps({"event": "window", "t0": t0, "t1": t1}), flush=True)
        lag_task = asyncio.create_task(lag_sampler(lag, stop_lag))
        tasks: list[asyncio.Task] = []

        def request(r: dict, due: float | None) -> asyncio.Task:
            rec = {"idx": r["idx"], "prompt_len": r["prompt_len"],
                   "max_tokens": r["max_tokens"], "due": due}
            records.append(rec)
            ids = trafficgen.prompt_ids(args.seed, r["idx"], r["prompt_len"],
                                        args.vocab)
            return asyncio.create_task(complete(session, base, rec, ids))

        async def open_loop():
            for r in sched["requests"]:
                due = t0 + r["due_s"]
                await asyncio.sleep(max(0.0, due - now()))
                tasks.append(request(r, due))

        nxt = itertools.count()

        async def client():
            reqs = sched["requests"]
            while now() < t1:
                r = dict(reqs[next(nxt) % len(reqs)])
                t = request(r, None)
                tasks.append(t)
                await t

        async def at(t: float, coro_fn):
            await asyncio.sleep(max(0.0, t - now()))
            return await coro_fn()

        if sched["loop"] == "open":
            drivers = [asyncio.create_task(open_loop())]
        else:
            await asyncio.sleep(max(0.0, t_ramp - now()))
            drivers = [asyncio.create_task(client())
                       for _ in range(sched["clients"])]
        snaps = [asyncio.create_task(at(t0, lambda: counters(session, base))),
                 asyncio.create_task(at(t1, lambda: counters(session, base)))]
        probe_rec = None
        if probe is not None:
            probe_rec = {"idx": -1, "prompt_len": len(probe["ids"]),
                         "max_tokens": probe["max_tokens"], "due": None}
            probe_task = asyncio.create_task(at(
                t0 + args.probe_at * args.seconds,
                lambda: complete(session, base, probe_rec, probe["ids"],
                                 stream=False, logprobs=True)))
        await asyncio.sleep(max(0.0, t1 - now()))
        if args.abort_at_end:
            for t in drivers + tasks:
                t.cancel()
            await asyncio.gather(*drivers, *tasks, return_exceptions=True)
        else:
            # closed-loop clients stop at t1 by themselves; what is in
            # flight gets the drain limit, then is cut and counts as failed
            done, pending = await asyncio.wait(
                drivers, timeout=max(0.0, t1 + drain - now()))
            if tasks:
                done, pending2 = await asyncio.wait(
                    tasks, timeout=max(0.0, t1 + drain - now()))
                pending |= pending2
            for t in pending:
                t.cancel()
            await asyncio.gather(*drivers, *tasks, return_exceptions=True)
        if probe is not None:
            await asyncio.wait([probe_task], timeout=drain)
            if not probe_task.done():
                probe_task.cancel()
        start, end = await asyncio.gather(*snaps)
        stop_lag.set()
        await lag_task
    late = [x for t, x in lag if t0 <= t < t1]
    if sched["loop"] == "open":
        late += [r["sent"] - r["due"] for r in records
                 if r["due"] is not None and t0 <= r["due"] < t1
                 and r.get("sent") is not None]
    return {"loop": sched["loop"], "window": {"t0": t0, "t1": t1},
            "requests": records, "probe": probe_rec,
            "counters": {"start": start, "end": end},
            "late_ms": [x * 1e3 for x in late]}


async def run_requests(args) -> dict:
    """Steps, one after another.  A step: ``burst`` (requests sent at once and
    awaited), optionally ``beside`` (one streaming row started first and cut
    when the burst is done)."""
    import aiohttp

    with open(args.plan) as f:
        plan = json.load(f)
    base = f"http://127.0.0.1:{args.port}"
    out = []
    timeout = aiohttp.ClientTimeout(total=None)
    conn = aiohttp.TCPConnector(limit=0)
    async with aiohttp.ClientSession(timeout=timeout, connector=conn) as session:
        start = await counters(session, base)
        for step in plan["steps"]:
            t_step = now()
            beside = None
            if step.get("beside"):
                b = step["beside"]
                brec = {"idx": -2, "max_tokens": b["max_tokens"]}
                beside = asyncio.create_task(
                    complete(session, base, brec, b["ids"]))
                # the burst must meet a row that is already decoding; with
                # "beside_with_burst" the row is sent WITH the burst and
                # starts decoding a tick into it
                while (not step.get("beside_with_burst")
                       and not brec.get("token_t") and not beside.done()):
                    await asyncio.sleep(0.01)
            recs = [{"idx": i, "max_tokens": r["max_tokens"],
                     "name": r.get("name")}
                    for i, r in enumerate(step["burst"])]
            await asyncio.gather(*[
                complete(session, base, rec, r["ids"],
                         stream=not r.get("logprobs"),
                         logprobs=bool(r.get("logprobs")))
                for rec, r in zip(recs, step["burst"])])
            if beside is not None:
                beside.cancel()
                await asyncio.gather(beside, return_exceptions=True)
            for rec in recs:
                rec.pop("token_t", None)
            out.append({"name": step.get("name"), "seconds": now() - t_step,
                        "requests": recs})
        end = await counters(session, base)
    return {"steps": out, "counters": {"start": start, "end": end}}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("mode", choices=("window", "requests"))
    ap.add_argument("--port", type=int, required=True)
    ap.add_argument("--out", required=True)
    ap.add_argument("--traffic")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--ramp", type=float, default=None)
    ap.add_argument("--vocab", type=int, default=32768)
    ap.add_argument("--abort-at-end", action="store_true")
    ap.add_argument("--probe")
    ap.add_argument("--probe-at", type=float, default=0.4)
    ap.add_argument("--plan")
    args = ap.parse_args(argv)
    record = asyncio.run(run_window(args) if args.mode == "window"
                         else run_requests(args))
    tmp = args.out + ".tmp"
    with open(tmp, "w") as f:
        json.dump(record, f)
    os.replace(tmp, args.out)
    return 0


if __name__ == "__main__":
    sys.exit(main())
