"""From the load generator's records to the end-to-end metrics.  Part of the
yardstick: pure Python, no jax."""

from __future__ import annotations

import math

MIN_BEYOND = 10   # samples a percentile needs beyond it (choosing-metrics §1)


def percentile(values: list[float], q: float) -> float:
    """The q-th percentile (nearest rank).  Refuses a percentile with fewer
    than ``MIN_BEYOND`` samples beyond it on its far side: a p90 wants 100
    samples, a p95 200, the median 20."""
    n = len(values)
    far = q if q >= 50 else 100 - q
    beyond = n * (100 - far) / 100.0
    if beyond < MIN_BEYOND:
        raise ValueError(
            f"p{q:g} over {n} samples has {beyond:.1f} beyond it; "
            f"{MIN_BEYOND} are needed")
    s = sorted(values)
    return s[min(n - 1, max(0, math.ceil(q / 100.0 * n) - 1))]


def failure(rec: dict) -> str | None:
    """Why a request failed, or None.  The id tokenizer has no EOS, so
    ``length`` is the only legitimate finish."""
    if rec.get("error"):
        return f"error: {rec['error']}"
    if rec.get("status") != 200:
        return f"status {rec.get('status')}"
    if not rec.get("done"):
        return "not finished within the drain limit"
    if rec.get("finish") != "length":
        return f"finish_reason {rec.get('finish')!r}"
    if len(rec.get("token_t", ())) != rec["max_tokens"]:
        return f"{len(rec['token_t'])} tokens of {rec['max_tokens']}"
    return None


def end_to_end(record: dict) -> dict:
    """The window's numbers from one load-generator record.

    - ``attempted``: requests due (open loop) or sent (closed loop) in the
      window; ``failed`` among them by :func:`failure`.
    - TTFT of each attempted request: from when it was due (open) or sent
      (closed) to its first token event.
    - ITL: every gap between successive token events of one request that
      ENDS inside the window, whatever request it belongs to (the ramp's
      requests still decode in the window and are part of its work).
    - ``output_tok_s``: token events inside the window over its length.
    """
    t0, t1 = record["window"]["t0"], record["window"]["t1"]
    open_loop = record["loop"] == "open"
    attempted, failed, why = 0, 0, []
    ttft, itl, tokens = [], [], 0
    for rec in record["requests"]:
        start = rec["due"] if open_loop else rec["sent"]
        in_window = start is not None and t0 <= start < t1
        tt = rec.get("token_t", [])
        for a, b in zip(tt, tt[1:]):
            if t0 <= b < t1:
                itl.append((b - a) * 1e3)
        tokens += sum(1 for t in tt if t0 <= t < t1)
        if not in_window:
            continue
        attempted += 1
        f = failure(rec)
        if f:
            failed += 1
            why.append(f"request {rec['idx']}: {f}")
        elif tt:
            ttft.append((tt[0] - start) * 1e3)
    return {"attempted": attempted, "failed": failed, "why_failed": why[:5],
            "ttft_ms": ttft, "itl_ms": itl,
            "output_tok_s": tokens / (t1 - t0), "window_s": t1 - t0}


def metric_values(e2e: dict) -> dict:
    """Every end-to-end metric the sample can carry, by name."""
    out = {"output_tok_s": e2e["output_tok_s"]}
    for name, key, q in (("ttft_p50_ms", "ttft_ms", 50),
                         ("ttft_p90_ms", "ttft_ms", 90),
                         ("itl_p50_ms", "itl_ms", 50),
                         ("itl_p90_ms", "itl_ms", 90),
                         ("itl_p95_ms", "itl_ms", 95),
                         ("itl_p98_ms", "itl_ms", 98)):
        try:
            out[name] = percentile(e2e[key], q)
        except ValueError:
            pass
    return out
