"""The one general traffic generator: a traffic file's parameters and a seed
give the requests of a run.  Pure Python + numpy, never jax (the load
generator imports this and must stay off the chip).

Every seed gets the SAME multiset of lengths and arrival gaps, in another
order: lengths and gaps are the distribution's quantiles at (i + 0.5) / n
(a stratified sample), permuted by the seed.  So the work of a window does
not change with the seed, only its order does, and two runs with different
seeds differ no more than two runs of one seed.
"""

from __future__ import annotations

import math
from statistics import NormalDist

import numpy as np

# token ids 0..2 are left alone (pad/bos/eos by convention in the families)
FIRST_TOKEN_ID = 3


def quantiles(dist: dict, n: int) -> list[int]:
    """``n`` whole-number lengths: the quantiles (i + 0.5) / n of ``dist``,
    clipped to its ``min``/``max``."""
    kind = dist["dist"]
    lo, hi = int(dist["min"]), int(dist["max"])
    out = []
    for i in range(n):
        u = (i + 0.5) / n
        if kind == "uniform":
            x = lo + u * (hi - lo)
        elif kind == "lognormal":
            x = float(dist["median"]) * math.exp(
                float(dist["sigma"]) * NormalDist().inv_cdf(u))
        elif kind == "fixed":
            x = float(dist["value"])
        else:
            raise ValueError(f"unknown length distribution {kind!r}")
        out.append(int(min(max(round(x), lo), hi)))
    return out


def _permuted(values: list, rng: np.random.Generator) -> list:
    return [values[i] for i in rng.permutation(len(values))]


def lengths(traffic: dict, n: int, rng: np.random.Generator) -> list[tuple[int, int]]:
    """``n`` (prompt_len, output_len) pairs: both stratified, permuted
    independently."""
    p = _permuted(quantiles(traffic["prompt_len"], n), rng)
    o = _permuted(quantiles(traffic["output_len"], n), rng)
    return list(zip(p, o))


def arrivals(rate: float, seconds: float, rng: np.random.Generator) -> list[float]:
    """Poisson arrivals over [0, seconds): round(rate * seconds) of them, the
    gaps being the exponential's quantiles in a seeded order, scaled so that
    they fill the span exactly."""
    n = int(round(rate * seconds))
    if n <= 0:
        return []
    gaps = [-math.log(1.0 - (i + 0.5) / n) for i in range(n)]
    gaps = _permuted(gaps, rng)
    scale = seconds / sum(gaps)
    t, out = -0.5 * gaps[0] * scale, []
    for g in gaps:
        t += g * scale
        out.append(t)
    return out


def schedule(traffic: dict, seed: int, seconds: float, ramp_s: float) -> dict:
    """The requests of one run of ``traffic``.

    open loop:   {"loop": "open", "requests": [{"due_s", "prompt_len",
                 "max_tokens", "idx"}]} with ``due_s`` from -ramp_s (the
                 ramp's own arrivals) to ``seconds``; the window's requests
                 are those with due_s >= 0.
    closed loop: {"loop": "closed", "clients": c, "requests": [...]} - a
                 shared list the clients take from in order; it is long
                 enough that it is not exhausted (and wraps if it is).
    """
    rng = np.random.default_rng([int(seed) & 0xFFFFFFFF, int(seed) >> 32])
    if traffic["loop"] == "open":
        rate = float(traffic["rate_rps"])
        reqs = []
        for t0, span in ((-ramp_s, ramp_s), (0.0, seconds)):
            times = arrivals(rate, span, rng)
            for t, (p, o) in zip(times, lengths(traffic, len(times), rng)):
                reqs.append({"due_s": t0 + t, "prompt_len": p,
                             "max_tokens": o})
        for i, r in enumerate(reqs):
            r["idx"] = i
        return {"loop": "open", "rate_rps": rate, "requests": reqs}
    if traffic["loop"] == "closed":
        n = int(traffic["pool"])
        reqs = [{"idx": i, "prompt_len": p, "max_tokens": o}
                for i, (p, o) in enumerate(lengths(traffic, n, rng))]
        return {"loop": "closed", "clients": int(traffic["clients"]),
                "requests": reqs}
    raise ValueError(f"unknown loop kind {traffic['loop']!r}")


def prompt_ids(seed: int, idx: int, n: int, vocab: int) -> list[int]:
    """The ``n`` token ids of request ``idx`` under ``seed``."""
    rng = np.random.default_rng([int(seed) & 0xFFFFFFFF, int(seed) >> 32,
                                 int(idx)])
    return rng.integers(FIRST_TOKEN_ID, vocab, n).tolist()


def prompt_text(ids: list[int]) -> str:
    """The id tokenizer's text: space-separated token ids."""
    return " ".join(str(int(i)) for i in ids)
