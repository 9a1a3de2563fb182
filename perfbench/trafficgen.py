"""The one general traffic generator: a traffic file's parameters and a seed
give the requests of a run.  Pure Python + numpy, never jax (the load
generator imports this and must stay off the chip).

Every seed gets the SAME multiset of lengths and arrival gaps, in another
order: lengths and gaps are the distribution's quantiles at (i + 0.5) / n
(a stratified sample), permuted by the seed.  So the work of a window does
not change with the seed, only its order does.

In an open loop the order is itself load: where long outputs and short gaps
fall together more rows are live while prompts are chunked in, and a tail
moves with it.  A traffic file may therefore fix the order's PATTERN
(``"order": {"pattern_seed": s, "class_width": w}``): the sorted values fall
into classes of ``w`` neighbours, which class stands at which place comes
from ``pattern_seed`` (one realization of the arrival process, replayed by
every run like a recorded trace), and the run's seed only permutes the
members of a class among the class's places.  Every seed still gives the
same multiset in another order, and the load over time is the same to
within near-ties.  Without the key the seed permutes freely, as before.
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


class Order:
    """How the sorted values of one draw come to their places: freely
    permuted by the run's seed, or (``traffic["order"]``) by classes whose
    places the file's ``pattern_seed`` fixes."""

    def __init__(self, traffic: dict, rng: np.random.Generator):
        self.rng = rng
        spec = traffic.get("order")
        self.width = int(spec["class_width"]) if spec else 0
        self.pattern = (np.random.default_rng([int(spec["pattern_seed"])])
                        if spec else None)

    def place(self, values: list) -> list:
        """``values`` (sorted, as the quantiles come) in this run's order."""
        if self.pattern is None:
            return _permuted(values, self.rng)
        w = self.width
        members = [_permuted(values[i:i + w], self.rng)
                   for i in range(0, len(values), w)]
        return [members[i // w].pop()
                for i in self.pattern.permutation(len(values))]


def lengths(traffic: dict, n: int, order: Order) -> list[tuple[int, int]]:
    """``n`` (prompt_len, output_len) pairs: both stratified, placed
    independently."""
    p = order.place(quantiles(traffic["prompt_len"], n))
    o = order.place(quantiles(traffic["output_len"], n))
    return list(zip(p, o))


def arrivals(rate: float, seconds: float, order: Order) -> list[float]:
    """Poisson arrivals over [0, seconds): round(rate * seconds) of them, the
    gaps being the exponential's quantiles in the run's order, scaled so that
    they fill the span exactly."""
    n = int(round(rate * seconds))
    if n <= 0:
        return []
    gaps = [-math.log(1.0 - (i + 0.5) / n) for i in range(n)]
    gaps = order.place(gaps)
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
    order = Order(traffic, np.random.default_rng(
        [int(seed) & 0xFFFFFFFF, int(seed) >> 32]))
    if traffic["loop"] == "open":
        rate = float(traffic["rate_rps"])
        reqs = []
        for t0, span in ((-ramp_s, ramp_s), (0.0, seconds)):
            times = arrivals(rate, span, order)
            for t, (p, o) in zip(times, lengths(traffic, len(times), order)):
                reqs.append({"due_s": t0 + t, "prompt_len": p,
                             "max_tokens": o})
        for i, r in enumerate(reqs):
            r["idx"] = i
        return {"loop": "open", "rate_rps": rate, "requests": reqs}
    if traffic["loop"] == "closed":
        n = int(traffic["pool"])
        reqs = [{"idx": i, "prompt_len": p, "max_tokens": o}
                for i, (p, o) in enumerate(lengths(traffic, n, order))]
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
