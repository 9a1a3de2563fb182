"""Tiny helpers shared by every Pallas kernel module: the backend probe
that selects interpret mode, the tile rounding helper, and the masking
constant."""

from __future__ import annotations

import jax

NEG_INF = -1e30


def interpret() -> bool:
    """True off-TPU: kernels run in the (slow) Pallas interpreter."""
    return jax.default_backend() != "tpu"


def round_up(n: int, m: int) -> int:
    return ((n + m - 1) // m) * m


def heads_shardable(hq: int, hkv: int, tp: int) -> bool:
    """Whether the head-sharded (``*_sharded``) decode/ragged attention
    wrappers cover this GQA shape at this tp degree: q heads split evenly,
    and kv heads either split evenly or repeat up to ``tp`` with every
    shard's q heads inside one kv group.  Dispatch asks BEFORE routing to
    a wrapper; the wrappers raise on anything else."""
    if hq % tp:
        return False
    if hkv % tp == 0:
        return True
    return tp % hkv == 0 and (hq // hkv) % (tp // hkv) == 0
