"""On-device speculative-decoding helpers for the fused engine tick.

Drafting on the host would cost an n-gram table scan over ``prompt_ids +
output_ids`` per row per step, a per-step draft upload, and a blocking
d2h sync to walk the acceptance chain.  This module is the
device-resident half of keeping that loop inside the one-dispatch tick
(the Medusa/EAGLE observation — PAPERS.md arXiv 2401.10774 / 2401.15077:
speculative decoding pays off when draft+verify+accept stay resident on
the accelerator): the proposer below scans each row's device-resident
token history inside the traced program, so a speculative horizon step
needs no host n-gram table, no draft upload, and no per-step sync.

Reference: :func:`propose_ngram_rows` computes, per row, EXACTLY what the
plain numpy prompt-lookup proposer (reference lookup.py:145-273: longest
n-gram first, most recent earlier occurrence wins, continuation clipped
at the history end) computes — that proposer lives in
``tests/test_serving_spec.py`` beside
``test_device_proposer_matches_host``.  The token streams themselves
never depend on the drafts (acceptance only emits tokens sampled from
the true conditionals).
"""

from __future__ import annotations

import jax.numpy as jnp


def propose_ngram_rows(hist: jnp.ndarray, lens: jnp.ndarray, k: int,
                       ngram: int):
    """Prompt-lookup draft candidates for a batch of rows, fully traced.

    ``hist`` [R, S] int32 token history per row (prompt + emitted tokens,
    zero-padded); ``lens`` [R] the valid history length per row (the
    current token sits at ``hist[r, lens[r] - 1]``).  For each row, find
    the most recent earlier occurrence of the trailing n-gram (longest
    ``n <= ngram`` first) and propose
    the ``k`` tokens that followed it.

    Returns ``(drafts [R, k] int32, n_prop [R] int32)``: ``drafts[r, j]``
    is valid for ``j < n_prop[r]`` and zero-filled beyond (what the verify
    forward is fed at unproposed positions), and
    ``n_prop`` counts the proposed run — ``min(k, continuation length)``,
    0 when no n-gram of any length matches.  A row whose history is too
    short for even a 1-gram match (``lens < 2``) proposes nothing.
    """
    r, s = hist.shape
    idx = jnp.arange(s)
    found = jnp.zeros((r,), bool)
    best_start = jnp.zeros((r,), jnp.int32)   # continuation start per row
    for n in range(ngram, 0, -1):
        # trailing n-gram per row: hist[r, lens[r]-n : lens[r]]
        tpos = lens[:, None] - n + jnp.arange(n)[None, :]
        tail = jnp.take_along_axis(hist, jnp.clip(tpos, 0, s - 1), axis=1)
        # m[r, s0] == (hist[r, s0:s0+n] == tail[r]) via shifted compares;
        # the roll wraparound only touches s0 > S - n, which the validity
        # bound below excludes (s0 < lens - n <= S - n)
        m = jnp.ones((r, s), bool)
        for j in range(n):
            m = m & (jnp.roll(hist, -j, axis=1) == tail[:, j:j + 1])
        # a *previous* occurrence entirely before the tail window, and
        # only for rows whose history admits an n-gram (host loop bound:
        # n <= lens - 1)
        valid = (m & (idx[None, :] < (lens - n)[:, None])
                 & ((lens - 1) >= n)[:, None])
        any_m = valid.any(axis=1)
        start = (jnp.where(valid, idx, -1).max(axis=1) + n).astype(jnp.int32)
        take = any_m & ~found                 # longest n wins, host order
        best_start = jnp.where(take, start, best_start)
        found = found | any_m
    cpos = best_start[:, None] + jnp.arange(k)[None, :]
    cand = jnp.take_along_axis(hist, jnp.clip(cpos, 0, s - 1), axis=1)
    # continuation clipped at the history end (host: nxt = hist[s0+n :
    # s0+n+k], -1-padded; first pad truncates the proposed run)
    n_prop = jnp.where(
        found, jnp.clip(lens - best_start, 0, k), 0).astype(jnp.int32)
    drafts = jnp.where(jnp.arange(k)[None, :] < n_prop[:, None], cand, 0)
    return drafts.astype(jnp.int32), n_prop
