"""Token sampling ops (greedy / temperature / top-k / top-p / penalties).

Reference counterparts: HF's LogitsProcessor stack used by the patched
generate loops, plus ``xe_addons.repetition_penalty_logits_process_inplaced``
(§2.3).  Implemented as pure jnp so the whole sample step stays inside the
jitted decode program — no host round-trip per token, unlike the reference's
Python-driven sampling loop (SURVEY.md §3.2).
"""

from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp

NEG_INF = -1e30


@dataclass(frozen=True)
class SamplingParams:
    temperature: float = 1.0
    top_k: int = 0          # 0 = off
    top_p: float = 1.0      # 1.0 = off
    repetition_penalty: float = 1.0
    do_sample: bool = False


def apply_repetition_penalty(
    logits: jnp.ndarray, prev_tokens: jnp.ndarray, penalty: float
) -> jnp.ndarray:
    """CTRL-style repetition penalty over previously seen tokens.

    logits [B, V]; prev_tokens [B, L] with -1 padding for unused slots.
    """
    if penalty == 1.0:
        return logits
    b, v = logits.shape
    seen = jnp.zeros((b, v), dtype=bool)
    valid = prev_tokens >= 0
    idx = jnp.where(valid, prev_tokens, 0)
    seen = seen.at[jnp.arange(b)[:, None], idx].set(valid)
    penalized = jnp.where(logits > 0, logits / penalty, logits * penalty)
    return jnp.where(seen, penalized, logits)


def _top_k_mask(logits: jnp.ndarray, k: int) -> jnp.ndarray:
    kth = jax.lax.top_k(logits, k)[0][..., -1:]
    return jnp.where(logits < kth, NEG_INF, logits)


def _top_p_mask(logits: jnp.ndarray, p) -> jnp.ndarray:
    """Nucleus mask; ``p`` is a scalar or a per-row [B] vector."""
    if not isinstance(p, (int, float)):
        p = jnp.asarray(p)[..., None]
    sorted_logits = jnp.sort(logits, axis=-1)[..., ::-1]
    probs = jax.nn.softmax(sorted_logits, axis=-1)
    cum = jnp.cumsum(probs, axis=-1)
    # keep tokens until cumulative prob exceeds p (always keep the top-1)
    cutoff_mask = cum - probs > p
    # smallest *kept* logit: flood dropped slots with +inf before the min
    # (NEG_INF here would make the cutoff -inf and mask nothing)
    cutoff = jnp.where(cutoff_mask, jnp.inf, sorted_logits).min(axis=-1, keepdims=True)
    return jnp.where(logits < cutoff, NEG_INF, logits)


def sample_rows(logits: jnp.ndarray, temps: jnp.ndarray, top_ps: jnp.ndarray,
                key: jax.Array) -> jnp.ndarray:
    """Vectorized per-row sampling for the serving engine: rows with
    temperature 0 take argmax, others sample from the temperature-scaled,
    per-row-nucleus-masked distribution.  logits [R, V]; temps/top_ps [R]."""
    return sample_rows_with_logprobs(logits, temps, top_ps, key)[0]


def _top_k_mask_rows(logits: jnp.ndarray, ks: jnp.ndarray) -> jnp.ndarray:
    """Per-row top-k mask; ks [R] int32, <=0 disables for that row."""
    v = logits.shape[-1]
    sorted_desc = jnp.sort(logits, axis=-1)[..., ::-1]
    idx = jnp.clip(ks - 1, 0, v - 1)[:, None]
    cutoff = jnp.take_along_axis(sorted_desc, idx, axis=-1)
    masked = jnp.where(logits < cutoff, NEG_INF, logits)
    return jnp.where((ks > 0)[:, None], masked, logits)


def _top_p_mask_rows(logits: jnp.ndarray, ps: jnp.ndarray) -> jnp.ndarray:
    """Per-row nucleus mask; ps [R], >=1 disables for that row: it gets its
    logits back as they came, whatever its neighbours asked for."""
    return jnp.where((ps < 1)[:, None], _top_p_mask(logits, ps), logits)


def _draw_rows(logits, temps, top_ps, key, seeds, steps, top_ks, draws):
    """The drawing half of ``sample_rows_with_logprobs``: a token a row from
    its temperature-scaled, masked distribution.  Each mask's full-vocabulary
    sort runs only if a drawing row (``draws`` [R] bool) set it, and selects
    by row, so a row that set none draws from the same bits either way."""
    scaled = logits / jnp.maximum(temps, 1e-4)[:, None]
    if top_ks is not None:
        scaled = jax.lax.cond(
            jnp.any(draws & (top_ks > 0)),
            lambda: _top_k_mask_rows(scaled, top_ks), lambda: scaled)
    scaled = jax.lax.cond(
        jnp.any(draws & (top_ps < 1)),
        lambda: _top_p_mask_rows(scaled, top_ps), lambda: scaled)
    if seeds is None:
        sampled = jax.random.categorical(key, scaled, axis=-1)
    else:
        r = logits.shape[0]
        base = jax.vmap(lambda i: jax.random.fold_in(key, i))(jnp.arange(r))
        seeded = jax.vmap(
            lambda sd, st: jax.random.fold_in(jax.random.PRNGKey(sd), st)
        )(jnp.maximum(seeds, 0), steps)
        keys = jnp.where((seeds >= 0)[:, None], seeded, base)
        sampled = jax.vmap(
            lambda k, lg: jax.random.categorical(k, lg)
        )(keys, scaled)
    return sampled.astype(jnp.int32)


@jax.named_scope("sample")
def sample_rows_with_logprobs(logits: jnp.ndarray, temps: jnp.ndarray,
                              top_ps: jnp.ndarray, key: jax.Array,
                              seeds: jnp.ndarray | None = None,
                              steps: jnp.ndarray | None = None,
                              top_ks: jnp.ndarray | None = None,
                              active: jnp.ndarray | None = None):
    """sample_rows plus the chosen token's logprob under the MODEL
    distribution (raw log-softmax, the OpenAI ``logprobs`` convention —
    not the temperature/top-p-modified sampling distribution).

    ``seeds`` [R] int32 (-1 = unseeded) with ``steps`` [R] gives rows a
    DETERMINISTIC stream — fold_in(PRNGKey(seed), step) — independent of
    which other requests share the batch; unseeded rows derive per-row
    keys from the engine's stepping key.  ``step`` is the row's OUTPUT
    INDEX, so a first token always draws from fold_in(seed, 0) no matter
    which program samples it — the serving engine's mixed admission step
    folds first-token sampling into the batched chunk program (steps=0)
    and reproduces the sequential per-row first-token stream bit-for-bit.

    ``active`` [R] bool masks dead rows to (token 0, logprob 0) — ONE
    definition of the serving engines' row masking, shared by the plain,
    pipelined, and fused-horizon decode steps so their emitted padding
    stays identical.

    A stage runs only if a row asks for it, decided ON THE DEVICE from the
    rows handed in: the argmax, the chosen token's log-probability and the
    dead-row mask are outside any branch (a greedy row's bits are the same
    operations whatever its batch does); scaling, keys, noise and the draw
    run only if a live row has ``temperature > 0`` (``_draw_rows``), and
    each mask's sort only if such a row set it.  ``top_p >= 1`` and
    ``top_k <= 0`` are exactly "off"."""
    logits = logits.astype(jnp.float32)
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    draws = temps > 0 if active is None else (temps > 0) & active
    sampled = jax.lax.cond(
        jnp.any(draws),
        lambda: _draw_rows(logits, temps, top_ps, key, seeds, steps, top_ks,
                           draws),
        lambda: greedy)
    chosen = jnp.where(draws, sampled, greedy)
    lp = jnp.take_along_axis(
        jax.nn.log_softmax(logits, axis=-1), chosen[:, None], axis=-1
    )[:, 0]
    if active is not None:
        chosen = jnp.where(active, chosen, 0)
        lp = jnp.where(active, lp, 0.0)
    return chosen, lp


def _transform_logits(logits: jnp.ndarray, params: SamplingParams) -> jnp.ndarray:
    """The temperature/top-k/top-p transform chain shared by ``sample`` and
    ``transformed_probs`` — ONE definition, because speculative rejection
    sampling is distribution-identical to plain sampling only while the two
    stay byte-for-byte the same."""
    logits = logits.astype(jnp.float32)
    if params.temperature not in (0.0, 1.0):
        logits = logits / params.temperature
    if params.top_k > 0:
        logits = _top_k_mask(logits, params.top_k)
    if params.top_p < 1.0:
        logits = _top_p_mask(logits, params.top_p)
    return logits


def transformed_probs(logits: jnp.ndarray, params: SamplingParams) -> jnp.ndarray:
    """Post-transform (temperature/top-k/top-p) probability rows.

    The distribution ``sample`` draws from, materialized — used by
    speculative rejection-sampling verification, where both the draft's
    proposal q and the target's p must be actual distributions.
    """
    return jax.nn.softmax(_transform_logits(logits, params), axis=-1)


def sample(
    logits: jnp.ndarray,           # [B, V]
    key: jax.Array,
    params: SamplingParams,
    prev_tokens: jnp.ndarray | None = None,
) -> jnp.ndarray:
    """Returns next token ids [B] (int32). Jit-safe with static params."""
    logits = logits.astype(jnp.float32)
    if prev_tokens is not None and params.repetition_penalty != 1.0:
        logits = apply_repetition_penalty(logits, prev_tokens, params.repetition_penalty)
    if not params.do_sample:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    logits = _transform_logits(logits, params)
    return jax.random.categorical(key, logits, axis=-1).astype(jnp.int32)
