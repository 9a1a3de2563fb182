"""Speculative decoding INSIDE the fused tick (spec on == spec off).

The contract under test: with ``spec_k > 0`` the engine drafts,
verifies, and accepts ON DEVICE inside ``_ragged_tick``'s horizon loop
— one dispatch per tick, no per-step host sync — and its emitted streams
are those of the spec-off engine for greedy and seeded-sampled rows,
composed with decode horizons, fp8 KV storage, mid-horizon EOS inside
accepted draft runs, per-request opt-out masks, and transient-fault
rollback replay across a spec tick; and a speculating row's bits do not
depend on its batch.  The device prompt-lookup proposer
(ops/speculate.py) is additionally locked bit-exact against a plain
numpy proposer kept here.
"""

import numpy as np
import pytest
import jax.numpy as jnp

from ipex_llm_tpu.ops.speculate import propose_ngram_rows
from ipex_llm_tpu.serving.engine import (
    EngineConfig,
    Request,
    ServingEngine,
    stream_tokens,
)
from ipex_llm_tpu.serving.faults import TransientFault, rate_injector
from tests.test_decoder import rand_params, tiny_cfg
from tests.test_serving_mixed import _assert_rows_do_not_depend_on_batch

RNG = np.random.default_rng(93)

# ONE engine shape for the whole module: every test reuses the same
# compiled tick-program variants (jit caches globally by shape/static),
# which keeps the suite inside the tier-1 wall
EC = dict(max_rows=4, max_seq_len=256, page_size=32, prefill_bucket=32)
SPEC = dict(spec_k=3, decode_horizon=4)


@pytest.fixture(scope="module")
def cfg_params():
    cfg = tiny_cfg(vocab_size=127, hidden_size=48, intermediate_size=96,
                   num_heads=4, num_kv_heads=2, head_dim=12,
                   max_position_embeddings=512)
    return cfg, rand_params(cfg, qtype="bf16")


def _periodic_prompt(base_len=4, reps=10, seed=11):
    # explicit seeds keep every test's workload independent of execution
    # order; seed 11's cycle is one this tiny model actually continues
    # (strong draft acceptance), picked empirically
    rng = np.random.default_rng(seed)
    return list(np.tile(rng.integers(0, 127, base_len), reps).astype(int))


def _run(cfg, params, ec, req_kws, injector=None):
    eng = ServingEngine(cfg, params, ec, fault_injector=injector).start()
    try:
        reqs = [eng.submit(Request(**kw)) for kw in req_kws]
        streams = [list(stream_tokens(r, timeout=600)) for r in reqs]
        return (streams,
                [list(map(float, r.logprobs)) for r in reqs],
                [r.finish_reason for r in reqs],
                dict(eng.metrics), eng.spec_stats())
    finally:
        eng.stop()


# -- the device proposer is the host proposer -------------------------------

def _propose_ngram(history: np.ndarray, k: int, ngram: int) -> np.ndarray:
    """The plain reference: prompt-lookup candidates (reference
    lookup.py:145-273) — find the most recent earlier occurrence of the
    trailing n-gram (longest n first) and propose the k tokens that
    followed it.  Returns [k] int32, -1-padded."""
    out = np.full((k,), -1, np.int32)
    ln = len(history)
    for n in range(min(ngram, ln - 1), 0, -1):
        tail = history[ln - n:]
        wins = np.lib.stride_tricks.sliding_window_view(history, n)
        hits = np.nonzero((wins[: ln - n] == tail).all(axis=1))[0]
        if len(hits):
            s = int(hits[-1])  # most recent earlier occurrence
            nxt = history[s + n: s + n + k]
            out[: len(nxt)] = nxt
            return out
    return out


def test_device_proposer_matches_host():
    """ops/speculate.propose_ngram_rows computes bit-exactly what the
    numpy ``_propose_ngram`` above computes — same match position (longest
    n-gram first, most recent occurrence wins), same proposed run length
    (clipped at the history end), zeros past the run."""
    rng = np.random.default_rng(5)
    s = 96
    for trial in range(40):
        k = int(rng.integers(1, 6))
        ngram = int(rng.integers(1, 5))
        r = int(rng.integers(1, 5))
        hist = np.zeros((r, s), np.int32)
        lens = np.zeros((r,), np.int32)
        want = []
        for i in range(r):
            ln = int(rng.integers(1, s))
            h = rng.integers(0, 6, ln).astype(np.int32)  # tiny vocab:
            hist[i, :ln] = h                             # matches abound
            lens[i] = ln
            d = _propose_ngram(h, k, ngram)
            valid = d >= 0
            n_prop = k if valid.all() else int(valid.argmin())
            want.append((np.where(valid, d, 0), n_prop))
        drafts, n_prop = propose_ngram_rows(
            jnp.asarray(hist), jnp.asarray(lens), k, ngram)
        drafts, n_prop = np.asarray(drafts), np.asarray(n_prop)
        for i, (wd, wn) in enumerate(want):
            assert int(n_prop[i]) == wn, (trial, i, hist[i, :lens[i]])
            np.testing.assert_array_equal(drafts[i, :wn], wd[:wn])
            assert (drafts[i, wn:] == 0).all()


# -- spec on == spec off ------------------------------------------------------

@pytest.mark.parametrize("kv", [
    "bf16",
    # the fp8 form re-proves the same program family at twice the compile
    # cost; slow tier keeps the tier-1 wall (fast fp8 bit-identity
    # coverage of the shared tick rides test_serving_kv_storage)
    pytest.param("fp8", marks=pytest.mark.slow),
])
def test_spec_on_matches_spec_off(cfg_params, kv):
    """The pillar: greedy AND seeded-sampled streams through the
    speculating engine (on-device draft/verify/accept, spec x horizon)
    are those of the spec-off engine — tokens and finish reasons
    identical, logprobs to rounding — under the same KV storage."""
    cfg, params = cfg_params
    reqs = [
        dict(prompt_ids=_periodic_prompt(), max_new_tokens=18),  # greedy
        dict(prompt_ids=_periodic_prompt(5, 8, seed=61), max_new_tokens=14,
             temperature=0.8, top_p=0.9, top_k=40, seed=321),    # seeded
        dict(prompt_ids=list(RNG.integers(0, 127, 40)),
             max_new_tokens=10),                                 # 2-chunk
    ]
    fused = _run(cfg, params,
                 EngineConfig(kv_storage=kv, **EC, **SPEC), reqs)
    off = _run(cfg, params, EngineConfig(kv_storage=kv, **EC), reqs)
    assert fused[0] == off[0]            # greedy + seeded: spec-invisible
    assert fused[2] == off[2]
    # logprobs vs the spec-off engine are NEAR-identical, not bitwise:
    # the [R, k+1] verify forward and the T=1 step round bf16 matmuls
    # differently in low bits (the bitwise logprob contract, a
    # speculating row against itself in another batch, is
    # test_a_speculating_row_does_not_depend_on_its_batch below)
    for a, b in zip(fused[1], off[1]):
        np.testing.assert_allclose(a, b, atol=5e-3, rtol=5e-3)
    # the tick really speculated, and every token is accounted for as a
    # verify round's free token or an accepted draft
    assert fused[3]["spec_steps"] > 0
    assert fused[3]["draft_proposed"] > 0
    assert 0.0 < fused[3]["spec_accept_rate"] <= 1.0
    assert (fused[3]["spec_emitted"]
            == fused[3]["spec_row_steps"] + fused[3]["draft_accepted"])
    assert 0.0 <= fused[4]["accept_rate"] <= 1.0


@pytest.mark.parametrize("kv", ("bf16", "fp8"))
def test_a_speculating_row_does_not_depend_on_its_batch(cfg_params, kv):
    """``spec_k > 0``: every row of a staggered wave — greedy rows whose
    periodic prompts get drafts accepted, a seeded sampled row, a row that
    opted out — emits the tokens and logprob BITS it emits served alone:
    what a row drafts, accepts and samples is a function of its own
    history.  (``planner="static"``: the MPC planner masks speculation off
    from the ENGINE-wide accept window and measured tick times, a
    scheduling decision that moves no token but moves a logprob's low
    bits between the wide verify step and the plain one.)"""
    cfg, params = cfg_params
    ec = EngineConfig(kv_storage=kv, planner="static", **EC, **SPEC)
    eng, streams = _assert_rows_do_not_depend_on_batch(
        cfg, params, ec,
        {0: [dict(prompt_ids=_periodic_prompt(), max_new_tokens=14)],
         1: [dict(prompt_ids=_periodic_prompt(5, 8, seed=61),
                  max_new_tokens=12, temperature=0.8, top_p=0.9, top_k=40,
                  seed=321)],
         2: [dict(prompt_ids=_periodic_prompt(4, 9, seed=17),
                  max_new_tokens=12, speculative=False)],
         4: [dict(prompt_ids=list(RNG.integers(0, 127, 40)),
                  max_new_tokens=10)]})
    assert eng.metrics["mixed_steps"] > 0
    assert eng.metrics["draft_proposed"] > 0, eng.metrics
    if kv == "bf16":   # the prompts' cycles are ones THIS pool continues
        assert eng.metrics["draft_accepted"] > 0, eng.metrics
    assert [len(s) for s in streams] == [14, 12, 12, 10]


def test_spec_horizon_matches_h1_and_accepts(cfg_params):
    """spec x horizon composition: H=4 and H=1 fused-spec engines emit
    identical streams, the periodic workload accepts drafts (more tokens
    than verify rounds per row), and a horizon tick amortizes: tokens
    per spec dispatch exceeds 1."""
    cfg, params = cfg_params
    reqs = [dict(prompt_ids=_periodic_prompt(), max_new_tokens=20)]
    h4 = _run(cfg, params, EngineConfig(**EC, **SPEC), reqs)
    h1 = _run(cfg, params,
              EngineConfig(spec_k=SPEC["spec_k"], decode_horizon=1, **EC),
              reqs)
    assert h4[0] == h1[0]
    assert h4[1] == h1[1]
    m = h4[3]
    assert m["spec_emitted"] > m["spec_row_steps"], m  # drafts accepted
    assert m["spec_tokens_per_dispatch"] > 1.0, m
    assert h4[4]["draft_accepted"] > 0


def test_spec_mid_horizon_eos_with_rejected_drafts(cfg_params):
    """A row whose EOS lands INSIDE an accepted draft run mid-horizon
    stops exactly where every other engine stops: the device truncates
    the emitted window at the first EOS (rejected drafts and post-EOS
    positions never leak), finish_reason is 'stop'."""
    cfg, params = cfg_params
    prompt = _periodic_prompt(4, 9, seed=17)
    # the plain continuation tells us which token to declare EOS so it
    # hits mid-stream (index 5: inside a draft window at spec_k=3)
    plain = _run(cfg, params, EngineConfig(**EC),
                 [dict(prompt_ids=prompt, max_new_tokens=16)])
    eos_tok = plain[0][0][5]
    reqs = [dict(prompt_ids=prompt, max_new_tokens=16,
                 eos_token_id=(int(eos_tok),))]
    fused = _run(cfg, params, EngineConfig(**EC, **SPEC), reqs)
    off = _run(cfg, params, EngineConfig(**EC), reqs)
    assert fused[0] == off[0]
    assert fused[2] == off[2] == ["stop"]
    stream = fused[0][0]
    assert stream[-1] == eos_tok and eos_tok not in stream[:-1]
    assert len(stream) == 6


def test_spec_per_request_optout_masks(cfg_params):
    """speculative=False and Request.spec_k caps ride the SAME compiled
    spec program as traced masks: opted-out rows take plain steps (their
    drafts never propose), capped rows cap, and every stream stays
    bit-identical to the spec-off engine (greedy) / the same seed
    (sampled)."""
    cfg, params = cfg_params
    p = _periodic_prompt(4, 8, seed=29)
    reqs = [
        dict(prompt_ids=p, max_new_tokens=12, speculative=False),
        dict(prompt_ids=p, max_new_tokens=12, spec_k=1),
        dict(prompt_ids=p, max_new_tokens=12, temperature=0.9, seed=7,
             spec_k=0),
    ]
    fused = _run(cfg, params, EngineConfig(**EC, **SPEC), reqs)
    off = _run(cfg, params, EngineConfig(**EC), reqs)
    assert fused[0] == off[0]
    assert fused[1] == off[1]
    assert fused[2] == off[2]


def test_spec_transient_fault_rollback_replay(cfg_params):
    """PR 3's recovery contract across a SPEC tick: a transient fault at
    the decode-dispatch site rolls the tick back (device history ring
    included — the epoch re-upload rebuilds it from host bookkeeping) and
    the retried tick replays bit-identically; the rolling accept window
    never double-counts the doomed tick."""
    cfg, params = cfg_params
    reqs = [dict(prompt_ids=_periodic_prompt(4, 7, seed=31), max_new_tokens=14),
            dict(prompt_ids=_periodic_prompt(5, 6, seed=37), max_new_tokens=12,
                 temperature=0.7, seed=11)]
    clean = _run(cfg, params, EngineConfig(**EC, **SPEC), reqs)
    inj = rate_injector("decode-dispatch", 3, TransientFault, limit=4)
    faulted = _run(cfg, params,
                   EngineConfig(retry_backoff_s=0.001, **EC, **SPEC),
                   reqs, injector=inj)
    assert inj.fired > 0
    assert faulted[3]["retries"] > 0
    assert faulted[0] == clean[0]
    assert faulted[1] == clean[1]
    assert faulted[2] == clean[2]
    # draft economics match too: the rolled-back tick left no residue
    assert faulted[3]["draft_proposed"] == clean[3]["draft_proposed"]
    assert faulted[3]["draft_accepted"] == clean[3]["draft_accepted"]


def test_spec_stats_surface(cfg_params):
    """engine.spec_stats() (the /health 'spec' block) reports the draft
    economics: counters move, the rolling accept_rate stays a rate, and
    tokens_per_dispatch reflects the fused loop's amortization."""
    cfg, params = cfg_params
    stats = _run(cfg, params, EngineConfig(**EC, **SPEC),
                 [dict(prompt_ids=_periodic_prompt(), max_new_tokens=16)]
                 )[4]
    assert stats["spec_k"] == SPEC["spec_k"]
    assert stats["draft_proposed"] >= stats["draft_accepted"] >= 0
    assert 0.0 <= stats["accept_rate"] <= 1.0
    assert stats["tokens_per_dispatch"] > 0
