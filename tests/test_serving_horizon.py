"""Fused multi-step decode (decode horizon).

The contract under test: an engine running ``decode_horizon=H`` — up to H
decode+sample steps fused into one on-device ``lax.while_loop`` program
(early-exiting when every row dies), with device-resident engine state
between epochs — must emit the EXACT
token/logprob streams of the H=1 engine (the seeded-stream contract),
across greedy rows, seeded sampled rows, and rows that hit EOS or their
length budget mid-horizon while other rows keep decoding.

Plus the device-residency regression: steady-state decode steps must NOT
re-upload request-static sampling params (temps/top_ps/top_ks/seeds) —
uploads happen only on admission/prefill/finish/page-allocation epochs
(tier-1 guard via a counting wrapper around the epoch-sync helper).
"""

import numpy as np
import pytest

from ipex_llm_tpu.serving.engine import (
    EngineConfig,
    Request,
    ServingEngine,
    stream_tokens,
)
from tests.test_decoder import rand_params, tiny_cfg
from tests.test_serving import _assert_greedy_stream

RNG = np.random.default_rng(77)


@pytest.fixture(scope="module")
def cfg_params():
    cfg = tiny_cfg(vocab_size=131, hidden_size=48, intermediate_size=96,
                   num_heads=4, num_kv_heads=2, head_dim=12,
                   max_position_embeddings=512)
    return cfg, rand_params(cfg, qtype="bf16")


def _run(cfg, params, horizon, specs, **ec_over):
    ec = dict(max_rows=4, max_seq_len=256, page_size=32, prefill_bucket=32,
              decode_horizon=horizon)
    ec.update(ec_over)
    eng = ServingEngine(cfg, params, EngineConfig(**ec)).start()
    try:
        reqs = [eng.submit(Request(**s)) for s in specs]
        streams = [list(stream_tokens(r, timeout=600)) for r in reqs]
    finally:
        eng.stop()
    return reqs, streams, dict(eng.metrics)


def test_fused_h8_bit_identical_to_h1(cfg_params):
    """Greedy, seeded-sampled, and mid-horizon-EOS rows through H=8 emit
    the exact token AND logprob sequences of H=1 — with the EOS row
    finishing inside a horizon while the other rows run on."""
    cfg, params = cfg_params
    p1 = list(RNG.integers(0, cfg.vocab_size, 9))
    p2 = list(RNG.integers(0, cfg.vocab_size, 17))
    p3 = list(RNG.integers(0, cfg.vocab_size, 12))
    # discover an id the greedy continuation of p3 emits at output
    # position 2 — mid-horizon for H=8
    _, (probe,), _ = _run(cfg, params, 1,
                          [dict(prompt_ids=p3, max_new_tokens=16)])
    eos = int(probe[2])
    specs = [
        dict(prompt_ids=p1, max_new_tokens=16),                       # greedy
        dict(prompt_ids=p2, max_new_tokens=16, temperature=0.8,
             top_p=0.9, top_k=40, seed=123),                # seeded sampled
        dict(prompt_ids=p3, max_new_tokens=16, eos_token_id=(eos,)),  # EOS
    ]
    r1, s1, _ = _run(cfg, params, 1, specs)
    r8, s8, m8 = _run(cfg, params, 8, specs)
    for a, b in zip(s1, s8):
        assert a == b, (a, b)
    for a, b in zip(r1, r8):
        assert a.finish_reason == b.finish_reason
        np.testing.assert_array_equal(
            np.asarray(a.logprobs, np.float32),
            np.asarray(b.logprobs, np.float32))
    # the EOS row stopped at 3 tokens while the others ran to budget
    assert len(s8[2]) == 3 and r8[2].finish_reason == "stop"
    assert len(s8[0]) == 16 and len(s8[1]) == 16
    # the horizon actually fused: far fewer host syncs than decode steps
    assert m8["decode_horizon_effective"] == 8
    assert m8["host_syncs"] < m8["steps"], m8
    _assert_greedy_stream(cfg, params, p1, s8[0])


def test_fused_short_budget_row_finishes_while_others_continue(cfg_params):
    """A 3-token-budget row dies inside the first horizon; the long row's
    stream must be unaffected and identical to its H=1 run."""
    cfg, params = cfg_params
    p1 = list(RNG.integers(0, cfg.vocab_size, 8))
    p2 = list(RNG.integers(0, cfg.vocab_size, 14))
    specs = [dict(prompt_ids=p1, max_new_tokens=3),
             dict(prompt_ids=p2, max_new_tokens=20)]
    r1, s1, _ = _run(cfg, params, 1, specs)
    r8, s8, _ = _run(cfg, params, 8, specs)
    assert s1 == s8
    assert r8[0].finish_reason == "length" and len(s8[0]) == 3
    assert r8[1].finish_reason == "length" and len(s8[1]) == 20


def test_horizon_shortens_under_page_pressure(cfg_params):
    """Two rows overcommitting a 5-page pool: horizon pre-allocation is
    budget-clamped per row and falls back to a shorter step (power-of-two)
    when the pool can't back it, instead of truncating on the spot.  Every
    emitted prefix must still match the greedy oracle."""
    cfg, params = cfg_params
    pa = list(RNG.integers(0, cfg.vocab_size, 25))
    pb = list(RNG.integers(0, cfg.vocab_size, 16))
    # 5 usable 16-slot pages = 80 slots; final footprints 51 + 36 = 87
    # overcommit the pool, so mid-flight ensure fails with backed >= 1
    reqs, streams, m = _run(
        cfg, params, 8,
        [dict(prompt_ids=pa, max_new_tokens=26),
         dict(prompt_ids=pb, max_new_tokens=20)],
        max_rows=2, page_size=16, pool_pages=6)
    assert m.get("horizon_clamped", 0) >= 1, m
    # contention may legally truncate with 'length', never corrupt
    for req, stream, prompt in zip(reqs, streams, (pa, pb)):
        assert req.finish_reason == "length"
        assert len(stream) >= 1
        _assert_greedy_stream(cfg, params, prompt, stream)
    assert len(streams[0]) == 26 or len(streams[1]) == 20  # someone finished


def test_no_param_reupload_between_epochs(cfg_params, monkeypatch):
    """Tier-1 regression (device-resident state): a steady decode stream
    must not re-upload request-static sampling params per step.  Counted
    via a wrapper around the epoch-sync upload helper — uploads may only
    track epochs (admission, prefill, page-boundary allocation, finish),
    never steps."""
    cfg, params = cfg_params
    eng = ServingEngine(cfg, params, EngineConfig(
        max_rows=2, max_seq_len=256, page_size=32, prefill_bucket=32,
        decode_horizon=1)).start()
    uploads = {"n": 0}
    orig = eng._upload_epoch

    def counting(*args, **kw):
        uploads["n"] += 1
        return orig(*args, **kw)

    monkeypatch.setattr(eng, "_upload_epoch", counting)
    try:
        prompt = list(RNG.integers(0, cfg.vocab_size, 16))
        req = eng.submit(Request(prompt_ids=prompt, max_new_tokens=40,
                                 temperature=0.7, top_p=0.9, top_k=20,
                                 seed=11))
        got = list(stream_tokens(req, timeout=600))
    finally:
        eng.stop()
    assert len(got) == 40
    steps = eng.metrics["steps"]
    assert steps >= 39
    # expected epochs: admission+prefill (1 upload before the first decode
    # step), one page-boundary allocation (16+40 slots over 32-slot pages),
    # and nothing else — a re-upload-per-step regression makes this track
    # ``steps``
    assert uploads["n"] <= 6, (uploads["n"], steps)
    assert eng.metrics["epoch_syncs"] == uploads["n"]
    # and the horizon metrics surface for /health
    assert eng.metrics["host_syncs"] >= steps
    assert eng.metrics["tokens_per_sync"] > 0


def test_spec_k_composes_with_horizon(cfg_params):
    """spec_k rides INSIDE the fused horizon loop (on-device
    draft/verify/accept; tests/test_serving_spec.py carries the
    spec-on == spec-off suite): H=8 with spec_k emits H=1 with spec_k's
    exact token AND logprob streams, greedy and seeded sampled."""
    cfg, params = cfg_params
    rng = np.random.default_rng(11)
    cycle = list(np.tile(rng.integers(0, cfg.vocab_size, 4), 8).astype(int))
    specs = [dict(prompt_ids=cycle, max_new_tokens=16),
             dict(prompt_ids=list(RNG.integers(0, cfg.vocab_size, 17)),
                  max_new_tokens=16, temperature=0.8, top_p=0.9, top_k=40,
                  seed=123)]
    r1, s1, m1 = _run(cfg, params, 1, specs, spec_k=2)
    r8, s8, m8 = _run(cfg, params, 8, specs, spec_k=2)
    assert s1 == s8
    for a, b in zip(r1, r8):
        assert a.finish_reason == b.finish_reason
        np.testing.assert_array_equal(
            np.asarray(a.logprobs, np.float32),
            np.asarray(b.logprobs, np.float32))
    assert m8["spec_steps"] > 0 and m8["draft_proposed"] > 0
    assert m8["decode_horizon_effective"] == 8
    assert m8["spec_ticks"] < m1["spec_ticks"]    # the horizon fused


def test_pool_dry_requeue_drops_horizon_to_single_steps(cfg_params):
    """A pool-dry-requeued request waiting in the engine-owned _pending
    FIFO (with a free row!) must drop the fused horizon to single steps,
    exactly like an inbox arrival would — pages freed by finishing rows
    then come back at H=1 pace instead of the joiner waiting out full
    H-step horizons (the fallback's contract: a joining row never waits
    out a horizon)."""
    cfg, params = cfg_params
    # 3 usable pages (page 0 is scratch): A's 64-slot prompt takes 2 and
    # its first decode page the 3rd -> pool dry with a row still free
    eng = ServingEngine(cfg, params, EngineConfig(
        max_rows=2, max_seq_len=256, page_size=32, pool_pages=4,
        prefill_bucket=32, decode_horizon=8))
    a = Request(prompt_ids=list(RNG.integers(0, cfg.vocab_size, 64)),
                max_new_tokens=24)   # 64+24 stays inside page 3
    eng.submit(a)
    for _ in range(200):     # prefill + the first fused decode tick
        eng._tick()
        if len(a.output_ids) >= 1:
            break
    assert len(eng.alloc.free) == 0          # pool is dry
    assert eng._free_row() is not None       # but a row is free

    b = Request(prompt_ids=list(RNG.integers(0, cfg.vocab_size, 32)),
                max_new_tokens=4)
    eng.submit(b)
    eng._tick()              # b: inbox -> _pending, pool-dry requeue
    assert len(eng._pending) == 1
    eng._tick()              # a steady tick with b parked in _pending
    assert eng.metrics["decode_horizon_effective"] == 1, (
        "pool-dry joiner in _pending did not drop the horizon")

    for _ in range(400):     # a finishes, pages free, b admits + finishes
        eng._tick()
        if b.finish_reason is not None:
            break
    assert a.finish_reason == "length" and len(a.output_ids) == 24
    assert b.finish_reason == "length" and len(b.output_ids) == 4
