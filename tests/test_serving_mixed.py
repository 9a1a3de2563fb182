"""Mixed prefill+decode step (admission-wave batching).

The contract under test: while any row is prefilling, the engine runs ONE
jitted program per tick — ragged batched prefill chunks for every joining
row, the decode step for every active row, and on-device first-token
sampling for rows whose prompt completes — and a request's token AND
logprob bits do not depend on what else is in the batch: served inside a
staggered wave (narrow chunks, neighbours joining and leaving) it emits
what it emits served alone on the same engine configuration (its chunks
take the whole budget), across greedy rows, seeded sampled rows, and rows
that finish prefill mid-wave while others are still prefilling.

Plus the dispatch-economics tier-1 guard: an admission wave of R rows must
issue O(total_prompt_tokens / budget) device programs and host syncs, not
O(R x chunks).

Engines are driven synchronously through ``_step_once`` (never started),
so submission timing is deterministic tick-for-tick.
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

RNG = np.random.default_rng(43)

EC = dict(max_rows=4, max_seq_len=256, page_size=32, prefill_bucket=32)


@pytest.fixture(scope="module")
def cfg_params():
    cfg = tiny_cfg(vocab_size=131, hidden_size=48, intermediate_size=96,
                   num_heads=4, num_kv_heads=2, head_dim=12,
                   max_position_embeddings=512)
    return cfg, rand_params(cfg, qtype="bf16")


def _drive(eng, schedule, max_ticks=3000):
    """Run the engine loop synchronously, submitting ``schedule[tick]``'s
    requests before that tick; returns each request's drained stream in
    schedule order."""
    reqs = [r for _, rs in sorted(schedule.items()) for r in rs]
    for t in range(max_ticks):
        for r in schedule.get(t, ()):
            eng.submit(r)
        eng._step_once()
        if all(r.finish_reason is not None for r in reqs):
            break
    assert all(r.finish_reason is not None for r in reqs), (
        [r.finish_reason for r in reqs])
    return [list(stream_tokens(r, timeout=10)) for r in reqs]


def _served(req, stream):
    """What a client saw of ``req``: tokens, logprob BITS, finish."""
    return (stream, np.asarray(req.logprobs, np.float32).tobytes(),
            req.finish_reason)


def _assert_rows_do_not_depend_on_batch(cfg, params, ec, schedule):
    """THE batch-invariance oracle.  ``schedule`` maps tick -> request
    kwargs; the requests are served once as that staggered wave and once
    each ALONE on a fresh engine of the same configuration, and every
    request must emit the same tokens, the same logprob bits and the same
    finish reason both ways.  (Greedy and seeded rows only: an unseeded
    temperature>0 row draws from the engine's key chain, which carries no
    reproducibility contract.)  Returns (wave engine, wave streams)."""
    wave = {t: [Request(**kw) for kw in kws] for t, kws in schedule.items()}
    eng = ServingEngine(cfg, params, ec)
    streams = _drive(eng, wave)
    reqs = [r for _, rs in sorted(wave.items()) for r in rs]
    kws = [kw for _, ks in sorted(schedule.items()) for kw in ks]
    for i, (req, stream, kw) in enumerate(zip(reqs, streams, kws)):
        solo = Request(**kw)
        (alone,) = _drive(ServingEngine(cfg, params, ec), {0: [solo]})
        assert _served(req, stream) == _served(solo, alone), (
            f"request {i} depends on its batch", stream, alone)
    return eng, streams


def _wave_specs(cfg):
    """Greedy long row, seeded sampled longer row, greedy short row that
    finishes prefill mid-wave (while the seeded row is still consuming its
    prompt) and decodes alongside the others' remaining chunks."""
    p1 = list(RNG.integers(0, cfg.vocab_size, 40))
    p2 = list(RNG.integers(0, cfg.vocab_size, 70))
    p3 = list(RNG.integers(0, cfg.vocab_size, 24))
    return [
        dict(prompt_ids=p1, max_new_tokens=12),
        dict(prompt_ids=p2, max_new_tokens=12, temperature=0.8, top_p=0.9,
             top_k=40, seed=123),
        dict(prompt_ids=p3, max_new_tokens=12),
    ]


def test_a_row_in_a_staggered_wave_matches_the_row_alone(cfg_params):
    """Staggered admissions emit, request by request, the exact token and
    logprob streams each request gets served alone — greedy, seeded
    sampled, and a row finishing prefill mid-wave."""
    cfg, params = cfg_params
    specs = _wave_specs(cfg)
    eng, streams = _assert_rows_do_not_depend_on_batch(
        cfg, params, EngineConfig(**EC),
        {0: [specs[0]], 1: [specs[1]], 3: [specs[2]]})
    assert eng.metrics["mixed_steps"] > 0       # the wave really mixed
    _assert_greedy_stream(cfg, params, specs[0]["prompt_ids"], streams[0])
    # first tokens were sampled on device inside mixed ticks
    assert eng.metrics["prefill_tokens_per_step"] > 0


# the fused mixed tick (PR 52): a dense model's chunk and its decode rows in
# ONE forward.  kernel-forced: int4 weights through the dequant-matmul kernel
# (interpret mode), the pool's write and walk through theirs
FUSED = {
    "bf16-pool": dict(),
    "fp8-pool": dict(kv_storage="fp8"),
    "int4-kernel-forced": dict(qtype="sym_int4", force_pallas=True),
}


def _lens_by_tick(eng, schedule, max_ticks=400):
    """``_drive``, keeping every request's output length after each tick."""
    reqs = [r for _, rs in sorted(schedule.items()) for r in rs]
    seen = []
    for t in range(max_ticks):
        for r in schedule.get(t, ()):
            eng.submit(r)
        eng._step_once()
        seen.append([len(r.output_ids) for r in reqs])
        if all(r.finish_reason is not None for r in reqs):
            break
    streams = [list(stream_tokens(r, timeout=10)) for r in reqs]
    return reqs, streams, np.asarray(seen)


@pytest.mark.parametrize("variant", list(FUSED))
def test_fused_mixed_ticks_match_the_row_alone_and_the_staged_form(
        variant, cfg_params, monkeypatch):
    """A dense model's mixed tick makes ONE pass over the weights, the
    chunk's tokens and the decode rows in one forward.  A request served in
    a staggered wave through such ticks emits the tokens and logprob bits
    it emits ALONE, and those the STAGED form of the same wave emits (the
    prefill block's forward, the merge, then the rows' forward): greedy and
    seeded sampled rows, over bf16 and fp8 pools, and int4 weights with
    the kernels forced.  What differs is when: a row's first decode step
    is the tick AFTER the one that completes its prompt."""
    from ipex_llm_tpu.ops import dispatch
    from ipex_llm_tpu.serving import engine as engine_mod

    opts = dict(FUSED[variant])
    cfg, params = cfg_params
    forced = opts.pop("force_pallas", False)
    if forced:
        cfg = tiny_cfg(vocab_size=131, hidden_size=128,
                       intermediate_size=256, num_heads=4, num_kv_heads=2,
                       head_dim=128, max_position_embeddings=512)
        monkeypatch.setenv("IPEX_LLM_TPU_FORCE_PALLAS", "1")
        dispatch.clear_cache()
    try:
        if "qtype" in opts:
            params = rand_params(cfg, qtype=opts.pop("qtype"))
        ec = EngineConfig(**EC, **opts)
        specs = _wave_specs(cfg)
        schedule = {0: [specs[0]], 1: [specs[1]], 3: [specs[2]]}
        eng, streams = _assert_rows_do_not_depend_on_batch(
            cfg, params, ec, schedule)
        wave = {t: [Request(**kw) for kw in kws]
                for t, kws in schedule.items()}
        reqs, again, lens = _lens_by_tick(ServingEngine(cfg, params, ec),
                                          wave)
        # the staged form of the same wave, by the same engine code
        monkeypatch.setattr(engine_mod, "fuses_mixed_tick",
                            lambda *a: False)
        engine_mod._ragged_tick.clear_cache()
        wave = {t: [Request(**kw) for kw in kws]
                for t, kws in schedule.items()}
        staged = ServingEngine(cfg, params, ec)
        s_reqs, s_streams, s_lens = _lens_by_tick(staged, wave)
    finally:
        monkeypatch.undo()
        dispatch.clear_cache()
        engine_mod._ragged_tick.clear_cache()
    m, ms = eng.metrics, staged.metrics
    assert m["mixed_fused_steps"] == m["mixed_steps"] > 2
    assert ms["mixed_fused_steps"] == 0 and ms["mixed_steps"] > 2
    assert again == streams
    for r, s, a, b in zip(reqs, s_reqs, again, s_streams):
        assert _served(r, a) == _served(s, b)
    # a joiner's second token arrives one tick after its first; staged,
    # both come home in the tick that completes the prompt
    for col, s_col in zip(lens.T, s_lens.T):
        first = int(np.flatnonzero(col)[0])
        assert (col[first], col[first + 1]) == (1, 2)
        assert s_col[np.flatnonzero(s_col)[0]] == 2


def test_first_token_eos_mid_wave_matches_the_row_alone(cfg_params):
    """A row whose FIRST sampled token is its EOS finishes from inside a
    mixed tick with reason 'stop' while the other row keeps prefilling —
    and both rows emit what they emit alone."""
    cfg, params = cfg_params
    p_short = list(RNG.integers(0, cfg.vocab_size, 20))
    p_long = list(RNG.integers(0, cfg.vocab_size, 60))
    # discover the short prompt's greedy first token via a probe run
    probe = ServingEngine(cfg, params, EngineConfig(**EC))
    (ptoks,) = _drive(probe, {0: [Request(prompt_ids=p_short,
                                          max_new_tokens=2)]})
    eos = int(ptoks[0])

    eng, streams = _assert_rows_do_not_depend_on_batch(
        cfg, params, EngineConfig(**EC),
        {0: [dict(prompt_ids=p_long, max_new_tokens=8)],
         1: [dict(prompt_ids=p_short, max_new_tokens=8,
                  eos_token_id=(eos,))]})
    assert eng.metrics["mixed_steps"] > 0
    assert streams[1] == [eos]


def test_admission_wave_sync_budget_tier1(cfg_params):
    """Tier-1 dispatch-economics guard: a simultaneous 3-row admission
    wave must stay under the budgeted ceiling of blocking host syncs and
    device programs.  A regression to per-row per-chunk dispatch
    (O(R x chunks)) blows both bounds."""
    cfg, params = cfg_params
    prompts = [list(RNG.integers(0, cfg.vocab_size, 64)) for _ in range(3)]
    reqs = [Request(prompt_ids=p, max_new_tokens=4) for p in prompts]
    eng = ServingEngine(cfg, params, EngineConfig(**EC))  # budget = 32
    _drive(eng, {0: reqs})
    m = dict(eng.metrics)
    # 192 prompt tokens / (3 rows x 8-token pow2 share) = 8 prefill ticks;
    # only the completion tick and the 3 decode steps block on the device
    assert m["mixed_steps"] <= 10, m
    assert m["host_syncs"] <= 6, m


def test_mixed_respects_page_pool_contention(cfg_params):
    """Mixed admission under an overcommitted pool: every request either
    completes correctly or fails loudly ('length'/'error'), never
    corrupts, and the pool drains back to free."""
    cfg, params = cfg_params
    prompts = [list(RNG.integers(0, cfg.vocab_size, 30 + 10 * i))
               for i in range(4)]
    reqs = [Request(prompt_ids=p, max_new_tokens=12) for p in prompts]
    eng = ServingEngine(cfg, params, EngineConfig(
        max_rows=4, max_seq_len=256, page_size=16, pool_pages=18,
        prefill_bucket=32))
    streams = _drive(eng, {0: reqs})
    served = 0
    for p, r, s in zip(prompts, reqs, streams):
        if r.finish_reason == "length" and len(s) == 12:
            _assert_greedy_stream(cfg, params, p, s)
            served += 1
        else:
            assert r.finish_reason in ("length", "error"), r.finish_reason
    assert served >= 1, [r.finish_reason for r in reqs]
    cached = set(eng.alloc.prefix.values())
    for pid in range(1, eng.alloc.n_pages):
        refs = int(eng.alloc.ref[pid])
        assert refs == 0 or (pid in cached and refs == 1), (pid, refs)


def test_step_token_budget_zero_is_refused(cfg_params):
    """The budget is the positive per-tick prefill budget (None = the
    prefill bucket): 0, which used to select a sequential engine, and
    negative values are refused by name; a positive one serves."""
    cfg, params = cfg_params
    for bad in (0, -1):
        with pytest.raises(ValueError, match="step_token_budget.*positive"):
            ServingEngine(cfg, params,
                          EngineConfig(step_token_budget=bad, **EC))
    prompt = list(RNG.integers(0, cfg.vocab_size, 40))
    eng = ServingEngine(cfg, params,
                        EngineConfig(step_token_budget=16, **EC))
    (stream,) = _drive(eng, {0: [Request(prompt_ids=prompt,
                                         max_new_tokens=8)]})
    assert eng.metrics["mixed_steps"] == 3      # 40 tokens, 16 a tick
    _assert_greedy_stream(cfg, params, prompt, stream)


def test_inbox_peek_preserves_fifo(cfg_params):
    """The idle-path peek must not consume or reorder the inbox (the old
    get()+put() rotated the head request behind later arrivals), and
    queued requests admit in submission order."""
    cfg, params = cfg_params
    eng = ServingEngine(cfg, params,
                        EngineConfig(**{**EC, "max_rows": 1}))
    r1 = Request(prompt_ids=[3, 5, 7], max_new_tokens=4)
    r2 = Request(prompt_ids=[9, 11, 13], max_new_tokens=4)
    eng._inbox.put(r1)
    eng._inbox.put(r2)
    eng._wait_for_work(0.0)
    assert list(eng._inbox.queue) == [r1, r2]  # untouched, in order

    # with one row, the first-submitted request must finish first
    for _ in range(1000):
        eng._step_once()
        if r1.finish_reason is not None or r2.finish_reason is not None:
            break
    assert r1.finish_reason is not None and r2.finish_reason is None
    for _ in range(1000):
        eng._step_once()
        if r2.finish_reason is not None:
            break
    assert len(list(stream_tokens(r1, timeout=10))) == 4
    assert len(list(stream_tokens(r2, timeout=10))) == 4


def test_mixed_concurrent_threads_end_to_end(cfg_params):
    """The started (threaded) engine serves a staggered churn wave through
    the mixed step: all streams complete, greedy rows match the oracle,
    and /health's admission metrics populate."""
    import threading
    import time

    cfg, params = cfg_params
    prompts = [list(RNG.integers(0, cfg.vocab_size, n))
               for n in (22, 45, 67, 33)]
    eng = ServingEngine(cfg, params, EngineConfig(**EC)).start()
    try:
        reqs = [Request(prompt_ids=p, max_new_tokens=6) for p in prompts]
        outs = {}

        def drain(i, r):
            outs[i] = list(stream_tokens(r, timeout=600))

        threads = []
        for i, r in enumerate(reqs):
            eng.submit(r)
            th = threading.Thread(target=drain, args=(i, r))
            th.start()
            threads.append(th)
            time.sleep(0.02)  # staggered joins mid-wave
        for th in threads:
            th.join(timeout=600)
    finally:
        eng.stop()
    assert all(r.finish_reason == "length" for r in reqs)
    for i, p in enumerate(prompts):
        assert len(outs[i]) == 6
        _assert_greedy_stream(cfg, params, p, outs[i])
    assert eng.metrics["mixed_steps"] > 0
    assert eng.metrics["ttft_p95_s"] > 0.0
