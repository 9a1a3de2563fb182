"""Serving engine + OpenAI API correctness.

The strong invariant: greedy requests running CONCURRENTLY through the
continuous-batching engine must produce exactly the tokens that plain
single-request generate produces — rows must not leak into each other.
(The reference has no unit test for its serving stack; SURVEY.md §4.)
"""

import json
import threading
import time
import urllib.request

import numpy as np
import pytest

from ipex_llm_tpu.generation import GenerationConfig, generate
from ipex_llm_tpu.serving.engine import (
    EngineConfig,
    Request,
    ServingEngine,
    stream_tokens,
)
from tests.test_decoder import rand_params, tiny_cfg

RNG = np.random.default_rng(21)


@pytest.fixture(scope="module")
def cfg_params():
    cfg = tiny_cfg(vocab_size=131, hidden_size=48, intermediate_size=96,
                   num_heads=4, num_kv_heads=2, head_dim=12,
                   max_position_embeddings=512)
    return cfg, rand_params(cfg, qtype="bf16")


@pytest.fixture(scope="module")
def engine(cfg_params):
    cfg, params = cfg_params
    eng = ServingEngine(
        cfg, params, EngineConfig(max_rows=3, max_seq_len=256,
                                  prefill_bucket=32)
    ).start()
    yield eng
    eng.stop()


def _reference_tokens(cfg, params, prompt, n):
    gen = GenerationConfig(max_new_tokens=n, do_sample=False)
    res = generate(cfg, params, [prompt], gen)
    return list(res.sequences[0, len(prompt):len(prompt) + n])


def _assert_greedy_stream(cfg, params, prompt, got, rel_tie=5e-3):
    """Teacher-forcing oracle check, tie-tolerant.

    The paged engine and dense ``generate`` are DIFFERENT XLA programs;
    bf16 reduction-order differences can flip argmax where two logits are
    numerically tied (r4's "concurrent corruption" was exactly such a flip
    — the engine was self-consistent, see rand_params' hermeticity note).
    Exact token equality across the two programs is therefore not a sound
    invariant.  This check is: every emitted token must be the dense
    oracle's argmax GIVEN THE ENGINE'S OWN PREFIX, or lie within the
    numerical-tie margin of it — real cross-row corruption produces large
    gaps and still fails loudly.  One full-sequence forward scores the
    whole stream (logits[j] predicts position j+1)."""
    from ipex_llm_tpu.transformers.model import TPUModelForCausalLM

    seq = list(map(int, prompt)) + list(map(int, got))
    tpad = 1 << max(len(seq) - 1, 1).bit_length()
    toks = np.zeros((1, tpad), np.int32)
    toks[0, :len(seq)] = seq
    model = TPUModelForCausalLM(cfg, params, {}, "bf16")
    lg = np.asarray(model(toks))[0]
    for j, tok in enumerate(map(int, got)):
        row = lg[len(prompt) - 1 + j]
        top = int(row.argmax())
        if tok == top:
            continue
        gap = float(row[top] - row[tok])
        spread = float(row.max() - row.min())
        # tie margin: a couple of bf16 ULPs at the logit magnitude (two
        # different XLA programs legitimately differ by 1-2 ULPs of
        # reduction rounding); real corruption shows gaps of order spread
        ulp = 2.0 ** (np.floor(np.log2(max(abs(float(row.max())), 1e-9)))
                      - 7)
        margin = max(rel_tie * max(spread, 1.0), 2.5 * ulp)
        assert gap <= margin, (
            f"stream token {j} diverges beyond the tie margin: got={tok} "
            f"oracle_top={top} gap={gap:.4f} margin={margin:.4f} "
            f"spread={spread:.3f}")


def test_concurrent_requests_match_single(cfg_params, engine):
    cfg, params = cfg_params
    prompts = [list(RNG.integers(0, cfg.vocab_size, n)) for n in (9, 17, 30)]
    reqs = [engine.submit(Request(prompt_ids=p, max_new_tokens=12))
            for p in prompts]
    got = [list(stream_tokens(r)) for r in reqs]
    for g, p in zip(got, prompts):
        _assert_greedy_stream(cfg, params, p, g)
    assert all(r.finish_reason == "length" for r in reqs)


def test_more_requests_than_rows(cfg_params, engine):
    """5 requests through 3 rows: queueing + row reuse must stay isolated."""
    cfg, params = cfg_params
    prompts = [list(RNG.integers(0, cfg.vocab_size, 8 + 3 * i))
               for i in range(5)]
    reqs = [engine.submit(Request(prompt_ids=p, max_new_tokens=8))
            for p in prompts]
    got = [list(stream_tokens(r)) for r in reqs]
    for g, p in zip(got, prompts):
        _assert_greedy_stream(cfg, params, p, g)


def test_eos_stops_row(cfg_params, engine):
    cfg, params = cfg_params
    prompt = list(RNG.integers(0, cfg.vocab_size, 10))
    # engine-own oracle: a full run through the SAME engine (same compiled
    # program) gives the exact stream; its 4th token becomes the eos
    full = engine.submit(Request(prompt_ids=prompt, max_new_tokens=12))
    ref = list(stream_tokens(full))
    eos = int(ref[3])
    req = engine.submit(Request(prompt_ids=prompt, max_new_tokens=12,
                                eos_token_id=(eos,)))
    got = list(stream_tokens(req))
    assert got == ref[:4]
    assert req.finish_reason == "stop"


def test_oversized_request_rejected(engine):
    req = engine.submit(Request(prompt_ids=[1] * 250, max_new_tokens=100))
    assert list(stream_tokens(req)) == []
    assert req.finish_reason == "length"


# ---------------------------------------------------------------------------
# HTTP layer
# ---------------------------------------------------------------------------


class _Tok:
    """Minimal id-passthrough tokenizer for HTTP tests."""

    eos_token_id = None
    chat_template = None

    def __call__(self, text):
        def tid(x):
            try:
                return int(x) % 131
            except ValueError:
                return hash(x) % 131
        return {"input_ids": [tid(x) for x in text.split()]}

    def decode(self, ids):
        return " ".join(str(i) for i in ids)


@pytest.fixture(scope="module")
def http_server(cfg_params):
    aiohttp = pytest.importorskip("aiohttp")
    import asyncio

    from ipex_llm_tpu.serving.api_server import OpenAIServer
    from aiohttp import web

    cfg, params = cfg_params
    eng = ServingEngine(
        cfg, params, EngineConfig(max_rows=2, max_seq_len=128,
                                  prefill_bucket=32)
    ).start()
    srv = OpenAIServer(eng, _Tok(), "tiny")

    loop = asyncio.new_event_loop()
    started = threading.Event()
    port_holder = {}

    def run():
        asyncio.set_event_loop(loop)
        runner = web.AppRunner(srv.app)
        loop.run_until_complete(runner.setup())
        site = web.TCPSite(runner, "127.0.0.1", 0)
        loop.run_until_complete(site.start())
        port_holder["port"] = site._server.sockets[0].getsockname()[1]
        started.set()
        loop.run_forever()

    t = threading.Thread(target=run, daemon=True)
    t.start()
    started.wait(10)
    yield port_holder["port"]
    loop.call_soon_threadsafe(loop.stop)
    eng.stop()


def _post(port, path, body):
    data = json.dumps(body).encode()
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}{path}", data=data,
        headers={"Content-Type": "application/json"},
    )
    return urllib.request.urlopen(req, timeout=120)


def test_http_completions(http_server):
    port = http_server
    resp = _post(port, "/v1/completions",
                 {"prompt": "1 2 3 4 5 6", "max_tokens": 6})
    body = json.loads(resp.read())
    assert body["object"] == "text_completion"
    assert len(body["choices"][0]["text"].split()) == 6


def test_http_chat_stream_two_in_flight(http_server):
    """Two streaming chat requests in flight; both must complete with SSE."""
    port = http_server
    results = {}

    def worker(name, msg):
        resp = _post(port, "/v1/chat/completions", {
            "messages": [{"role": "user", "content": msg}],
            "max_tokens": 8, "stream": True,
        })
        chunks = []
        for line in resp:
            line = line.decode().strip()
            if line.startswith("data: ") and line != "data: [DONE]":
                chunks.append(json.loads(line[6:]))
        results[name] = chunks

    t1 = threading.Thread(target=worker, args=("a", "7 8 9 10"))
    t2 = threading.Thread(target=worker, args=("b", "11 12 13 14 15"))
    t1.start(); t2.start()
    t1.join(120); t2.join(120)
    for name in ("a", "b"):
        chunks = results[name]
        pieces = [c["choices"][0]["delta"].get("content", "")
                  for c in chunks]
        assert sum(1 for p in pieces if p) == 8
        assert chunks[-1]["choices"][0]["finish_reason"] == "length"


def test_abort_frees_row(cfg_params, engine):
    cfg, params = cfg_params
    prompt = list(RNG.integers(0, cfg.vocab_size, 12))
    req = engine.submit(Request(prompt_ids=prompt, max_new_tokens=200))
    # read a couple of tokens, then hang up
    got = [req.stream_queue.get(timeout=60) for _ in range(2)]
    assert all(t is not None for t in got)
    engine.abort(req)
    # the stream must terminate (None) well before 200 tokens
    rest = list(stream_tokens(req))
    assert len(got) + len(rest) < 200
    assert req.finish_reason == "abort"


def test_http_stop_sequence(http_server):
    """A stop string truncates output and finishes with reason 'stop'."""
    port = http_server
    # discover the greedy continuation first (temperature pinned: the
    # server's OpenAI-compatible default is now 1.0 = sampled)
    resp = _post(port, "/v1/completions",
                 {"prompt": "20 21 22 23 24", "max_tokens": 6,
                  "temperature": 0.0})
    full = json.loads(resp.read())["choices"][0]["text"].split()
    stop_word = full[2]
    resp = _post(port, "/v1/completions",
                 {"prompt": "20 21 22 23 24", "max_tokens": 6,
                  "temperature": 0.0, "stop": stop_word})
    body = json.loads(resp.read())
    text = body["choices"][0]["text"]
    assert stop_word not in text.split()
    assert body["choices"][0]["finish_reason"] == "stop"


def test_http_response_format_json(http_server):
    """response_format json_object routes through constrained decoding."""
    from ipex_llm_tpu.structured import JsonValidator

    port = http_server
    resp = _post(port, "/v1/chat/completions", {
        "messages": [{"role": "user", "content": "1 2 3"}],
        "max_tokens": 24,
        "response_format": {"type": "json_object"},
    })
    body = json.loads(resp.read())
    text = body["choices"][0]["message"]["content"]
    v = JsonValidator()
    assert v.feed(text), text  # always a valid JSON prefix


def test_http_models_and_health(http_server):
    port = http_server
    body = json.loads(urllib.request.urlopen(
        f"http://127.0.0.1:{port}/v1/models", timeout=30).read())
    assert body["data"][0]["id"] == "tiny"
    health = json.loads(urllib.request.urlopen(
        f"http://127.0.0.1:{port}/health", timeout=30).read())
    assert health["status"] == "ok"
    # fused-horizon host-sync economics + mixed-step admission economics
    # ride the health payload
    dec = health["decode"]
    assert set(dec) == {"tokens_per_sync", "host_sync_s",
                        "decode_horizon_effective", "mixed_steps",
                        "mixed_fused_steps", "prefill_tokens_per_step",
                        "ttft_p95_s"}
    assert dec["host_sync_s"] >= 0.0
    assert dec["ttft_p95_s"] >= 0.0


# ---------------------------------------------------------------------------
# paged KV: prefix caching + concurrency at scale (VERDICT r2 item 4)
# ---------------------------------------------------------------------------


def test_prefix_cache_sharing(cfg_params):
    """Two requests with the same long prefix must share KV pages (the
    second prefills only the remainder) and still match plain generate."""
    cfg, params = cfg_params
    eng = ServingEngine(
        cfg, params, EngineConfig(max_rows=2, max_seq_len=256, page_size=32,
                                  prefill_bucket=32)
    ).start()
    try:
        prefix = list(RNG.integers(0, cfg.vocab_size, 80))
        p1 = prefix + [3, 5]
        p2 = prefix + [7, 9, 11]
        r1 = eng.submit(Request(prompt_ids=p1, max_new_tokens=8))
        got1 = list(stream_tokens(r1, timeout=120))
        r2 = eng.submit(Request(prompt_ids=p2, max_new_tokens=8))
        got2 = list(stream_tokens(r2, timeout=120))
        _assert_greedy_stream(cfg, params, p1, got1)
        _assert_greedy_stream(cfg, params, p2, got2)
        # 80-token shared prefix over 32-slot pages => 2 full shared pages
        assert eng.metrics["prefix_hits"] >= 1
        assert eng.metrics["prefix_pages_shared"] >= 2
    finally:
        eng.stop()


# slow tier: the 16-row wave compiles every (P, W) tick-program variant
# of the fused one-dispatch tick — the most compile-dominated test in the
# module (the behaviors it stresses stay fast-tier covered:
# test_concurrent_requests_match_single, test_serving_mixed's threaded
# e2e + contention, test_serving_horizon's page-pressure clamp)
@pytest.mark.slow
def test_sixteen_concurrent_streams(cfg_params):
    """>=16 concurrent mixed-length streams all complete correctly and
    per-token decode latency stays within ~2x of a single stream."""
    cfg, params = cfg_params
    eng = ServingEngine(
        cfg, params, EngineConfig(max_rows=16, max_seq_len=256, page_size=32,
                                  prefill_bucket=32)
    ).start()
    try:
        n_new = 10
        lengths = [7 + 3 * i for i in range(16)]           # 7..52 tokens
        prompts = [list(RNG.integers(0, cfg.vocab_size, n)) for n in lengths]

        # warm the programs first: a full concurrent wave walks the mixed
        # admission path through its (batch, width) buckets — a cold wave
        # would compile them inside the measured window.  DISTINCT draws
        # of the same lengths: warming with `prompts` would register their
        # pages in the prefix cache and hand the measured wave cached
        # prefills, skipping the admission path under test (private rng:
        # the module RNG's draw sequence feeds later tests' prompts)
        wrng = np.random.default_rng(99)
        warm = [eng.submit(Request(
                    prompt_ids=list(wrng.integers(0, cfg.vocab_size, n)),
                    max_new_tokens=4))
                for n in lengths]
        for w in warm:
            list(stream_tokens(w, timeout=600))
        # single-stream baseline per-token latency
        warm1 = eng.submit(Request(prompt_ids=prompts[0], max_new_tokens=n_new))
        list(stream_tokens(warm1, timeout=300))
        t0 = time.perf_counter()
        solo = eng.submit(Request(prompt_ids=prompts[1], max_new_tokens=n_new))
        list(stream_tokens(solo, timeout=300))
        solo_per_tok = (time.perf_counter() - t0) / n_new

        reqs = [eng.submit(Request(prompt_ids=p, max_new_tokens=n_new))
                for p in prompts]
        outs = {}
        t0 = time.perf_counter()
        threads = []

        def drain(i, r):
            outs[i] = list(stream_tokens(r, timeout=600))

        for i, r in enumerate(reqs):
            th = threading.Thread(target=drain, args=(i, r))
            th.start()
            threads.append(th)
        for th in threads:
            th.join(timeout=600)
        wall = time.perf_counter() - t0

        # (1) isolation invariant, EXACT: the same engine must reproduce
        # every stream single-request — _decode_step's math is row-
        # independent (per-row pages, per-row matmul rows), so concurrency
        # may never change a stream, bit for bit
        for i, p in enumerate(prompts):
            solo_req = eng.submit(Request(prompt_ids=p, max_new_tokens=n_new))
            solo = list(stream_tokens(solo_req, timeout=600))
            assert outs[i] == solo, (
                f"row {i}: concurrent stream differs from the same engine's "
                f"single-stream run — cross-row leak: {outs[i]} vs {solo}")
        # (2) cross-path oracle check, tie-tolerant (different XLA program)
        for i, p in enumerate(prompts):
            _assert_greedy_stream(cfg, params, p, outs[i])
        # aggregate per-token latency: 16 streams share each decode step, so
        # the whole batch should take ~16x solo tokens at ~solo step cost;
        # allow 2x (prefill interleaving + host overhead)
        per_tok = wall / (16 * n_new)
        assert per_tok < 2.0 * solo_per_tok + 0.05, (per_tok, solo_per_tok)
    finally:
        eng.stop()


def test_seeded_sampling_reproducible(cfg_params):
    """Request.seed gives a deterministic stream independent of batch
    composition; different seeds diverge (OpenAI seed / vLLM seed)."""
    cfg, params = cfg_params
    eng = ServingEngine(cfg, params, EngineConfig(
        max_rows=4, max_seq_len=128, page_size=32)).start()
    try:
        def run(seed, prompt=(3, 5, 7, 9)):
            req = Request(prompt_ids=list(prompt), max_new_tokens=8,
                          temperature=1.0, top_p=0.95, seed=seed)
            eng.submit(req)
            return tuple(stream_tokens(req))

        a = run(1234)
        # interleave an unrelated request so batch composition differs
        other = Request(prompt_ids=[2, 4, 6], max_new_tokens=4,
                        temperature=1.0)
        eng.submit(other)
        b = run(1234)
        list(stream_tokens(other))
        assert a == b, (a, b)
        c = run(4321)
        assert c != a
    finally:
        eng.stop()


def test_top_k_one_is_greedy(cfg_params):
    """top_k=1 at temperature 1 must reproduce greedy decoding exactly."""
    cfg, params = cfg_params
    eng = ServingEngine(cfg, params, EngineConfig(
        max_rows=2, max_seq_len=128, page_size=32)).start()
    try:
        greedy = Request(prompt_ids=[3, 5, 7, 9], max_new_tokens=8,
                         temperature=0.0)
        eng.submit(greedy)
        g = tuple(stream_tokens(greedy))
        k1 = Request(prompt_ids=[3, 5, 7, 9], max_new_tokens=8,
                     temperature=1.0, top_k=1)
        eng.submit(k1)
        assert tuple(stream_tokens(k1)) == g
    finally:
        eng.stop()


# -- speculative serving (VERDICT r3 missing #7 / next #6) -------------------


def test_speculative_engine_matches_plain(cfg_params):
    """Greedy requests through a spec_k engine must be token-identical to
    the plain engine (the lookup_generate guarantee inside continuous
    batching), and the acceptance metrics must be reported."""
    cfg, params = cfg_params
    prompts = [list(RNG.integers(0, cfg.vocab_size, n)) for n in (9, 21)]
    eng = ServingEngine(
        cfg, params,
        EngineConfig(max_rows=2, max_seq_len=256, prefill_bucket=32,
                     spec_k=3),
    ).start()
    try:
        reqs = [eng.submit(Request(prompt_ids=p, max_new_tokens=14))
                for p in prompts]
        got = [list(stream_tokens(r)) for r in reqs]
    finally:
        eng.stop()
    for g, p in zip(got, prompts):
        assert len(g) == 14
        _assert_greedy_stream(cfg, params, p, g)
    assert eng.metrics["spec_steps"] > 0
    assert 0.0 < eng.metrics["spec_accept_rate"] <= 1.0


def test_speculative_accepts_on_repetitive_sequence(cfg_params):
    """A strongly periodic prompt must make prompt-lookup accept drafts:
    fewer verify steps than emitted tokens."""
    cfg, params = cfg_params
    # a prompt whose greedy continuation the model repeats (cycle prompt).
    # From a generator of its own: drawn from the module's, the cycle
    # depended on which tests the worker had run before, and one draw in
    # four repeats nothing the proposer can use (19 steps for 19 tokens)
    RNG.integers(0, cfg.vocab_size, 4)   # (the later tests' draws stay)
    base = [int(t) for t in np.random.default_rng(7).integers(
        0, cfg.vocab_size, 4)]
    prompt = base * 8
    eng = ServingEngine(
        cfg, params,
        EngineConfig(max_rows=1, max_seq_len=256, prefill_bucket=32,
                     spec_k=4),
    ).start()
    try:
        req = eng.submit(Request(prompt_ids=prompt, max_new_tokens=20))
        got = list(stream_tokens(req))
    finally:
        eng.stop()
    assert len(got) == 20
    _assert_greedy_stream(cfg, params, prompt, got)
    # decode emitted 20 tokens minus the prefill-sampled first one; if any
    # draft chain accepted, steps < 19
    assert eng.metrics["spec_emitted"] >= 19
    assert eng.metrics["spec_steps"] < 19, eng.metrics


def test_speculative_optout_and_sampled_rows(cfg_params):
    """speculative=False rows and temperature>0 rows still serve correctly
    through the wide step (one token per step, seeded-reproducible)."""
    cfg, params = cfg_params
    p1 = list(RNG.integers(0, cfg.vocab_size, 12))
    want = _reference_tokens(cfg, params, p1, 8)
    eng = ServingEngine(
        cfg, params,
        EngineConfig(max_rows=2, max_seq_len=256, prefill_bucket=32,
                     spec_k=2),
    ).start()
    try:
        r1 = eng.submit(Request(prompt_ids=p1, max_new_tokens=8,
                                speculative=False))
        r2 = eng.submit(Request(prompt_ids=p1, max_new_tokens=8,
                                temperature=0.8, seed=7))
        g1 = list(stream_tokens(r1))
        g2 = list(stream_tokens(r2))
        r3 = eng.submit(Request(prompt_ids=p1, max_new_tokens=8,
                                temperature=0.8, seed=7))
        g3 = list(stream_tokens(r3))
    finally:
        eng.stop()
    _assert_greedy_stream(cfg, params, p1, g1)
    np.testing.assert_array_equal(g2, g3)  # same seed, same stream


def test_speculative_sampled_seeded_matches_plain_engine(cfg_params):
    """VERDICT r4 next #4: temperature>0 requests get REAL speculative
    acceptance with distribution preservation.  A seeded sampled stream
    through a spec_k engine must be bit-identical to the plain engine's
    stream — every verify position samples with fold_in(seed, output_index),
    the same key the plain step uses — and acceptance must be > 0 on a
    periodic prompt."""
    cfg, params = cfg_params
    base = list(RNG.integers(0, cfg.vocab_size, 4))
    prompt = base * 8

    def run(ec):
        eng = ServingEngine(cfg, params, ec).start()
        try:
            req = eng.submit(Request(prompt_ids=prompt, max_new_tokens=16,
                                     temperature=0.8, top_p=0.95, seed=97))
            return list(stream_tokens(req)), dict(eng.metrics)
        finally:
            eng.stop()

    plain, _ = run(EngineConfig(max_rows=1, max_seq_len=256,
                                prefill_bucket=32))
    spec, m = run(EngineConfig(max_rows=1, max_seq_len=256,
                               prefill_bucket=32, spec_k=3))
    assert spec == plain, (spec, plain)
    assert m["spec_steps"] > 0
    assert 0.0 < m["spec_accept_rate"] <= 1.0
    # the distribution-preserving chain should accept at least once on a
    # strongly periodic prompt with a seeded stream
    assert m["spec_emitted"] >= m["spec_steps"]


# slow tier: long churn over an overcommitted pool — compile-dominated
# under the fused tick's (P, W) variants; fast contention coverage rides
# test_serving_mixed::test_mixed_respects_page_pool_contention and
# test_serving_horizon::test_horizon_shortens_under_page_pressure
@pytest.mark.slow
def test_pool_contention_under_load(cfg_params):
    """VERDICT r3 weak #9: drive the paged pool into contention — more
    concurrent demand than pages — and require every request to either
    complete CORRECTLY or fail loudly with 'length', never corrupt."""
    cfg, params = cfg_params
    eng = ServingEngine(
        cfg, params,
        EngineConfig(max_rows=8, max_seq_len=256, page_size=16,
                     pool_pages=24, prefill_bucket=32),
    ).start()
    try:
        prompts = [list(RNG.integers(0, cfg.vocab_size, 20 + 7 * i))
                   for i in range(12)]
        reqs = [eng.submit(Request(prompt_ids=p, max_new_tokens=24))
                for p in prompts]
        got = [list(stream_tokens(r, timeout=600)) for r in reqs]
    finally:
        eng.stop()
    completed = 0
    for gi, (g, r) in enumerate(zip(got, reqs)):
        if r.finish_reason == "length" and len(g) == 24:
            # tie-tolerant oracle check (the engine is a different XLA
            # program than generate; see _assert_greedy_stream)
            _assert_greedy_stream(cfg, params, prompts[gi], g)
            completed += 1
        else:
            # pool-dry rejection is allowed under contention, silence isn't
            assert r.finish_reason in ("length", "error"), r.finish_reason
    assert completed >= 8, f"only {completed}/12 served under contention"
    # every page either free or held ONLY by the prefix cache (refcount 1)
    cached = set(eng.alloc.prefix.values())
    for pid in range(1, eng.alloc.n_pages):
        refs = int(eng.alloc.ref[pid])
        assert refs == 0 or (pid in cached and refs == 1), (pid, refs)
