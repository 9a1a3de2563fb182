"""Ragged paged-attention superkernel tick (ONE dispatch per engine tick).

The contract under test: every engine tick — admission wave or steady
state — lowers to the SINGLE jitted entry ``_ragged_tick`` (ragged
prefill + on-device first-token merge + fused decode horizon), the only
jitted program the engine module holds, and a request's token AND
logprob bits do not depend on what else rides the tick (greedy and
seeded sampled rows, H=1 and H=8), under bf16 AND fp8 KV storage.
Plus: the dead-row scratch-route regression (stale
device lens on masked rows must never corrupt live pages) and the
tightened host-sync budget.
"""

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from ipex_llm_tpu.hostutil import h2d
from ipex_llm_tpu.kv import PagedKVCache
from ipex_llm_tpu.serving.engine import (
    EngineConfig,
    Request,
    ServingEngine,
    _ragged_tick,
    stream_tokens,
)
from tests.test_decoder import rand_params, tiny_cfg
from tests.test_serving import _assert_greedy_stream
from tests.test_serving_mixed import (_assert_rows_do_not_depend_on_batch,
                                      _drive)

RNG = np.random.default_rng(47)

EC = dict(max_rows=4, max_seq_len=256, page_size=32, prefill_bucket=32)


@pytest.fixture(scope="module")
def cfg_params():
    cfg = tiny_cfg(vocab_size=127, hidden_size=48, intermediate_size=96,
                   num_heads=4, num_kv_heads=2, head_dim=12,
                   max_position_embeddings=512)
    return cfg, rand_params(cfg, qtype="bf16")


def _wave_specs(cfg):
    p1 = list(RNG.integers(0, cfg.vocab_size, 40))
    p2 = list(RNG.integers(0, cfg.vocab_size, 70))
    p3 = list(RNG.integers(0, cfg.vocab_size, 24))
    return [
        dict(prompt_ids=p1, max_new_tokens=10),
        dict(prompt_ids=p2, max_new_tokens=10, temperature=0.8, top_p=0.9,
             top_k=40, seed=321),
        dict(prompt_ids=p3, max_new_tokens=10),
    ]


# -- bit-identity through the superkernel tick ------------------------------
#
# Tier note: the fast tier's other suites gate the same invariant on
# their own axes (test_serving_mixed: a greedy/seeded wave at bf16 +
# first-token EOS + contention; test_serving_horizon: H8==H1;
# test_serving_kv_storage: fp8).  The slow re-statements below are the
# ragged suite's own end-to-end forms.

@pytest.mark.parametrize("horizon", (1, 8))
@pytest.mark.parametrize("kv", ("bf16", "fp8"))
def test_a_seeded_sampled_row_does_not_depend_on_its_batch(cfg_params, kv,
                                                           horizon):
    """Temperature > 0 with a per-request seed: every row of a staggered
    wave of SAMPLED requests (top-p, top-k, plain temperature) draws the
    tokens and logprob bits it draws served alone — its stream is keyed
    by fold_in(seed, output index), never by the tick's key chain, the
    chunk widths or the neighbours.  At horizon 8 the wave's joining
    ticks run H=1 and the row alone runs H=8 blocks."""
    cfg, params = cfg_params
    rng = np.random.default_rng(7)
    sampled = (dict(temperature=0.8, top_p=0.9, top_k=40, seed=321),
               dict(temperature=1.1, seed=5),
               dict(temperature=0.6, top_k=8, seed=77))
    specs = [dict(prompt_ids=list(rng.integers(0, cfg.vocab_size, n)),
                  max_new_tokens=11, **kw)
             for n, kw in zip((40, 70, 24), sampled)]
    eng, streams = _assert_rows_do_not_depend_on_batch(
        cfg, params,
        EngineConfig(kv_storage=kv, decode_horizon=horizon, **EC),
        {0: [specs[0]], 1: [specs[1]], 3: [specs[2]]})
    assert eng.metrics["mixed_steps"] > 0
    assert eng.metrics["decode_horizon_effective"] == horizon
    assert [len(s) for s in streams] == [11, 11, 11]
    # the draws are draws: a greedy run of the same prompt differs
    (greedy,) = _drive(
        ServingEngine(cfg, params,
                      EngineConfig(kv_storage=kv, decode_horizon=horizon,
                                   **EC)),
        {0: [Request(prompt_ids=specs[1]["prompt_ids"],
                     max_new_tokens=11)]})
    assert greedy != streams[1]


@pytest.mark.slow
@pytest.mark.parametrize("kv", ("bf16", "fp8"))
def test_a_row_in_a_wave_matches_the_row_alone(cfg_params, kv):
    """Staggered admissions through the one-dispatch tick emit, request by
    request, the token and logprob streams each gets served alone —
    greedy, seeded sampled, and a mid-wave finish — under both KV
    storages."""
    cfg, params = cfg_params
    specs = _wave_specs(cfg)
    eng, streams = _assert_rows_do_not_depend_on_batch(
        cfg, params, EngineConfig(kv_storage=kv, **EC),
        {0: [specs[0]], 1: [specs[1]], 3: [specs[2]]})
    assert eng.metrics["mixed_steps"] > 0
    if kv == "bf16":
        _assert_greedy_stream(cfg, params, specs[0]["prompt_ids"],
                              streams[0])


@pytest.mark.slow
@pytest.mark.parametrize("kv", ("bf16", "fp8"))
def test_h8_bit_identical_to_h1(cfg_params, kv):
    """H=8 steady-state decode through the superkernel entry emits H=1's
    exact streams (tokens AND logprobs), bf16 and fp8."""
    cfg, params = cfg_params
    specs = _wave_specs(cfg)

    def run(h):
        sched = {0: [Request(**s) for s in specs]}
        eng = ServingEngine(cfg, params, EngineConfig(
            kv_storage=kv, decode_horizon=h, **EC))
        streams = _drive(eng, sched)
        return streams, [r.logprobs for rs in sched.values() for r in rs], \
            eng.metrics

    s1, lp1, _ = run(1)
    s8, lp8, m8 = run(8)
    assert s8 == s1
    for a, b in zip(lp8, lp1):
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32))
    assert m8["decode_horizon_effective"] == 8


@pytest.mark.slow
def test_first_token_eos_finishes_inside_the_tick(cfg_params):
    """A row whose FIRST sampled token is its EOS finishes 'stop' from
    inside the fused tick (the on-device join must keep it OUT of the
    decode stage) while another row keeps prefilling — and both rows emit
    what they emit alone."""
    cfg, params = cfg_params
    p_short = list(RNG.integers(0, cfg.vocab_size, 20))
    p_long = list(RNG.integers(0, cfg.vocab_size, 60))
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


@pytest.mark.slow
def test_pool_contention_clamp(cfg_params):
    """Overcommitted pool through the one-dispatch tick: every request
    completes correctly or fails loudly ('length'/'error'), the clamp
    counters fire instead of silent corruption, and the pool drains."""
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
    for pid in range(1, eng.alloc.n_pages):
        refs = int(eng.alloc.ref[pid])
        cached = set(eng.alloc.prefix.values())
        assert refs == 0 or (pid in cached and refs == 1), (pid, refs)


def test_one_sync_per_tick_tier1(cfg_params):
    """Tier-1 dispatch-economics guard, tightened for the superkernel: a
    simultaneous 3-row wave pays ONE blocking sync per tick that emits
    (completion tick + per-decode-tick), and pure-chunk ticks pay none:
    first tokens ride the tick's one sync."""
    cfg, params = cfg_params
    prompts = [list(RNG.integers(0, cfg.vocab_size, 64)) for _ in range(3)]
    reqs = [Request(prompt_ids=p, max_new_tokens=4) for p in prompts]
    eng = ServingEngine(cfg, params, EngineConfig(**EC))
    _drive(eng, {0: reqs})
    m = eng.metrics
    # 192 prompt tokens / (3 rows x 8-token pow2 share) = 8 prefill ticks
    # (7 pure-chunk: no sync) + 1 completion tick (one fused sync) +
    # 3 steady decode ticks (one sync each) = 4 blocking syncs
    assert m["mixed_steps"] <= 10, m
    assert m["host_syncs"] <= 5, m
    assert m["tokens_per_sync"] >= 2.0, m


# -- one jitted program, driven directly ------------------------------------

def test_the_engine_module_holds_one_jitted_tick():
    """The only ``jax.jit``-wrapped callables ``serving.engine`` holds are
    ``_ragged_tick`` and the small program that takes a packed epoch apart
    where the tick has no prefill block (``epoch.unpack_rows``): a second
    engine (a sequential prefill program, a host-walk verify step, a pp
    step) cannot come back unnoticed.  Oracles live in the tests."""
    from ipex_llm_tpu.serving import engine as engine_mod

    jitted = {name: obj for name, obj in vars(engine_mod).items()
              if hasattr(obj, "lower") and hasattr(obj, "clear_cache")}
    assert set(jitted) == {"_ragged_tick", "unpack_rows"}, sorted(jitted)


def _random_pool_state(cfg, kv: str, seed: int = 0):
    """A filled 4-row paged pool with rows 0/1 mid-decode, rows 2/3
    idle, and the device row state beside it."""
    rng = np.random.default_rng(seed)
    r, ps, maxp, pages = 4, 16, 4, 24
    cache = PagedKVCache.init(
        cfg.num_layers, pages, r, maxp, cfg.num_kv_heads, ps,
        cfg.head_dim, v_head_dim=cfg.v_dim, storage=kv)
    tables = np.asarray(
        1 + np.arange(r * maxp, dtype=np.int32).reshape(r, maxp))
    pool_shape = cache.k.shape
    kpool = jnp.asarray(rng.standard_normal(pool_shape),
                        jnp.float32).astype(cache.k.dtype)
    vpool = jnp.asarray(rng.standard_normal(cache.v.shape),
                        jnp.float32).astype(cache.v.dtype)
    cache = PagedKVCache(k=kpool, v=vpool, tables=jnp.asarray(tables),
                         length=cache.length, storage=kv)
    state = dict(
        toks=np.asarray([5, 9, 0, 0], np.int32),
        row_lens=np.asarray([20, 9, 8, 0], np.int32),
        active=np.asarray([True, True, False, False]),
        temps=np.asarray([0.0, 0.8, 0.5, 0.0], np.float32),
        top_ps=np.asarray([1.0, 0.9, 0.95, 1.0], np.float32),
        seeds=np.asarray([-1, 7, 3, -1], np.int32),
        steps=np.asarray([2, 1, 0, 0], np.int32),
        top_ks=np.asarray([0, 5, 4, 0], np.int32),
        eos=np.asarray([[1, -1], [1, -1], [1, -1], [1, -1]], np.int32),
        remain=np.asarray([4, 5, 6, 0], np.int32),
    )
    return cache, state


def _dev_state(state):
    return {k: h2d(v) for k, v in state.items()}


def test_stale_device_lens_cannot_corrupt_neighbors(cfg_params):
    """Regression (the PR 2 scratch-route rule, carried to the ragged
    tick): a masked/dead row whose DEVICE row_lens is stale — pointing
    into a LIVE row's allocated pages — must route its decode KV write to
    the scratch page.  After the tick, no page except scratch (page 0)
    and the live row's own write slot may change."""
    cfg, params = cfg_params
    cache, st = _random_pool_state(cfg, "bf16", seed=3)
    # row 1 is DEAD this tick but its stale device len points straight
    # into row 0's history (row 0's pages are 1..4, slots 0..63)
    st["active"] = np.asarray([True, False, False, False])
    st["row_lens"] = np.asarray([20, 10, 0, 0], np.int32)
    k_before = np.asarray(cache.k.astype(jnp.float32)).copy()
    dev = _dev_state(st)
    _home, cache, *_rest = _ragged_tick(
        cfg, params, cache, dev["toks"], dev["row_lens"], dev["active"],
        dev["temps"], dev["top_ps"], jax.random.PRNGKey(0), dev["seeds"],
        dev["steps"], dev["top_ks"], dev["eos"], dev["remain"],
        prefill=None, horizon=1, with_decode=True)
    k_after = np.asarray(cache.k.astype(jnp.float32))
    ps = 16
    # row 0 wrote exactly its slot 20 -> page 1+20//16 = page 2, offset 4
    changed = np.argwhere(
        (k_before != k_after).any(axis=(0, 2, 4)))  # [page, slot] pairs
    assert len(changed), "the live row must have written its slot"
    for page, slot in changed:
        assert page == 0 or (page == 1 + 20 // ps and slot == 20 % ps), (
            f"page {page} slot {slot} corrupted by a dead row's stale len")


# -- a dense model's dead decode rows are idle rows of the kernel -------------

def test_dense_dead_rows_are_idle_and_live_rows_keep_their_bits(monkeypatch):
    """The decode stage hands EVERY model's dead rows to the attention
    kernel as idle rows (``chunk_len`` 0: no page walked, zero output), as
    it always did for a hybrid model; live rows get ``chunk_len`` 1 and the
    ``kv_len`` they always had, so their tokens and log-probabilities are
    bit-identical to the old call (``chunk_lens=None``: every row a decode
    row, a dead one walking its whole table) on the same requests."""
    from ipex_llm_tpu.ops import dispatch
    from ipex_llm_tpu.ops.pallas import ragged_paged_attention
    from ipex_llm_tpu.serving import engine as engine_mod

    # a configuration of its own: its tick programs are traced here, under
    # the spy, and never found in another test's jit cache
    cfg = tiny_cfg(vocab_size=113, hidden_size=48, intermediate_size=96,
                   num_heads=4, num_kv_heads=2, head_dim=12,
                   max_position_embeddings=512)
    params = rand_params(cfg, qtype="bf16")
    rng = np.random.default_rng(5)
    specs = [dict(prompt_ids=list(rng.integers(0, 113, n)), max_new_tokens=m,
                  **kw)
             for n, m, kw in ((20, 3, {}), (33, 9, {}),
                              (12, 6, dict(temperature=0.8, top_p=0.9,
                                           top_k=40, seed=11)))]
    seen = []          # (chunk_len, kv_len) of every decode-stage call
    real_sdpa = ragged_paged_attention.ragged_paged_sdpa

    def spy(q, k, v, tables, kv_len, chunk_len=None, **kw):
        if q.shape[1] == 1:
            cl = (jnp.ones_like(kv_len) if chunk_len is None else chunk_len)
            jax.debug.callback(
                lambda c, n: seen.append((np.asarray(c), np.asarray(n))),
                cl, kv_len)
        return real_sdpa(q, k, v, tables, kv_len, chunk_len, **kw)

    def serve():
        engine_mod._ragged_tick.clear_cache()
        reqs = [Request(**s) for s in specs]
        eng = ServingEngine(cfg, params, EngineConfig(**EC))
        streams = _drive(eng, {0: reqs})
        jax.effects_barrier()
        return streams, [list(r.logprobs) for r in reqs]

    monkeypatch.setattr(ragged_paged_attention, "ragged_paged_sdpa", spy)
    monkeypatch.setenv("IPEX_LLM_TPU_FORCE_PALLAS", "1")
    dispatch.clear_cache()
    try:
        new = serve()
        calls_new, seen[:] = list(seen), []
        # the old call: the decode step's chunk_lens dropped again
        real_forward = engine_mod.decoder_forward

        def old_forward(cfg_, params_, tokens, *a, **kw):
            if tokens.shape[1] == 1 and kw.get("last_token_only"):
                kw["chunk_lens"] = None
            return real_forward(cfg_, params_, tokens, *a, **kw)

        monkeypatch.setattr(engine_mod, "decoder_forward", old_forward)
        # (the same in a fused mixed tick, whose rows are the block of
        # width 1 of ``forward_blocks``)
        real_blocks = engine_mod.forward_blocks

        def old_blocks(cfg_, params_, cache, blocks):
            return real_blocks(cfg_, params_, cache, tuple(
                b._replace(chunk_lens=jnp.ones_like(b.chunk_lens))
                if b.tokens.shape[1] == 1 else b for b in blocks))

        monkeypatch.setattr(engine_mod, "forward_blocks", old_blocks)
        old = serve()
        calls_old = list(seen)
    finally:
        monkeypatch.delenv("IPEX_LLM_TPU_FORCE_PALLAS", raising=False)
        dispatch.clear_cache()
        engine_mod._ragged_tick.clear_cache()
    assert new == old
    assert [len(t) for t in new[0]] == [3, 9, 6]
    table_slots = EC["max_seq_len"]
    dead_new = [c == 0 for c, _ in calls_new]
    assert any(d.any() for d in dead_new), "no tick held a dead row"
    for (c, n), dead in zip(calls_new, dead_new):
        assert set(c.tolist()) <= {0, 1}
        # a dead row sits AT the scratch sentinel and asks for no page; a
        # live one inside its table
        assert (n[dead] == table_slots).all() and (n[~dead] < table_slots
                                                   ).all()
    # what the old call handed the kernel: every row a decode row, the dead
    # ones one past the sentinel (every page of the table live to them)
    assert all((c == 1).all() for c, _ in calls_old)
    assert any((n == table_slots + 1).any() for _, n in calls_old)


def _attn_pages_of(prompt: int, new_tokens: int, width: int, ps: int,
                   rows: int, maxp: int) -> tuple[int, int]:
    """(live, table) page counts of ONE request served alone: its prompt in
    chunks of ``width`` (each chunk's rows attend up to its end; the
    completion tick's decode stage holds no live row: a dense model's row
    takes its first decode step in the NEXT tick), then one steady tick a
    token, the row ``prompt + emitted`` slots long."""
    ends = list(range(width, prompt, width)) + [prompt]
    live = sum(-(-e // ps) for e in ends)
    table = maxp * len(ends) + rows * maxp       # + the completion's decode
    for emitted in range(1, new_tokens):
        live += -(-(prompt + emitted) // ps)
        table += rows * maxp
    return live, table


def test_attn_page_counters_follow_the_lengths_and_roll_back(cfg_params):
    """``attn_pages_live_total`` is the sum, over the rows that attend, of
    the pages their lengths hold; ``attn_pages_table_total`` the table
    width handed over.  A tick rolled back after its dispatch leaves both
    as the retried tick alone counts them."""
    from ipex_llm_tpu.serving.faults import FaultInjector, TransientFault

    cfg, params = cfg_params
    prompt = list(RNG.integers(0, cfg.vocab_size, 70))
    want = _attn_pages_of(70, 7, EC["prefill_bucket"], EC["page_size"],
                          EC["max_rows"],
                          EC["max_seq_len"] // EC["page_size"])

    def counters(injector=None):
        eng = ServingEngine(cfg, params, EngineConfig(**EC),
                            fault_injector=injector)
        req = eng.submit(Request(prompt_ids=prompt, max_new_tokens=7))
        for _ in range(200):       # the transaction: checkpoint, retry
            eng._tick()
            if req.finish_reason is not None:
                break
        assert len(list(stream_tokens(req, timeout=10))) == 7
        return (eng.metrics["attn_pages_live_total"],
                eng.metrics["attn_pages_table_total"]), eng

    got, _ = counters()
    assert got == want
    # the "sample" site fires after the completion tick's dispatch was
    # counted: the rollback takes the counts back, the retry adds them once
    inj = FaultInjector().inject("sample", TransientFault, nth=1)
    again, eng = counters(inj)
    assert inj.fired == 1 and eng.metrics["retries"] == 1
    assert again == want
