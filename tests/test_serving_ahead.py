"""One program in flight behind the running one (``engine._queue_next``).

In a run of steady ticks the engine dispatches the NEXT tick's program
before it reads this one's.  The contract under test:

- **the same bits**: every stream (tokens, logprob bits, finish reasons)
  is what the serial order gives, over a staggered wave that crosses page
  edges and ends rows by EOS and by budget inside a run;
- **the host retires a row while a program is queued** (abort, deadline,
  disconnect): no token after the finish, no row reused early, pages
  returned once;
- **the transaction**: a fault with a program queued throws that program
  away unread and the streams equal the fault-free run's;
- **what waits**: a recurrent state, speculation, a fused horizon never
  queue ahead;
- **the counts**: programs dispatched == ticks that read one + programs
  discarded (+ the one still queued), never two queued.

The serial order is the same engine with the predicate patched
(``_ahead_blocked``): there is no option to turn.  Engines are driven
synchronously through ``_tick`` (never started), so what is queued when
is deterministic tick for tick.
"""

import time

import numpy as np
import pytest

from ipex_llm_tpu.serving.engine import (EngineConfig, Request, ServingEngine,
                                         stream_tokens)
from ipex_llm_tpu.serving.faults import FaultInjector, TransientFault
from tests.test_decoder import rand_params, tiny_cfg

EC = dict(max_rows=4, max_seq_len=256, page_size=16, prefill_bucket=32)
BLOCKED = ("queue", "pages", "dirty", "state", "plan")


def _cfg():
    return tiny_cfg(vocab_size=131, hidden_size=48, intermediate_size=96,
                    num_heads=4, num_kv_heads=2, head_dim=12,
                    max_position_embeddings=512)


@pytest.fixture(scope="module")
def cfg_params():
    cfg = _cfg()
    return cfg, rand_params(cfg, qtype="bf16")


def _serial(eng):
    """The same engine in the serial order."""
    eng._ahead_blocked = lambda: "dirty"
    return eng


def _wave(cfg, eos=()):
    """Six requests over four rows, joining at ticks 0 and 5: greedy and
    seeded sampled rows, 16-token pages crossed by every row, budgets that
    end inside a run of steady ticks, and stop ids (``eos``: token ids the
    greedy rows are known to emit mid-stream)."""
    rng = np.random.default_rng(48)
    spec = ((20, 40, 0.0, None), (45, 26, 0.8, 5), (70, 19, 0.0, None),
            (10, 50, 0.9, 7), (33, 30, 0.0, None), (12, 44, 0.7, 9))
    kws = [dict(prompt_ids=[int(t) for t in rng.integers(0, cfg.vocab_size, n)],
                max_new_tokens=m, temperature=t, seed=s, top_p=0.9 if t else 1.0,
                eos_token_id=tuple(eos))
           for n, m, t, s in spec]
    return {0: kws[:3], 5: kws[3:]}


def _drive(eng, schedule, max_ticks=2000):
    reqs_at = {t: [Request(**kw) for kw in kws]
               for t, kws in schedule.items()}
    reqs = [r for _, rs in sorted(reqs_at.items()) for r in rs]
    for t in range(max_ticks):
        for r in reqs_at.get(t, ()):
            eng.submit(r)
        eng._tick()
        if all(r.finish_reason is not None for r in reqs):
            break
    assert all(r.finish_reason is not None for r in reqs)
    return reqs


def _seen(reqs):
    """What each client saw: tokens, logprob BITS, finish reason."""
    return [(list(stream_tokens(r, timeout=10)),
             np.asarray(r.logprobs, np.float32).tobytes(), r.finish_reason)
            for r in reqs]


def _greedy_stop_ids(cfg, params, ec):
    """Two token ids the wave's greedy rows emit a while into their
    streams, so that a stop falls inside a run of steady ticks."""
    reqs = _drive(_serial(ServingEngine(cfg, params, ec)), _wave(cfg))
    return (reqs[0].output_ids[17], reqs[4].output_ids[11])


# -- (a) run-ahead == serial, bit for bit -------------------------------------

VARIANTS = {
    "bf16": dict(),
    "fp8-pool": dict(kv_storage="fp8"),
    "int4-weights": dict(qtype="sym_int4"),
    "kernel-forced": dict(force_pallas=True),
    # a latent pool, held experts, four residual streams: the engine
    # carries the expert layer's device counters as a state of a few
    # numbers (``_stateful`` without ``_recurrent``) and runs ahead
    "held-experts": dict(model="xing4"),
}


def _xing4():
    """tests/test_xing4.py's tiny model: (ModelConfig, params)."""
    from ipex_llm_tpu.models.families import FAMILIES
    from tests import test_xing4 as t

    gen = t.weights.generator(t.FAMILY)
    hf = gen.tiny(t.published())
    cfg = FAMILIES[t.FAMILY].to_config(hf)
    return cfg, gen.param_tree(cfg, hf, "sym_int4", 11)


@pytest.mark.parametrize("variant", list(VARIANTS))
def test_streams_equal_the_serial_order(variant, monkeypatch):
    """The wave served with a program in flight and in the serial order:
    the same tokens, logprob bits and finish reasons, request by request;
    the run crosses page edges WITHOUT falling back, ends rows by EOS and
    by budget, and most of its ticks read a program that was queued
    ahead."""
    from ipex_llm_tpu.ops import dispatch

    opts = dict(VARIANTS[variant])
    cfg = _cfg()
    if opts.pop("force_pallas", False):
        cfg = tiny_cfg(vocab_size=131, hidden_size=128,
                       intermediate_size=256, num_heads=4, num_kv_heads=2,
                       head_dim=128, max_position_embeddings=512)
        monkeypatch.setenv("IPEX_LLM_TPU_FORCE_PALLAS", "1")
        dispatch.clear_cache()
    try:
        if opts.pop("model", None):
            cfg, params = _xing4()
        else:
            params = rand_params(cfg, qtype=opts.pop("qtype", "bf16"))
        ec = EngineConfig(**EC, **opts)
        schedule = _wave(cfg, eos=_greedy_stop_ids(cfg, params, ec))
        serial = _serial(ServingEngine(cfg, params, ec))
        want = _seen(_drive(serial, schedule))
        eng = ServingEngine(cfg, params, ec)
        got = _seen(_drive(eng, schedule))
    finally:
        if variant == "kernel-forced":
            monkeypatch.delenv("IPEX_LLM_TPU_FORCE_PALLAS", raising=False)
            dispatch.clear_cache()
    assert got == want
    assert {g[2] for g in got} == {"stop", "length"}
    m, ms = eng.metrics, serial.metrics
    assert ms["ahead_ticks_total"] == 0
    assert m["ahead_ticks_total"] > 0.7 * (m["host_syncs"]
                                           - m["mixed_steps"])
    # the 16-token page edges of six rows lie inside runs: none of them
    # sent the engine back to the serial order, and the table crossed about
    # as often as in it (a page backed for a row that then stopped, an edge
    # that the serial order folds into a finish's epoch)
    assert m["ahead_blocked_pages_total"] == 0
    assert m["table_row_syncs"] > 8
    assert abs(m["table_row_syncs"] - ms["table_row_syncs"]) <= 2
    assert m["tokens"] == ms["tokens"]
    assert m["ahead_discarded_total"] == m["ahead_stray_steps_total"] == 0
    assert eng._ahead is None or not any(eng.rows)
    if eng._stateful:
        # the device counters that ride the state came home once a tick
        # (the routed pairs add up token by token; the experts HIT are a
        # union over a program's tokens and follow the admission's timing)
        assert not eng._recurrent and len(eng._state_held) <= 1
        for name in ("moe_pairs_routed_total", "moe_pairs_local_total"):
            assert m[name] == ms[name] > 0, name


def test_a_dry_pool_falls_back_and_clamps_as_in_the_serial_order(cfg_params):
    """Two rows reach a page's edge together and the pool is dry: nothing
    is queued ahead (``ahead_blocked_pages_total``), the tick that needs
    the page clamps and counts once, and the streams are the serial
    order's."""
    cfg, params = cfg_params
    rng = np.random.default_rng(5)
    kws = [dict(prompt_ids=[int(t) for t in rng.integers(0, 131, 40)],
                max_new_tokens=50) for _ in range(2)]
    ec = EngineConfig(pool_pages=7, max_rows=4, max_seq_len=256,
                      page_size=16, prefill_bucket=32)
    serial = _serial(ServingEngine(cfg, params, ec))
    want = _seen(_drive(serial, {0: kws}))
    eng = ServingEngine(cfg, params, ec)
    got = _seen(_drive(eng, {0: kws}))
    assert got == want
    assert eng.metrics["ahead_blocked_pages_total"] >= 1
    assert (eng.metrics["alloc_fail_clamps"]
            == serial.metrics["alloc_fail_clamps"] >= 1)


# -- (b) the host retires a row while a program is queued ---------------------

def _run_until_queued(eng, reqs, min_tokens=6):
    for r in reqs:
        eng.submit(r)
    for _ in range(400):
        eng._tick()
        if (eng._ahead is not None
                and all(len(r.output_ids) >= min_tokens for r in reqs)):
            return
    raise AssertionError("no program was ever queued ahead")


def _retire(how, eng, req):
    if how == "abort":
        eng.abort(req)
    elif how == "deadline":
        req.deadline_s = 1e-6
    else:                       # a client's disconnect: the handler's path
        req.finish_reason = "stop_string"
        req.cancelled = True


@pytest.mark.parametrize("how", ["abort", "deadline", "disconnect"])
def test_a_row_the_host_retires_while_a_program_is_queued(cfg_params, how):
    """The retired row's stream ends where the host ended it (no token
    after the finish), the queued program's step on it is dropped and
    counted, its pages come back once, the row is handed to no request
    before the queued program has been read, and the neighbour's stream is
    the one it has when served beside the row's full stream."""
    cfg, params = cfg_params
    rng = np.random.default_rng(11)
    prompts = [[int(t) for t in rng.integers(0, 131, n)] for n in (20, 30, 25)]
    ec = EngineConfig(**EC)
    # the neighbour's stream does not depend on the batch: serve it alone
    alone = Request(prompt_ids=prompts[1], max_new_tokens=40)
    _drive_reqs(_serial(ServingEngine(cfg, params, ec)), [alone], [alone])

    eng = ServingEngine(cfg, params, EngineConfig(**EC))
    victim = Request(prompt_ids=prompts[0], max_new_tokens=60)
    other = Request(prompt_ids=prompts[1], max_new_tokens=40)
    _run_until_queued(eng, [victim, other])
    row = eng.rows.index(victim)
    pids = eng.tables[row][eng.tables[row] >= 0].copy()
    ref0 = eng.alloc.ref[pids].copy()
    n_seen = len(victim.output_ids)
    _retire(how, eng, victim)
    late = Request(prompt_ids=prompts[2], max_new_tokens=5)
    eng.submit(late)            # wants the row the victim leaves
    queued = eng._ahead
    eng._tick()
    # the tick that notices reads the program that was queued; nothing was
    # queued behind it, and the row has not been handed on
    assert victim.finish_reason in ("abort", "timeout", "stop_string")
    assert len(victim.output_ids) <= n_seen + 1
    n_final = len(victim.output_ids)
    assert eng._ahead is None and eng._ahead is not queued
    assert eng.rows[row] is None and late not in eng.rows
    assert (eng.alloc.ref[pids] == ref0 - 1).all()  # returned, once
    assert len(set(eng.alloc.free)) == len(eng.alloc.free)
    assert eng.metrics["ahead_stray_steps_total"] >= 1
    _drive_reqs(eng, [other, late])
    assert late.finish_reason == "length" and len(late.output_ids) == 5
    assert len(victim.output_ids) == n_final        # no token after it
    toks = list(stream_tokens(victim, timeout=10))
    assert toks == victim.output_ids
    assert other.output_ids == alone.output_ids
    assert other.logprobs == alone.logprobs
    # every page is back: what is still held, the prefix cache holds
    assert (eng.alloc.ref >= 0).all()
    assert int(eng.alloc.ref.sum()) == len(eng.alloc.prefix)
    assert len(set(eng.alloc.free)) == len(eng.alloc.free)


def _drive_reqs(eng, reqs, submit=(), max_ticks=2000):
    for r in submit:
        eng.submit(r)
    for _ in range(max_ticks):
        if all(r.finish_reason is not None for r in reqs):
            return
        eng._tick()
    raise AssertionError([r.finish_reason for r in reqs])


# -- (c) a fault with a program queued -----------------------------------------

@pytest.mark.parametrize("site", ["decode-dispatch", "drain",
                                  "decode-dispatch/held-experts"])
def test_a_fault_with_a_program_queued_discards_it(cfg_params, site):
    """A transient fault before the dispatch of the program to be queued,
    and one while the awaited program is drained with its successor in
    flight: the tick rolls back, what was queued is thrown away unread
    (``ahead_discarded_total``), the retry runs in the serial order and
    the streams equal the fault-free run's."""
    cfg, params = cfg_params
    site, _, model = site.partition("/")
    if model:
        cfg, params = _xing4()
    schedule = _wave(cfg)
    clean = ServingEngine(cfg, params, EngineConfig(**EC))
    want = _seen(_drive(clean, schedule))
    inj = FaultInjector()
    eng = ServingEngine(cfg, params,
                        EngineConfig(retry_backoff_s=0.0, **EC),
                        fault_injector=inj)
    fired = {"n": 0}
    if site == "decode-dispatch":
        # hits 1.. are the mixed ticks' and the first steady tick's own;
        # by the 12th a run is under way and the site guards an AHEAD
        # dispatch with a program queued
        inj.inject("decode-dispatch", TransientFault, nth=12)
    else:
        real = eng._drain_block

        def faulty(*a, **kw):
            if eng._ahead is not None and eng.metrics["ahead_ticks_total"] >= 6 \
                    and not fired["n"]:
                fired["n"] = 1
                raise TransientFault("injected drain fault")
            return real(*a, **kw)

        eng._drain_block = faulty
    got = _seen(_drive(eng, schedule))
    assert (inj.fired or fired["n"]) == 1
    assert got == want
    m = eng.metrics
    assert m["retries"] == 1
    assert m["ahead_discarded_total"] == 1
    assert m["ahead_ticks_total"] > 0
    if eng._stateful:
        # the device counters of the discarded programs went with them
        for name in ("moe_pairs_routed_total", "moe_pairs_local_total"):
            assert m[name] == clean.metrics[name] > 0, name


def test_stop_discards_a_queued_program_and_streams_stay_whole(cfg_params):
    """``stop()`` with a program queued: it is thrown away unread, every
    token a stream got it got once and in order, and a host operation
    (``run_on_engine``) runs over committed state between two ticks."""
    cfg, params = cfg_params
    rng = np.random.default_rng(3)
    eng = ServingEngine(cfg, params, EngineConfig(**EC)).start()
    try:
        reqs = [eng.submit(Request(
            prompt_ids=[int(t) for t in rng.integers(0, 131, 20)],
            max_new_tokens=120)) for _ in range(2)]
        deadline = time.monotonic() + 120
        while (eng.metrics["ahead_ticks_total"] < 10
               and time.monotonic() < deadline):
            time.sleep(0.005)
        assert eng.metrics["ahead_ticks_total"] >= 10
        # a host operation waits for the queued program to be read
        seen = eng.run_on_engine(
            lambda: (eng._ahead, [int(n) for n in eng.row_lens[:2]],
                     [len(r.prompt_ids) + len(r.output_ids) - 1
                      for r in reqs]))
        assert seen[0] is None and seen[1] == seen[2]
    finally:
        eng.stop()
    assert eng._ahead is None
    for r in reqs:
        got = []
        while not r.stream_queue.empty():
            tok = r.stream_queue.get_nowait()
            if tok is not None:
                got.append(tok)
        assert got == r.output_ids[:len(got)]
        assert len(got) == len(r.output_ids)


# -- (d) what never queues ahead -------------------------------------------------

@pytest.mark.parametrize("kind", ["recurrent-state", "spec_k", "horizon"])
def test_these_engines_never_queue_ahead(cfg_params, kind):
    """A recurrent state (the rollback's source would be overwritten),
    speculation and a fused horizon keep the serial order: no tick reads a
    program queued ahead, and every steady tick says why."""
    cfg, params = cfg_params
    extra = {}
    if kind == "recurrent-state":
        cfg, params = _hybrid()
        why = "state"
    elif kind == "spec_k":
        extra, why = dict(spec_k=2), "plan"
    else:
        extra, why = dict(decode_horizon=4), "plan"
    eng = ServingEngine(cfg, params, EngineConfig(**EC, **extra))
    steady = {"n": 0}
    launch = eng._launch

    def counted(*a, **kw):
        steady["n"] += 1
        return launch(*a, **kw)

    eng._launch = counted
    rng = np.random.default_rng(2)
    reqs = [Request(prompt_ids=[int(t) for t in
                                rng.integers(0, cfg.vocab_size, n)],
                    max_new_tokens=24) for n in (20, 35)]
    _drive_reqs(eng, reqs, reqs)
    m = eng.metrics
    assert all(len(r.output_ids) == 24 for r in reqs)
    assert m["ahead_ticks_total"] == 0 and eng._ahead is None
    assert m[f"ahead_blocked_{why}_total"] == steady["n"] > 0
    assert sum(m[f"ahead_blocked_{w}_total"] for w in BLOCKED) == steady["n"]


def _hybrid():
    """tests/test_nemotron_h.py's tiny hybrid model (Mamba-2 blocks beside
    attention): an engine with a recurrent state."""
    from ipex_llm_tpu.models.families import FAMILIES
    from tests import test_nemotron_h as t

    hf = t.tiny_hf()
    cfg = FAMILIES["nemotron_h"].to_config(hf)
    return cfg, t.gen.param_tree(cfg, hf, "sym_int4", 7)


# -- (e) the counts: see tests/test_serving_perfwatch.py ----------------------
