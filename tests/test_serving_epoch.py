"""The packed epoch (serving/epoch.py): what the host publishes for an
epoch crosses to the device as ONE buffer in ONE transfer, and the tick
program takes it apart.  Tier-1 guards: no one-op program is left on the
path between two ticks, ``h2d_total`` counts exactly the transfers, the
buffer round-trips every field bit for bit, and the lowered mixed tick
gathers the prefill rows' tables itself, under scope ``tick/epoch``.

And the way home, its mirror (``ResultLayout``): what a tick sends back
is ONE buffer the program packs under scope ``tick/home``, whose slots
follow from shapes alone and whose fields come apart on the host bit for
bit as the program computed them."""

import logging
import re

import jax
import numpy as np
import pytest

from ipex_llm_tpu.serving.engine import EngineConfig, Request, ServingEngine
from ipex_llm_tpu.serving.epoch import (EpochLayout, ResultLayout,
                                         unpack_rows)
from ipex_llm_tpu.serving.faults import FaultInjector, TransientFault
from tests.test_decoder import rand_params, tiny_cfg

pytestmark = pytest.mark.fast

RNG = np.random.default_rng(38)
EC = dict(max_rows=4, max_seq_len=256, page_size=32, prefill_bucket=32)


@pytest.fixture(scope="module")
def cfg_params():
    cfg = tiny_cfg(vocab_size=131, hidden_size=48, intermediate_size=96,
                   num_heads=4, num_kv_heads=2, head_dim=12,
                   max_position_embeddings=512)
    return cfg, rand_params(cfg, qtype="bf16")


def _prompt(cfg, n):
    return [int(t) for t in RNG.integers(0, cfg.vocab_size, n)]


def _finish_all(eng, reqs, max_ticks=400):
    for _ in range(max_ticks):
        if all(r.finish_reason is not None for r in reqs):
            return
        eng._tick()
    raise AssertionError([r.finish_reason for r in reqs])


# -- (a) nothing eager between two ticks ---------------------------------------

class _Compiles(logging.Handler):
    def __init__(self):
        super().__init__(logging.DEBUG)
        self.names = []

    def emit(self, record):
        m = re.match(r"Compiling (?:jit\()?([\w.<>]+)", record.getMessage())
        if m:
            self.names.append(m.group(1))


def test_only_the_tick_and_the_unpack_compile_after_construction(cfg_params):
    """An admission wave, completions, finishes and a page boundary on a
    fresh engine: every program compiled after construction is
    ``_ragged_tick`` or the one unpack.  An eager ``convert_element_type``
    (``jnp.array``), gather, scatter, select or slice between two ticks
    would compile a one-op program of that name."""
    cfg, params = cfg_params
    eng = ServingEngine(cfg, params, EngineConfig(**EC))
    seen = _Compiles()
    log = logging.getLogger("jax._src.interpreters.pxla")
    log.addHandler(seen)
    try:
        with jax.log_compiles():
            # 3 join at once (p_b 4), a 4th later beside decoding rows; 20
            # + 40 tokens cross the 32-token page inside the decode
            reqs = [Request(prompt_ids=_prompt(cfg, n), max_new_tokens=m,
                            temperature=t, seed=5, eos_token_id=e)
                    for n, m, t, e in ((20, 40, 0.0, ()), (45, 6, 0.8, (3,)),
                                       (70, 9, 0.0, (3, 7, 9)))]
            for r in reqs:
                eng.submit(r)
            for _ in range(4):
                eng._tick()
            late = Request(prompt_ids=_prompt(cfg, 33), max_new_tokens=5)
            eng.submit(late)
            _finish_all(eng, reqs + [late])
    finally:
        log.removeHandler(seen)
    m = eng.metrics
    assert m["mixed_steps"] >= 3 and m["table_row_syncs"] >= 3, m
    assert seen.names, "the compile log was not captured"
    assert set(seen.names) <= {"_ragged_tick", "unpack_rows"}, seen.names
    assert "_ragged_tick" in seen.names


# -- (b) the counter ------------------------------------------------------------

def test_h2d_total_counts_one_transfer_an_epoch_and_none_without_news(
        cfg_params):
    cfg, params = cfg_params
    eng = ServingEngine(cfg, params, EngineConfig(**EC))
    m = eng.metrics
    req = Request(prompt_ids=_prompt(cfg, 40), max_new_tokens=30)
    eng.submit(req)
    per_tick = []                      # (mixed?, leaves news?, transfers)
    while req.finish_reason is None:
        before = (m["h2d_total"], m["mixed_steps"])
        # a tick publishes what the NEXT steady tick needs before it
        # commits: here the tick that leaves the row at 64 slots, the edge
        # of the pages it holds (and the mixed tick that completes the
        # prompt, below)
        edge = int(eng.row_lens[0]) == 63 and eng.tables[0, 2] < 0
        eng._tick()
        assert req.finish_reason or not (eng._dirty or eng._tables_dirty)
        per_tick.append((m["mixed_steps"] - before[1] == 1, bool(edge),
                         m["h2d_total"] - before[0]))
    mixed = [t for t in per_tick if t[0]]
    edge_steady = [t for t in per_tick if not t[0] and t[1]]
    quiet_steady = [t for t in per_tick if not t[0] and not t[1]]
    # 40 tokens in 32-token chunks: two mixed ticks, an epoch each, and the
    # second, which completes the prompt, publishes the decoding row's
    # state for the steady tick after it; one steady tick ends on the page
    # boundary at 64 slots; the rest are quiet, the tick after the
    # completion and the tick that writes slot 64 among them
    assert [n for _, _, n in mixed] == [1, 2], per_tick
    assert [n for _, _, n in edge_steady] == [1], per_tick
    assert len(quiet_steady) >= 20, per_tick
    assert all(n == 0 for _, _, n in quiet_steady), per_tick
    assert m["h2d_total"] == m["epoch_syncs"] == 4
    # admission backs the whole prompt, so the table has news twice: at
    # the first chunk and at the boundary
    assert m["table_row_syncs"] == 2


def test_a_dry_pool_is_left_to_the_tick_that_needs_the_page(cfg_params):
    """Publishing a page boundary ahead is a saving, not a second
    allocator: where the pool cannot give the pages, the tick that ends on
    the boundary leaves the table alone and counts nothing; the tick that
    needs them clamps, once, as it always did."""
    cfg, params = cfg_params
    # page 0 is the scratch page: 4 usable pages, two prompts take them all
    eng = ServingEngine(cfg, params, EngineConfig(pool_pages=5, **EC))
    reqs = [Request(prompt_ids=_prompt(cfg, 40), max_new_tokens=50)
            for _ in range(2)]
    for r in reqs:
        eng.submit(r)
    for _ in range(60):
        if int(eng.row_lens[:2].max()) == 64:
            break
        eng._tick()
    assert [int(n) for n in eng.row_lens[:2]] == [64, 64]
    assert (eng.tables[:2, 2] < 0).all()
    assert eng.metrics.get("alloc_fail_clamps", 0) == 0
    _finish_all(eng, reqs)
    # the first row finds the pool dry and ends; its pages back the second
    assert eng.metrics["alloc_fail_clamps"] == 1
    assert [len(r.output_ids) for r in reqs] == [64 - 40 + 1, 50]


def test_a_rolled_back_tick_leaves_h2d_total_where_it_was(cfg_params):
    """``sample`` fires after the upload and the dispatch of the tick that
    completes a prompt: the tick rolls back, and the counter with it; the
    retry then counts its own epoch and the decoding row's state, which it
    publishes for the steady tick after it."""
    cfg, params = cfg_params
    inj = FaultInjector().inject("sample", TransientFault, nth=1)
    eng = ServingEngine(cfg, params, EngineConfig(
        retry_backoff_s=0.0, **EC), fault_injector=inj)
    req = Request(prompt_ids=_prompt(cfg, 12), max_new_tokens=3)
    eng.submit(req)
    assert eng._tick() is False and inj.fired == 1
    assert eng.metrics["h2d_total"] == 0 and eng.metrics["retries"] == 1
    assert eng._tick() is True
    assert eng.metrics["h2d_total"] == 2
    _finish_all(eng, [req])
    assert len(req.output_ids) == 3


# -- (c) the buffer round-trips bit for bit -------------------------------------

def _fields(lay: EpochLayout, seed: int) -> dict:
    rng = np.random.default_rng(seed)
    r, p = lay.rows, lay.p_b
    f32 = np.array([0.0, -0.0, 1e-45, 1.1754942e-38, 0.7, 1.0, np.inf,
                    3.4028235e38], np.float32)        # incl. denormals
    eos = rng.integers(-1, 2 ** 31 - 1, (r, lay.e_w)).astype(np.int32)
    eos[:, lay.e_w // 2:] = -1                        # the pad
    fields = dict(
        toks=rng.integers(0, 2 ** 31 - 1, r), row_lens=rng.integers(0, 9, r),
        active=rng.integers(0, 2, r).astype(bool), temps=rng.choice(f32, r),
        top_ps=rng.choice(f32[::-1], r),
        seeds=rng.integers(-2 ** 31, 2 ** 31 - 1, r),       # negative seeds
        top_ks=rng.integers(0, 99, r), steps=rng.integers(0, 99, r),
        remain=rng.integers(0, 99, r), eos=eos,
        tables=rng.integers(-1, 4096, (r, lay.max_pages)))
    if p:
        rowmap = rng.integers(0, r, p)
        rowmap[-1] = r                                # a pad slot
        base = rng.integers(0, 64, p)
        base[-1] = lay.max_pages * 32                 # past the table
        fields.update(
            p_toks=rng.integers(0, 131, (p, lay.width)), p_base=base,
            p_nvalid=rng.integers(0, lay.width + 1, p),
            p_emit=rng.integers(0, 2, p).astype(bool),
            p_canjoin=rng.integers(0, 2, p).astype(bool), p_rowmap=rowmap)
    dtypes = {name: dt for name, (_, _, _, dt) in lay.slots().items()}
    return {k: np.asarray(v).astype(dtypes[k]) for k, v in fields.items()}


@pytest.mark.parametrize("lay", [
    EpochLayout(rows=4, max_pages=8, e_w=1, p_b=2, width=8, p_maxp=8),
    EpochLayout(rows=16, max_pages=32, e_w=4, p_b=8, width=64, p_maxp=4),
    EpochLayout(rows=3, max_pages=5, e_w=2),          # a steady tick's epoch
], ids=lambda lay: f"r{lay.rows}-p{lay.p_b}x{lay.width}-e{lay.e_w}")
def test_the_packed_buffer_round_trips_every_field_bit_for_bit(lay):
    fields = _fields(lay, seed=lay.rows)
    buf = lay.pack(**fields)
    assert buf.dtype == np.int32 and buf.shape == (lay.size,)
    got = jax.jit(lay.unpack)(buf)
    assert set(got) == set(fields)
    for name, want in fields.items():
        have = np.asarray(got[name])
        assert have.dtype == want.dtype and have.shape == want.shape, name
        assert have.tobytes() == want.tobytes(), name
    if not lay.p_b:                    # the program the steady epoch runs
        again = unpack_rows(buf, lay)
        assert all(np.asarray(again[k]).tobytes() == fields[k].tobytes()
                   for k in fields)


def test_an_upload_is_a_private_copy():
    """``hostutil.upload`` has ``h2d``'s copy semantics: the host may
    refill its buffer while the upload is still in flight."""
    from ipex_llm_tpu.hostutil import upload

    buf = np.arange(4096, dtype=np.int32)
    dev = upload(buf)
    buf[:] = -1
    np.testing.assert_array_equal(np.asarray(dev),
                                  np.arange(4096, dtype=np.int32))


# -- (d) the gather is the program's ---------------------------------------------

def test_the_lowered_mixed_tick_gathers_its_tables_under_tick_epoch():
    """The guard style of tests/test_serving_lowbit.py: in the mixed
    tick's jaxpr the prefill rows' table view comes from a gather of the
    unpacked table inside scope ``tick/epoch`` (with the two float fields'
    bitcasts beside it), and the only arguments that reach the program
    for the epoch are the packed buffer and the key."""
    from ipex_llm_tpu.analysis.trace.registry import _build_ragged_tick
    from ipex_llm_tpu.serving.engine import _ragged_tick

    args, kw = _build_ragged_tick({"rows": 4, "width": 8, "horizon": 1,
                                   "kv": "bf16"})
    lay = kw["epoch"]
    jaxpr = _ragged_tick.trace(*args, **kw).jaxpr.jaxpr

    def scoped(eqn):
        return "tick/epoch" in str(eqn.source_info.name_stack)

    gathers = [e for e in jaxpr.eqns if e.primitive.name == "gather"
               and scoped(e)]
    assert [tuple(e.outvars[0].aval.shape) for e in gathers] == [
        (lay.p_b, lay.max_pages)], gathers
    assert tuple(gathers[0].invars[0].aval.shape) == (lay.rows,
                                                      lay.max_pages)
    bitcasts = [e for e in jaxpr.eqns
                if e.primitive.name == "bitcast_convert_type" and scoped(e)]
    assert len(bitcasts) == 2          # temps, top_ps
    text = _ragged_tick.lower(*args, **kw).as_text(debug_info=True)
    assert "tick/epoch" in text


# -- (e) the way home: one packed buffer a tick --------------------------------

RESULT_LAYOUTS = {
    "steady-h1": (ResultLayout(rows=16, horizon=1), 1 + 16 + 16),
    "steady-h8": (ResultLayout(rows=16, horizon=8), 1 + 2 * 16 * 8),
    "mixed-completes-a-prompt": (ResultLayout(rows=4, horizon=1, p_b=2),
                                 1 + 2 * 4 + 2 * 2),
    "speculating": (ResultLayout(rows=4, horizon=4, k1=3),
                    1 + 2 * 4 * 4 * 3 + 4 * 4 + 2),
    "speculating-mixed": (ResultLayout(rows=4, horizon=1, k1=3, p_b=8),
                          1 + 2 * 4 * 3 + 2 * 8 + 4 + 2),
    "hybrid-with-stats": (ResultLayout(rows=8, horizon=1, p_b=4, n_stats=6),
                          7 + 2 * 8 + 2 * 4),
}
# bit patterns a value-wise copy could lose: -0.0, a NaN with a payload,
# infinities, a denormal
ODD_FLOATS = np.asarray([0x80000000, 0x7FC00123, 0xFF800000, 0x7F800000,
                         0x00000001], np.uint32).view(np.float32)


def _result_fields(lay, seed):
    """Random values of every slot of ``lay``, in the shapes and dtypes the
    tick program computes them in (``n_exec`` a scalar without stats)."""
    rng = np.random.default_rng(seed)
    out = {}
    for name, (lo, hi, shape, dt) in lay.slots().items():
        if dt is np.float32:
            x = rng.standard_normal(hi - lo).astype(np.float32)
            x[:ODD_FLOATS.size] = ODD_FLOATS[:x.size]
        else:
            x = rng.integers(-2**31, 2**31, hi - lo).astype(np.int32)
        out[name] = x.reshape(shape)
    if not lay.n_stats:
        out["n_exec"] = out["n_exec"].reshape(())
    return out


@pytest.mark.parametrize("case", RESULT_LAYOUTS)
def test_the_packed_result_round_trips_every_field_bit_for_bit(case):
    lay, size = RESULT_LAYOUTS[case]
    assert lay.size == size                     # from the shapes alone
    fields = _result_fields(lay, seed=size)
    assert ResultLayout.of(fields) == lay
    buf = np.asarray(jax.jit(lambda f: lay.pack(**f))(fields))
    assert buf.dtype == np.int32 and buf.shape == (size,)
    got = lay.unpack(buf)
    assert list(got) == list(fields) == list(lay.slots())
    for name, want in fields.items():
        have = got[name]
        assert have.dtype == want.dtype, name
        assert have.shape == lay.slots()[name][2], name
        assert have.tobytes() == want.tobytes(), name
        assert np.shares_memory(have, buf), name        # a view, no copy
    with pytest.raises(ValueError, match="not a packed result"):
        lay.unpack(buf[:-1])
    with pytest.raises(TypeError, match="tok"):
        lay.pack(**dict(fields, tok=fields["tok"].astype(np.int16)))


@pytest.mark.parametrize("horizon,spec_k", [(1, 0), (4, 0), (2, 2)])
def test_what_comes_home_is_what_the_tick_body_computed(cfg_params, horizon,
                                                        spec_k):
    """``_ragged_tick``'s buffer, taken apart, against the program's own
    values: ``_tick_body`` jitted by itself on the same pool and row state
    (a greedy row, a seeded sampling row, two dead ones)."""
    from ipex_llm_tpu.hostutil import h2d
    from ipex_llm_tpu.serving import engine as engine_mod
    from tests.test_serving_ragged import _random_pool_state

    cfg, params = cfg_params
    kw = dict(prefill=None, horizon=horizon, with_decode=True)
    if spec_k:
        kw.update(spec_k=spec_k, spec_ngram=2)

    def call(fn):
        cache, st = _random_pool_state(cfg, "bf16", seed=9)   # donated: anew
        dev = {k: h2d(v) for k, v in st.items()}
        extra = {}
        if spec_k:
            hist = np.tile(np.arange(64, dtype=np.int32) % 5, (4, 1))
            extra = dict(hist=h2d(hist), spec_ks=h2d(np.full(4, spec_k,
                                                             np.int32)))
        return fn(cfg, params, cache, dev["toks"], dev["row_lens"],
                  dev["active"], dev["temps"], dev["top_ps"],
                  jax.random.PRNGKey(3), dev["seeds"], dev["steps"],
                  dev["top_ks"], dev["eos"], dev["remain"], **kw, **extra)

    body = call(jax.jit(
        engine_mod._tick_body,
        static_argnames=("cfg", "horizon", "with_decode", "spec_k",
                         "spec_ngram"),
        compiler_options=engine_mod._EXACT_ROUNDING))
    want = {"n_exec": body[4], "tok": body[2], "lp": body[3]}
    if spec_k:
        want.update(take=body[12], prop=body[14], acc=body[15])
    out = call(engine_mod._ragged_tick)
    assert len(out) == (9 if spec_k else 8)       # home, 7 carried[, hist]
    lay = ResultLayout(rows=4, horizon=horizon, k1=spec_k + 1 if spec_k else 0)
    got = lay.unpack(np.asarray(out[0]))
    assert list(got) == list(want)
    assert int(got["n_exec"][0]) >= 1 and (got["tok"] != 0).any()
    for name, w in want.items():
        assert got[name].tobytes() == np.asarray(w).tobytes(), name
    # what stays on the device is where it was in the body's tuple
    for a, b in zip(out[2:8], body[6:12]):
        assert np.asarray(a).tobytes() == np.asarray(b).tobytes()


@pytest.mark.parametrize("point", [
    {"rows": 4, "width": 0, "horizon": 1, "kv": "bf16"},
    {"rows": 4, "width": 0, "horizon": 8, "kv": "bf16"},
    {"rows": 4, "width": 8, "horizon": 1, "kv": "bf16"},
    {"rows": 4, "width": 8, "horizon": 1, "kv": "bf16", "wd": False},
    {"rows": 4, "width": 0, "horizon": 4, "kv": "bf16", "spec": 2},
    {"rows": 4, "width": 0, "horizon": 1, "kv": "bf16", "tp": 2},
], ids=lambda pt: "-".join(f"{k}{v}" for k, v in pt.items() if k != "kv"))
def test_the_lowered_tick_sends_one_buffer_home_under_tick_home(point):
    """Every form of the tick returns ONE host-bound array, the packed
    int32 buffer of its ``ResultLayout``, concatenated under scope
    ``tick/home`` (outside the manual region where the tick has one), and
    nothing else a host reads: the rest of the tuple is the cache, the
    carried row state, the key, the history and an epoch's held fields."""
    from ipex_llm_tpu.analysis.trace.registry import _build_ragged_tick
    from ipex_llm_tpu.serving.engine import _ragged_tick

    args, kw = _build_ragged_tick(point)
    traced = _ragged_tick.trace(*args, **kw)
    lay = ResultLayout(
        rows=point["rows"], horizon=point["horizon"],
        k1=point["spec"] + 1 if point.get("spec") else 0,
        p_b=kw["epoch"].p_b if point["width"] else 0)
    home = jax.tree_util.tree_leaves(traced.out_info)[0]
    assert (home.shape, home.dtype) == ((lay.size,), np.int32)
    jaxpr = traced.jaxpr.jaxpr
    packs = [e for e in jaxpr.eqns if e.primitive.name == "concatenate"
             and "tick/home" in str(e.source_info.name_stack)]
    assert len(packs) == 1 and packs[0].outvars[0] is jaxpr.outvars[0]
    assert len(packs[0].invars) == len(lay.slots())
    # floats leave as their bits: one bitcast a float slot, no conversion
    scoped = [e.primitive.name for e in jaxpr.eqns
              if "tick/home" in str(e.source_info.name_stack)]
    assert scoped.count("bitcast_convert_type") == sum(
        dt is np.float32 for *_, dt in lay.slots().values())
    assert "convert_element_type" not in scoped
