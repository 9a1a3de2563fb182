"""The serving sampler does a row's work only when a row asks for it
(ops/sampling.py ``sample_rows_with_logprobs``): the draw lives inside a
conditional on "a live row has temperature > 0", each mask's sort inside
one on "a live drawing row set it".  Tier-1 guards: (a) the lowered sampler
holds no sort, cumulative sum or random bits outside a conditional's
branch; (b) against a copy of the function as it was before (the plain
reference), greedy rows and rows that set a mask get the same bytes;
(c) a row's bits do not depend on its batch ACROSS the branches; (d) the
same under ``vmap`` over positions and inside a ``while_loop``; (e) the
engine's three counters over a scripted run and over a rollback; (f) the
benchmark's two metric files read the names and counters the program writes."""

import importlib
import itertools
import json
import os
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ipex_llm_tpu.ops import sampling
from ipex_llm_tpu.ops.sampling import sample_rows_with_logprobs
from ipex_llm_tpu.serving.engine import (EngineConfig, Request, ServingEngine,
                                         _sample_verify_positions)
from ipex_llm_tpu.serving.faults import FaultInjector, TransientFault
from tests.test_decoder import rand_params, tiny_cfg

pytestmark = pytest.mark.fast

V = 997


def _logits(rows: int, seed: int = 43, scale: float = 3.0) -> jnp.ndarray:
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.normal(0.0, scale, (rows, V)), jnp.bfloat16)


# -- the plain reference: the function as it stood before this file -------------

def reference_sample(logits, temps, top_ps, key, seeds=None, steps=None,
                     top_ks=None, active=None):
    """Every stage for every row, a ``where`` at the end."""
    logits = logits.astype(jnp.float32)
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    scaled = logits / jnp.maximum(temps, 1e-4)[:, None]
    if top_ks is not None:
        scaled = sampling._top_k_mask_rows(scaled, top_ks)
    scaled = sampling._top_p_mask(scaled, top_ps)
    r = logits.shape[0]
    if seeds is None:
        sampled = jax.random.categorical(key, scaled, axis=-1)
    else:
        base = jax.vmap(lambda i: jax.random.fold_in(key, i))(jnp.arange(r))
        seeded = jax.vmap(
            lambda sd, st: jax.random.fold_in(jax.random.PRNGKey(sd), st)
        )(jnp.maximum(seeds, 0), steps)
        keys = jnp.where((seeds >= 0)[:, None], seeded, base)
        sampled = jax.vmap(
            lambda k, lg: jax.random.categorical(k, lg))(keys, scaled)
    sampled = sampled.astype(jnp.int32)
    chosen = jnp.where(temps > 0, sampled, greedy)
    lp = jnp.take_along_axis(
        jax.nn.log_softmax(logits, axis=-1), chosen[:, None], axis=-1)[:, 0]
    if active is not None:
        chosen = jnp.where(active, chosen, 0)
        lp = jnp.where(active, lp, 0.0)
    return chosen, lp


def _bits(pair):
    tok, lp = pair
    return np.asarray(tok), np.asarray(lp).view(np.uint32)


def _same(a, b):
    (ta, la), (tb, lb) = _bits(a), _bits(b)
    np.testing.assert_array_equal(ta, tb)
    np.testing.assert_array_equal(la, lb)


# -- rows and batches -----------------------------------------------------------

# temperature, top_p, top_k, seed, live
GREEDY = (0.0, 1.0, 0, -1, True)
GREEDY_WITH_MASKS = (0.0, 0.5, 3, 7, True)      # masks on a greedy row: unused
NUCLEUS = (0.8, 0.9, 0, 11, True)
TOPK = (1.3, 1.0, 40, 12, True)
BOTH = (0.7, 0.85, 20, 13, True)
BOTH_UNSEEDED = (0.7, 0.85, 20, -1, True)
DEAD_MASKED = (0.9, 0.5, 5, 3, False)


def _batch(rows, with_seeds=True, with_active=True, with_topk=True):
    t, p, k, s, a = (np.array(c) for c in zip(*rows))
    n = len(rows)
    return dict(
        temps=jnp.asarray(t, jnp.float32), top_ps=jnp.asarray(p, jnp.float32),
        key=jax.random.PRNGKey(43),
        seeds=jnp.asarray(s, jnp.int32) if with_seeds else None,
        steps=jnp.arange(n, dtype=jnp.int32) * 3 if with_seeds else None,
        top_ks=jnp.asarray(k, jnp.int32) if with_topk else None,
        active=jnp.asarray(a, bool) if with_active else None)


# -- (a) where the sorts live -----------------------------------------------------

HEAVY = {"sort", "cumsum", "random_bits", "threefry2x32"}


def _subjaxprs(eqn):
    for v in eqn.params.values():
        for x in (v if isinstance(v, (list, tuple)) else (v,)):
            if hasattr(x, "jaxpr") and hasattr(x.jaxpr, "eqns"):
                yield x.jaxpr                       # ClosedJaxpr
            elif hasattr(x, "eqns"):
                yield x


def _primitives(jaxpr, into_cond: bool) -> list[str]:
    """Names of the equations reachable from ``jaxpr``; a conditional's
    branches are entered only with ``into_cond``."""
    out = []
    for eqn in jaxpr.eqns:
        out.append(eqn.primitive.name)
        if eqn.primitive.name == "cond" and not into_cond:
            continue
        for sub in _subjaxprs(eqn):
            out += _primitives(sub, into_cond)
    return out


def _args(rows, seeded, masks, dead):
    """``rows`` live rows that draw under both masks; the optional arguments
    there or not."""
    return _batch([(0.8, 0.9, 5, i, True) for i in range(rows)],
                  with_seeds=seeded, with_topk=masks, with_active=dead)


@pytest.mark.parametrize("seeded,masks,dead", list(itertools.product(
    (False, True), repeat=3)))
def test_no_sort_noise_or_cumsum_outside_a_conditional(seeded, masks, dead):
    kw = _args(4, seeded, masks, dead)
    jaxpr = jax.make_jaxpr(
        lambda lg: sample_rows_with_logprobs(lg, **kw))(_logits(4)).jaxpr
    outside = set(_primitives(jaxpr, into_cond=False))
    everywhere = set(_primitives(jaxpr, into_cond=True))
    assert "cond" in outside
    assert not outside & HEAVY, outside & HEAVY
    # the same walk does see them inside the branches: the check can fail
    assert {"sort", "cumsum"} <= everywhere
    assert everywhere & {"random_bits", "threefry2x32"}
    # outside: the argmax and the model's log-softmax, once each
    assert {"argmax", "reduce_max", "exp"} <= outside


def test_the_conditionals_stay_conditionals_under_vmap_and_while():
    """The verify window's ``vmap`` over positions batches logits, keys and
    steps, not the predicates; a ``while_loop`` body keeps them too."""
    rows, k = 3, 2
    kw = _args(rows, True, True, True)
    lg = jnp.stack([_logits(rows, s) for s in range(k + 1)], axis=1)
    jaxpr = jax.make_jaxpr(lambda lg: _sample_verify_positions(
        lg, kw["active"], kw["temps"], kw["top_ps"], kw["key"], kw["seeds"],
        kw["steps"], kw["top_ks"], k))(lg).jaxpr
    outside = set(_primitives(jaxpr, into_cond=False))
    assert "cond" in outside and "select_n" in outside
    assert not outside & HEAVY, outside

    def loop(lg):
        def body(c):
            n, alive, tok = c
            t, _ = sample_rows_with_logprobs(lg[n], **dict(kw, active=alive))
            return n + 1, alive & (t != 0), t
        return jax.lax.while_loop(lambda c: c[0] < 3, body,
                                  (0, kw["active"], jnp.zeros(rows, jnp.int32)))
    jaxpr = jax.make_jaxpr(loop)(jnp.moveaxis(lg, 1, 0)).jaxpr
    outside = set(_primitives(jaxpr, into_cond=False))
    assert {"while", "cond"} <= outside
    assert not outside & HEAVY, outside


# -- (b) against the plain reference ------------------------------------------------

@pytest.mark.parametrize("name,rows,kw", [
    ("all greedy", [GREEDY, GREEDY_WITH_MASKS, GREEDY], {}),
    ("all greedy, no seeds / active / top_k", [GREEDY, GREEDY],
     dict(with_seeds=False, with_active=False, with_topk=False)),
    ("greedy beside masked rows", [GREEDY, NUCLEUS, TOPK, BOTH, GREEDY], {}),
    ("masked rows seeded", [NUCLEUS, TOPK, BOTH], {}),
    ("masked rows, a dead one beside", [BOTH, DEAD_MASKED, NUCLEUS], {}),
    ("masked rows unseeded, own keys", [BOTH_UNSEEDED, BOTH_UNSEEDED, NUCLEUS],
     {}),
    ("masked rows, one key for the batch", [BOTH, NUCLEUS, BOTH],
     dict(with_seeds=False)),
    ("nucleus rows, no top_k argument", [NUCLEUS, GREEDY, NUCLEUS],
     dict(with_topk=False)),
    ("only dead rows draw", [GREEDY, DEAD_MASKED], {}),
])
def test_greedy_and_masked_rows_get_the_reference_bytes(name, rows, kw):
    """Rows that set a mask keep the arithmetic they had, greedy rows the
    argmax and its log-probability; jitted, as the tick runs it."""
    args = _batch(rows, **kw)
    lg = _logits(len(rows))
    new = jax.jit(lambda lg: sample_rows_with_logprobs(lg, **args))(lg)
    old = jax.jit(lambda lg: reference_sample(lg, **args))(lg)
    _same(new, old)
    tok = np.asarray(new[0])
    for i, row in enumerate(rows):
        if not row[4]:
            assert tok[i] == 0 and float(new[1][i]) == 0.0
        elif row[0] == 0.0:
            assert tok[i] == int(jnp.argmax(lg[i].astype(jnp.float32)))


def test_a_row_without_masks_draws_from_its_scaled_logits_exactly():
    """``top_p >= 1`` and ``top_k <= 0`` are exactly off: such a row's draw
    is ``categorical`` over its temperature-scaled logits, whether or not a
    neighbour makes the batch sort."""
    lg = _logits(3)
    plain = (0.8, 1.0, 0, 21, True)
    for rows in ([plain, GREEDY, GREEDY], [plain, BOTH, NUCLEUS]):
        args = _batch(rows)
        tok, lp = jax.jit(
            lambda lg: sample_rows_with_logprobs(lg, **args))(lg)
        key = jax.random.fold_in(jax.random.PRNGKey(21), 0)
        f32 = lg[0].astype(jnp.float32)
        want = jax.random.categorical(key, f32 / jnp.float32(0.8))
        assert int(tok[0]) == int(want)
        assert float(lp[0]) == float(jax.nn.log_softmax(f32)[int(want)])


# -- (c) a row's bits do not depend on its batch, across the branches -------------

TRIO = {"greedy": GREEDY, "draws": (0.8, 1.0, 0, 31, True),
        "nucleus": (0.8, 0.9, 0, 32, True)}
SUBSETS = [c for n in (1, 2, 3) for c in itertools.combinations(TRIO, n)]


def _trio_logits():
    return dict(zip(TRIO, _logits(3, seed=7)))


def _run_subset(names, dead, sampler, steps=None):
    """The named rows in one batch (a dead, masked, drawing row first and
    last with ``dead``): {name: (token, logprob bits)}."""
    lgs = _trio_logits()
    rows = [TRIO[n] for n in names]
    lg = [lgs[n] for n in names]
    if dead:
        rows = [DEAD_MASKED] + rows + [DEAD_MASKED]
        lg = [lgs["draws"]] + lg + [lgs["nucleus"]]
    args = _batch(rows)
    args["steps"] = jnp.full((len(rows),), 5, jnp.int32)
    tok, lp = _bits(sampler(jnp.stack(lg), args))
    off = 1 if dead else 0
    return {n: (int(tok[i + off]), int(lp[i + off]))
            for i, n in enumerate(names)}


def _jitted(lg, args):
    return jax.jit(lambda lg: sample_rows_with_logprobs(lg, **args))(lg)


@pytest.mark.parametrize("dead", (False, True), ids=("live", "dead-beside"))
@pytest.mark.parametrize("names", SUBSETS, ids="+".join)
def test_a_rows_bits_do_not_depend_on_its_batch(names, dead):
    """A greedy row, a seeded drawing row and a seeded nucleus row, each
    alone, in pairs and all three: three different sets of branches run,
    each row gets the token and the log-probability it gets alone."""
    alone = {n: _run_subset((n,), False, _jitted)[n] for n in names}
    assert _run_subset(names, dead, _jitted) == alone


# -- (d) under vmap over positions, inside a while_loop ---------------------------

def _vmapped(lg, args):
    """The verify window's form: three positions, the row's own logits at
    position 1 (output index 5: ``steps`` 4 + 1)."""
    stack = jnp.stack([lg[::-1], lg, lg * 0.5], axis=1)
    t, lp, _ = jax.jit(lambda st: _sample_verify_positions(
        st, args["active"], args["temps"], args["top_ps"], args["key"],
        args["seeds"], args["steps"] - 1, args["top_ks"], 2))(stack)
    return t[:, 1], lp[:, 1]


def _looped(lg, args):
    """Eight steps of a ``while_loop`` whose rows die on the way (rows 0,
    1, .. at steps 1, 2, ..) and are alive again for the last: the row's
    own logits at step 7, earlier steps over other logits."""
    n_rows = lg.shape[0]

    def body(c):
        n, tok, lp = c
        alive = args["active"] & ((jnp.arange(n_rows) >= n) | (n == 7))
        cur = jnp.where(n == 7, lg, jnp.roll(lg, 1, axis=1) * 0.25)
        t, l = sample_rows_with_logprobs(
            cur, **dict(args, active=alive,
                        steps=args["steps"] - 7 + n))
        return n + 1, t, l
    _, tok, lp = jax.jit(lambda: jax.lax.while_loop(
        lambda c: c[0] < 8, body,
        (0, jnp.zeros(n_rows, jnp.int32), jnp.zeros(n_rows, jnp.float32))))()
    return tok, lp


@pytest.mark.parametrize("sampler", (_vmapped, _looped),
                         ids=("vmap", "while_loop"))
@pytest.mark.parametrize("names,dead", [
    (("greedy",), False), (("draws",), True), (("nucleus",), False),
    (("greedy", "draws"), False), (("greedy", "nucleus"), True),
    (("greedy", "draws", "nucleus"), False),
    (("greedy", "draws", "nucleus"), True)],
    ids=lambda v: "+".join(v) if isinstance(v, tuple) else str(v))
def test_a_rows_bits_under_vmap_and_inside_a_loop(names, dead, sampler):
    """The same rows through the speculative window's ``vmap`` and through
    a loop's body give what the plain call gives each row alone."""
    alone = {n: _run_subset((n,), False, _jitted)[n] for n in names}
    assert _run_subset(names, dead, sampler) == alone


# -- (e) the counters that say how often it engages -------------------------------

EC = dict(max_rows=4, max_seq_len=256, page_size=32, prefill_bucket=32)
COUNTERS = ("sample_stages_total", "sample_draw_stages_total",
            "sample_sort_stages_total")


@pytest.fixture(scope="module")
def cfg_params():
    cfg = tiny_cfg(vocab_size=131, hidden_size=48, intermediate_size=96,
                   num_heads=4, num_kv_heads=2, head_dim=12,
                   max_position_embeddings=512)
    return cfg, rand_params(cfg, qtype="bf16")


def _prompt(cfg, n, seed):
    return [int(t) for t in np.random.default_rng(seed).integers(
        0, cfg.vocab_size, n)]


def _serve(eng, req, max_ticks=200):
    """Ticks until ``req`` ends: [(the counters' advance, mixed?)] a tick."""
    eng.submit(req)
    m, out = eng.metrics, []
    for _ in range(max_ticks):
        if req.finish_reason is not None:
            return out
        before = [m[c] for c in COUNTERS] + [m["mixed_steps"]]
        eng._tick()
        after = [m[c] for c in COUNTERS] + [m["mixed_steps"]]
        out.append((tuple(a - b for a, b in zip(after[:3], before[:3])),
                    after[3] > before[3]))
    raise AssertionError(req.finish_reason)


@pytest.mark.parametrize("horizon", (1, 4))
@pytest.mark.parametrize("name,params,per_stage", [
    ("greedy", dict(temperature=0.0, top_p=0.5, top_k=3), (1, 0, 0)),
    ("draws", dict(temperature=0.8, seed=3), (1, 1, 0)),
    ("nucleus", dict(temperature=0.8, top_p=0.9, seed=3), (1, 1, 1)),
    ("top_k", dict(temperature=0.8, top_k=5, seed=3), (1, 1, 1)),
])
def test_the_counters_follow_the_rows_parameters(cfg_params, name, params,
                                                 per_stage, horizon):
    """One request of 40 tokens in 32-token chunks: the first mixed tick
    emits nothing (its first-token stage is counted, and draws nothing),
    the second samples the first token and holds a decode stage with no
    live row (a dense model's row takes its first decode step in the NEXT
    tick: two stages, of which the first alone can draw), every steady
    tick is one stage whatever its horizon."""
    cfg, prm = cfg_params
    eng = ServingEngine(cfg, prm, EngineConfig(decode_horizon=horizon, **EC))
    ticks = _serve(eng, Request(prompt_ids=_prompt(cfg, 40, 1),
                                max_new_tokens=9, **params))
    mixed = [d for d, mx in ticks if mx]
    steady = [d for d, mx in ticks if not mx]
    assert mixed[0] == (1, 0, 0), ticks           # a chunk that emits nothing
    assert mixed[1] == (2,) + per_stage[1:], ticks
    assert len(mixed) == 2 and steady, ticks
    assert all(d == per_stage for d in steady), ticks
    m = eng.metrics
    assert m["sample_stages_total"] == 3 + len(steady)


def test_the_counters_over_a_mixed_batch_and_a_rollback(cfg_params):
    """A greedy request decodes alone (no draw), a drawing one joins (draws,
    no sort), a nucleus one joins (sorts); a tick that rolls back takes its
    counts with it and the retry counts its own."""
    cfg, prm = cfg_params
    inj = FaultInjector().inject("sample", TransientFault, nth=1)
    eng = ServingEngine(cfg, prm, EngineConfig(retry_backoff_s=0.0, **EC),
                        fault_injector=inj)
    # tick-exact below: in the serial order an arrival's mixed tick is the
    # very next one (with a program queued it is read first)
    eng._ahead_blocked = lambda: "dirty"

    def counts():
        return [eng.metrics[c] for c in COUNTERS]

    greedy = Request(prompt_ids=_prompt(cfg, 12, 2), max_new_tokens=40)
    eng.submit(greedy)
    assert eng._tick() is False and inj.fired == 1
    assert counts() == [0, 0, 0] and eng.metrics["retries"] == 1
    assert eng._tick() is True
    assert counts() == [2, 0, 0]
    for _ in range(3):
        eng._tick()
    assert counts() == [5, 0, 0]
    eng.submit(Request(prompt_ids=_prompt(cfg, 10, 3), max_new_tokens=30,
                       temperature=0.9, seed=1))
    eng._tick()             # its first token draws; the greedy row decodes
    assert counts() == [7, 1, 0]
    eng._tick()             # both rows decode: the stage draws
    assert counts() == [8, 2, 0]
    eng.submit(Request(prompt_ids=_prompt(cfg, 10, 4), max_new_tokens=4,
                       temperature=0.9, top_p=0.8, seed=2))
    eng._tick()             # its first token sorts; the decode stage draws
    assert counts() == [10, 4, 1]
    eng._tick()             # the three rows decode: the stage sorts
    assert counts() == [11, 5, 2]


# -- (f) the benchmark's two metric files read the names the program writes -------

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "perfbench")


def _metric_reader(name):
    """(the reader's module, its parameters) for a per-layer metric's file."""
    if BENCH not in sys.path:
        sys.path.insert(0, BENCH)
    with open(os.path.join(BENCH, "layer_metrics", name + ".json")) as f:
        spec = json.load(f)["reader"]
    return importlib.import_module("readers." + spec["kind"]), spec


def test_a_branchs_operations_keep_the_scope_sample_read_reads():
    """``sample_share`` finds the sampler's device time by the scope in an
    operation's name path: the compiled program's sorts carry it THROUGH
    the conditionals' branches."""
    reader, spec = _metric_reader("sample_share")
    kw = _args(4, True, True, True)
    held = ("temps", "top_ps", "top_ks")     # arguments: nothing folds away
    hlo = jax.jit(lambda lg, *a: sample_rows_with_logprobs(
        lg, **dict(kw, **dict(zip(held, a))))).lower(
        _logits(4), *(kw[k] for k in held)).compile().as_text()
    paths = re.findall(r'= [^\n]*? sort\([^\n]*?op_name="([^"]+)"', hlo)
    assert len(paths) >= 2, hlo[:400]
    for p in paths:
        assert reader.in_scope(p, spec["scope"]), p
        assert "/cond/branch_1_fun/" in p, p
    cond = re.findall(r'= [^\n]*? conditional\([^\n]*?op_name="([^"]+)"', hlo)
    assert cond and all(reader.in_scope(p, spec["scope"]) for p in cond), cond


def test_sample_share_books_a_conditional_its_own_time_only():
    """A planted timeline: a matmul outside the scope, the argmax, and a
    conditional that spans the sort it ran: (1 + (5 - 4) + 4) of 15."""
    reader, spec = _metric_reader("sample_share")
    ops = [("qmatmul", 0.0, 9.0, "jit(tick)/layer/mlp/qmatmul"),
           ("fusion", 9.0, 1.0, "jit(tick)/tick/decode/sample/argmax"),
           ("conditional", 10.0, 5.0, "jit(tick)/tick/decode/sample/cond"),
           ("sort", 10.5, 4.0,
            "jit(tick)/tick/decode/sample/cond/branch_1_fun/jit(sort)/sort")]
    hit, total, n = reader.scoped_seconds({"ops": ops}, spec["scope"])
    assert (hit, total, n) == (6.0, 15.0, 3)


def test_sample_draw_share_reads_the_engines_counters(cfg_params):
    """``sample_draw_share`` over two scrapes of a real engine's metrics: 0
    for a greedy request, the drawing stages' share once a request draws,
    None from a program without the counters (the parent)."""
    reader, spec = _metric_reader("sample_draw_share")
    cfg, prm = cfg_params
    eng = ServingEngine(cfg, prm, EngineConfig(**EC))

    def scrape():
        return {"metrics": dict(eng.metrics)}

    def read(start, end):
        return reader.read(spec, {"record": {"counters": {
            "start": start, "end": end}}})

    t0 = scrape()
    _serve(eng, Request(prompt_ids=_prompt(cfg, 12, 5), max_new_tokens=4))
    t1 = scrape()
    assert read(t0, t1) == 0.0
    _serve(eng, Request(prompt_ids=_prompt(cfg, 12, 6), max_new_tokens=4,
                        temperature=0.7))
    t2 = scrape()
    # (every stage but the decode stage of the tick that completes its
    # prompt, which holds no live row: the first token, three steady ticks)
    assert read(t1, t2) == 80.0
    assert 0.0 < read(t0, t2) < 100.0
    old = {"metrics": {k: v for k, v in t2["metrics"].items()
                       if not k.startswith("sample_")}}
    assert read(old, old) is None
