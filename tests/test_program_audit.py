"""jaxprcheck (trace tier): per-rule fixtures, manifest lifecycle, gate.

Three layers, mirroring tests/test_static_analysis.py:

1. fixture tests — every JP rule fires on a known-bad jitted program and
   stays quiet on the known-good rewrite (the before/after pairs in
   docs/quickstart/static_analysis.md);
2. manifest tests — round-trip (``--update`` then audit is clean, and a
   second ``--update`` is a no-op), drift detection (mutating a donation
   in a fixture registry OR the locked file fails CI with a readable
   diff), suppression policy (reasons required);
3. the tier-1 gate — zero unsuppressed error-tier findings over the REAL
   program registry against the checked-in manifest, fp8+bf16 grid
   coverage, and the mixed tick's 2-dispatch JP106 gate.
"""

import json
import warnings
from functools import partial
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

from ipex_llm_tpu.analysis import core
from ipex_llm_tpu.analysis.trace import manifest as manifest_mod
from ipex_llm_tpu.analysis.trace import rules as jp
from ipex_llm_tpu.analysis.trace import runner
from ipex_llm_tpu.analysis.trace.registry import ProgramSpec, real_registry
from ipex_llm_tpu.analysis.trace.tickaudit import (TickSpec,
                                                   mixed_tick_dispatch_count)
from ipex_llm_tpu.analysis.trace.tracer import trace_entry

REPO = Path(__file__).resolve().parent.parent


def codes(findings, suppressed=False):
    return [f.rule for f in findings if f.suppressed == suppressed]


def errors(findings):
    return [f for f in findings
            if not f.suppressed and f.severity == "error"]


def sds(*shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype)


# --------------------------------------------------------------------------
# fixture programs (tiny: lowering is milliseconds)
# --------------------------------------------------------------------------

@partial(jax.jit, donate_argnums=(0,))
def _fx_donated(state, x):
    return state + x, x.sum()


@jax.jit
def _fx_undonated(state, x):
    return state + x, x.sum()


@partial(jax.jit, donate_argnums=(1,))
def _fx_held_donated(state, x):
    return state + x, x * 1.0


@partial(jax.jit, donate_argnums=(0,))
def _fx_donation_dropped(state, x):
    return (state * 2.0).sum(), x + 1.0


_POOL_SHAPE = (2, 8, 2, 16, 8)      # [L, P, H, page, D]


@jax.jit
def _fx_fp8_upcast(pool, idx):
    wide = pool.astype(jnp.bfloat16)            # wholesale pool upcast
    return jnp.take(wide, idx, axis=1).sum(), pool


@jax.jit
def _fx_fp8_dequant_at_read(pool, idx):
    tile = jnp.take(pool, idx, axis=1)          # gather e5m2 codes
    return tile.astype(jnp.bfloat16).sum(), pool


@jax.jit
def _fx_callback(x):
    jax.debug.print("x sum {}", x.sum())
    return x * 2


_FX_CONST = jnp.arange(32768, dtype=jnp.float32)          # 128 KiB
_FX_SMALL_CONST = jnp.arange(16, dtype=jnp.float32)


@jax.jit
def _fx_bloated(x):
    return x + _FX_CONST


@jax.jit
def _fx_lean(x):
    return x + _FX_SMALL_CONST


def _state_build(pt):
    return (sds(64, 64), sds(64, 64)), {}


def _pool_build(pt):
    return (sds(*_POOL_SHAPE, dtype=jnp.float8_e5m2),
            sds(3, dtype=jnp.int32)), {}


def _vec_build(pt):
    return (sds(32768),), {}


def _vec16_build(pt):
    return (sds(16),), {}


def _mismatched_build(pt):
    # x deliberately a different aval than state: the state donation has
    # no output to alias with and lowering must drop it
    return (sds(64, 64), sds(32, 32)), {}


def mkspec(fn, build, arg_names, name="fx.prog", grid=({},), **over):
    kw = dict(name=name, fn=fn, build=build, grid=tuple(grid),
              arg_names=tuple(arg_names), max_lowerings=8)
    kw.update(over)
    return ProgramSpec(**kw)


def _entry(spec, point=None):
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")   # DonationWarning fixtures
        return trace_entry(spec, point or {})


# --------------------------------------------------------------------------
# JP101 donation-coverage
# --------------------------------------------------------------------------

STATE_SPEC = dict(build=_state_build, arg_names=("state", "x"),
                  dead=frozenset({"state"}), held=frozenset({"x"}))


def test_jp101_fires_on_undonated_dead_input():
    spec = mkspec(_fx_undonated, **STATE_SPEC)
    found = list(jp.check_donation(spec, _entry(spec)))
    assert [f.rule for f in found] == ["JP101"]
    assert "re-uploaded" in found[0].message
    assert found[0].tier == "trace"


def test_jp101_quiet_when_donated():
    spec = mkspec(_fx_donated, **STATE_SPEC)
    entry = _entry(spec)
    assert list(jp.check_donation(spec, entry)) == []
    # and the alias really survived lowering
    assert any(l.alias is not None for l in entry.leaves
               if l.arg == "state")


def test_jp101_flags_donated_but_held_buffer():
    spec = mkspec(_fx_held_donated, **STATE_SPEC)
    found = list(jp.check_donation(spec, _entry(spec)))
    assert any(f.rule == "JP101" and "use-after-donate" in f.message
               for f in found)


def test_jp101_flags_donation_that_lowering_dropped():
    spec = mkspec(_fx_donation_dropped, **{**STATE_SPEC,
                                           "build": _mismatched_build})
    found = list(jp.check_donation(spec, _entry(spec)))
    assert any("no alias" in f.message for f in found)


def test_jp101_small_dead_inputs_are_not_demanded():
    spec = mkspec(_fx_undonated, **{**STATE_SPEC,
                                    "min_donate_bytes": 1 << 20})
    assert list(jp.check_donation(spec, _entry(spec))) == []


# --------------------------------------------------------------------------
# JP102 fp8-pool integrity
# --------------------------------------------------------------------------

POOL_SPEC = dict(build=_pool_build, arg_names=("pool", "idx"),
                 held=frozenset({"pool"}))


def test_jp102_fires_on_wholesale_pool_upcast():
    spec = mkspec(_fx_fp8_upcast, **POOL_SPEC)
    found = list(jp.check_fp8_integrity(spec, _entry(spec)))
    assert [f.rule for f in found] == ["JP102"]
    assert "upcast" in found[0].message


def test_jp102_quiet_on_dequant_at_read():
    spec = mkspec(_fx_fp8_dequant_at_read, **POOL_SPEC)
    assert list(jp.check_fp8_integrity(spec, _entry(spec))) == []


def test_jp102_quiet_without_fp8_inputs():
    spec = mkspec(_fx_donated, **STATE_SPEC)
    assert list(jp.check_fp8_integrity(spec, _entry(spec))) == []


# --------------------------------------------------------------------------
# JP107 packed-weight integrity
# --------------------------------------------------------------------------

_W_STACK = (2, 64, 128)        # [L, in_packed, out] nibble-packed planes


@jax.jit
def _fx_weight_wholesale(params, x):
    # dequantize the WHOLE stack up front: the [L, 2*in_packed, out] wide
    # form JP107 forbids (a full-width HBM copy of every layer's weights)
    p = params.astype(jnp.int32)
    codes = jnp.concatenate([p & 0x0F, p >> 4], axis=1)      # [L, in, out]
    w = codes.astype(jnp.float32) - 8.0
    return jnp.einsum("mi,lio->lmo", x, w).sum(axis=0), params


@jax.jit
def _fx_weight_per_layer(params, x):
    # the dequant-fused design: each layer's plane widens INSIDE the scan
    # body, right next to the matmul that consumes it (a per-layer 2-D
    # tile, never the full stack)
    def body(acc, plane):
        p = plane.astype(jnp.int32)
        codes = jnp.concatenate([p & 0x0F, p >> 4], axis=0)  # [in, out]
        w = codes.astype(jnp.float32) - 8.0
        return acc + x @ w, None

    acc, _ = jax.lax.scan(
        body, jnp.zeros((x.shape[0], _W_STACK[2]), jnp.float32), params)
    return acc, params


def _weight_build(pt):
    return (sds(*_W_STACK, dtype=jnp.uint8),
            sds(4, 2 * _W_STACK[1])), {}


WEIGHT_SPEC = dict(build=_weight_build, arg_names=("params", "x"),
                   held=frozenset({"params"}))


def test_jp107_fires_on_wholesale_stack_dequant():
    spec = mkspec(_fx_weight_wholesale, **WEIGHT_SPEC)
    found = list(jp.check_weight_integrity(spec, _entry(spec)))
    assert [f.rule for f in found] == ["JP107"]
    assert "wholesale" in found[0].message


def test_jp107_quiet_on_per_layer_dequant_in_scan():
    spec = mkspec(_fx_weight_per_layer, **WEIGHT_SPEC)
    assert list(jp.check_weight_integrity(spec, _entry(spec))) == []


def test_jp107_quiet_without_packed_inputs():
    spec = mkspec(_fx_donated, **STATE_SPEC)
    assert list(jp.check_weight_integrity(spec, _entry(spec))) == []


_W5_STACK = (2, 40, 128)   # dual-plane 5-bit rows = 5*in/8 -> in = 64


@jax.jit
def _fx_weight5_wholesale(params, x):
    # materializes the [L, in, out] dense form of a 5-bit plane stack
    w = jnp.broadcast_to(params[:, :1, :].astype(jnp.float32),
                         (_W5_STACK[0], _W5_STACK[1] * 8 // 5,
                          _W5_STACK[2]))
    return jnp.einsum("mi,lio->lmo", x, w).sum(axis=0), params


def test_jp107_covers_5bit_plane_ratio():
    """The dual-plane 5-bit layout (quantize/core._pack_5bit: data rows =
    5*in/8) is protected too — its dense stack shape is neither 1x nor
    2x the plane rows, so the rule carries the 8/5 ratio explicitly."""
    spec = mkspec(
        _fx_weight5_wholesale,
        build=lambda pt: ((sds(*_W5_STACK, dtype=jnp.uint8),
                           sds(4, _W5_STACK[1] * 8 // 5)), {}),
        arg_names=("params", "x"), held=frozenset({"params"}))
    found = list(jp.check_weight_integrity(spec, _entry(spec)))
    assert [f.rule for f in found] == ["JP107"]


# --------------------------------------------------------------------------
# JP108 paged-pool-by-value
# --------------------------------------------------------------------------

def _paged(k, v, tables):
    from ipex_llm_tpu.kv import PagedKVCache

    return PagedKVCache(k, v, tables, jnp.zeros((), jnp.int32))


@partial(jax.jit, donate_argnums=(0,))
def _fx_pool_scanned(cache, x):
    # the pool rides the layer scan BY VALUE: every step gets a layer
    # sliced out of the stack as xs and stacks it back as ys
    def body(x, layer):
        kl, vl = layer
        kl = kl.at[cache.tables[0, 0], :, 0].set(x.astype(kl.dtype))
        return x + kl[1, :, 0].astype(x.dtype) + vl[1, :, 0].astype(x.dtype), \
            (kl, vl)

    x, (k, v) = jax.lax.scan(body, x, (cache.k, cache.v))
    return _paged(k, v, cache.tables), x


@partial(jax.jit, donate_argnums=(0,))
def _fx_pool_layer_sliced(cache, x):
    # the stack is carried, but each step still takes its whole layer out
    # (dynamic_slice) and puts it back (dynamic_update_slice)
    def body(carry, layer):
        x, k = carry
        kl = jax.lax.dynamic_index_in_dim(k, layer, 0, keepdims=False)
        kl = kl.at[cache.tables[0, 0], :, 0].set(x.astype(kl.dtype))
        k = jax.lax.dynamic_update_index_in_dim(k, kl, layer, 0)
        return (x + kl[1, :, 0].astype(x.dtype), k), None

    (x, k), _ = jax.lax.scan(body, (x, cache.k),
                             jnp.arange(_POOL_SHAPE[0]))
    return _paged(k, cache.v, cache.tables), x


@partial(jax.jit, donate_argnums=(0,))
def _fx_pool_in_place(cache, x):
    # the design: the stacks ride the carry, every step writes and reads
    # through (layer, page); xs hold the layer's index alone
    def body(carry, layer):
        x, k = carry
        page = cache.tables[0, 0]
        k = k.at[layer, page, :, 0].set(x.astype(k.dtype))
        return (x + k[layer, page + 1, :, 0].astype(x.dtype), k), None

    (x, k), _ = jax.lax.scan(body, (x, cache.k),
                             jnp.arange(_POOL_SHAPE[0]))
    return _paged(k, cache.v, cache.tables), x


def _paged_build(pt):
    pool = sds(*_POOL_SHAPE, dtype=jnp.bfloat16)
    return (_paged(pool, pool, sds(4, 3, dtype=jnp.int32)),
            sds(_POOL_SHAPE[2], _POOL_SHAPE[4])), {}


PAGED_SPEC = dict(build=_paged_build, arg_names=("cache", "x"),
                  dead=frozenset({"cache"}))


def test_jp108_fires_on_pool_scanned_by_value():
    spec = mkspec(_fx_pool_scanned, **PAGED_SPEC)
    found = list(jp.check_pool_in_place(spec, _entry(spec)))
    assert [f.rule for f in found] == ["JP108"]
    assert "xs/ys" in found[0].message


def test_jp108_fires_on_whole_layer_slice_and_write_back():
    spec = mkspec(_fx_pool_layer_sliced, **PAGED_SPEC)
    found = list(jp.check_pool_in_place(spec, _entry(spec)))
    assert [f.rule for f in found] == ["JP108", "JP108"]
    assert "dynamic_slice" in found[0].message
    assert "dynamic_update_slice" in found[1].message


def test_jp108_quiet_on_pool_addressed_in_place():
    spec = mkspec(_fx_pool_in_place, **PAGED_SPEC)
    assert list(jp.check_pool_in_place(spec, _entry(spec))) == []


@partial(jax.jit, donate_argnums=(0,))
def _fx_dense_scanned(cache, x):
    def body(x, layer):
        kl, vl = layer
        kl = kl.at[0, :, 0].set(x.astype(kl.dtype))
        return x + vl[1, :, 0].astype(x.dtype), (kl, vl)

    x, (k, v) = jax.lax.scan(body, x, (cache.k, cache.v))
    return type(cache)(k, v, cache.length), x


def test_jp108_quiet_on_a_dense_cache_scan():
    """A dense cache has no block table to address through: its layer
    scan over xs/ys is the design, not this rule's finding."""
    from ipex_llm_tpu.kv import KVCache

    pool = sds(*_POOL_SHAPE, dtype=jnp.bfloat16)
    spec = mkspec(
        _fx_dense_scanned,
        build=lambda pt: ((KVCache(pool, pool, sds(dtype=jnp.int32)),
                           sds(_POOL_SHAPE[2], _POOL_SHAPE[4])), {}),
        arg_names=("cache", "x"), dead=frozenset({"cache"}))
    assert list(jp.check_pool_in_place(spec, _entry(spec))) == []


# --------------------------------------------------------------------------
# JP103 host callbacks / JP105 constant bloat
# --------------------------------------------------------------------------

def test_jp103_fires_on_debug_print():
    spec = mkspec(_fx_callback, _vec_build, ("x",))
    found = list(jp.check_callbacks(spec, _entry(spec)))
    assert [f.rule for f in found] == ["JP103"]
    assert "debug_print" in found[0].message


def test_jp103_quiet_on_callback_free_program():
    spec = mkspec(_fx_lean, _vec16_build, ("x",))
    assert list(jp.check_callbacks(spec, _entry(spec))) == []


def test_jp105_fires_on_baked_constant():
    spec = mkspec(_fx_bloated, _vec_build, ("x",))
    found = list(jp.check_constant_bloat(spec, _entry(spec)))
    assert [f.rule for f in found] == ["JP105"]
    assert found[0].severity == "warn"


def test_jp105_quiet_under_threshold():
    spec = mkspec(_fx_lean, _vec16_build, ("x",))
    assert list(jp.check_constant_bloat(spec, _entry(spec))) == []


# --------------------------------------------------------------------------
# JP104 recompile surface (and signature dedupe)
# --------------------------------------------------------------------------

def test_jp104_bounds_the_grid_lowering_count(tmp_path):
    def build(pt):
        return (sds(pt["n"], 64), sds(pt["n"], 64)), {}

    spec = mkspec(_fx_donated, build, ("state", "x"),
                  grid=({"n": 16}, {"n": 32}, {"n": 64}),
                  dead=frozenset({"state"}), max_lowerings=2)
    findings = runner.audit(specs=(spec,), ticks=(),
                            manifest_path=tmp_path / "m.json", update=True)
    assert any(f.rule == "JP104" and "above the spec bound" in f.message
               for f in findings)


def test_jp104_dedupes_identical_signatures(tmp_path):
    spec = mkspec(_fx_donated, _state_build, ("state", "x"),
                  grid=({"rep": 1}, {"rep": 2}),   # same avals + statics
                  dead=frozenset({"state"}), max_lowerings=1)
    findings = runner.audit(specs=(spec,), ticks=(),
                            manifest_path=tmp_path / "m.json", update=True)
    assert not any(f.rule == "JP104" for f in findings)
    lock = json.loads((tmp_path / "m.json").read_text())
    assert lock["programs"]["fx.prog"]["lowerings"] == 1


# --------------------------------------------------------------------------
# JP106 tick dispatch count
# --------------------------------------------------------------------------

_TICK_SRC = '''
import jax
from functools import partial

@partial(jax.jit)
def _prog_a(x):
    return x

@partial(jax.jit)
def _prog_b(x):
    return x

{extra_def}

def _mixed_step(self):
    y = _prog_a(1)
    {extra_call}
    return _horizon_step(y)

def _horizon_step(y):
    return _prog_b(y)
'''


def _tick_spec(**over):
    kw = dict(name="mixed", module="fixture", programs=("_prog_a", "_prog_b"),
              entries=("_mixed_step", "_horizon_step"),
              max_dispatches=2)
    kw.update(over)
    return TickSpec(**kw)


def test_jp106_quiet_on_declared_two_dispatch_tick():
    src = _TICK_SRC.format(extra_def="", extra_call="pass")
    from ipex_llm_tpu.analysis.trace.tickaudit import discover_tick_dispatches

    tick = _tick_spec()
    found = list(jp.check_tick_dispatches(
        tick, discover_tick_dispatches(tick, src)))
    assert found == []


def test_jp106_fires_on_a_third_dispatch_sneaking_in():
    src = _TICK_SRC.format(
        extra_def="@partial(jax.jit)\ndef _prog_c(x):\n    return x",
        extra_call="_prog_c(y)")
    from ipex_llm_tpu.analysis.trace.tickaudit import discover_tick_dispatches

    tick = _tick_spec()
    found = list(jp.check_tick_dispatches(
        tick, discover_tick_dispatches(tick, src)))
    assert any(f.rule == "JP106" and "_prog_c" in f.message for f in found)
    assert any("above the gate" in f.message for f in found)


def test_real_mixed_tick_issues_one_dispatch():
    # the ragged superkernel tick (_ragged_tick) drove it from 2 to
    # exactly 1, and JP106 keeps it there
    assert mixed_tick_dispatch_count() == 1


# --------------------------------------------------------------------------
# manifest lifecycle
# --------------------------------------------------------------------------

def _good_specs():
    return (mkspec(_fx_donated, **STATE_SPEC),)


def test_manifest_roundtrip_and_update_noop(tmp_path):
    path = tmp_path / "lock.json"
    first = runner.audit(specs=_good_specs(), ticks=(),
                         manifest_path=path, update=True)
    assert errors(first) == []
    before = path.read_text()
    clean = runner.audit(specs=_good_specs(), ticks=(), manifest_path=path)
    assert errors(clean) == []
    runner.audit(specs=_good_specs(), ticks=(), manifest_path=path,
                 update=True)
    assert path.read_text() == before     # --update is a no-op when clean


def test_manifest_missing_is_an_error(tmp_path):
    findings = runner.audit(specs=_good_specs(), ticks=(),
                            manifest_path=tmp_path / "absent.json")
    assert any(f.rule == "JP100" and "manifest missing" in f.message
               for f in errors(findings))


def test_mutated_donation_in_registry_fails_ci_shaped(tmp_path):
    """Lock the donated fixture, then swap in the un-donated twin (same
    avals): the audit must fail with JP101 AND a readable manifest diff."""
    path = tmp_path / "lock.json"
    runner.audit(specs=_good_specs(), ticks=(), manifest_path=path,
                 update=True)
    mutated = (mkspec(_fx_undonated, **STATE_SPEC),)
    findings = runner.audit(specs=mutated, ticks=(), manifest_path=path)
    errs = errors(findings)
    assert any(f.rule == "JP101" for f in errs)
    drift = [f for f in errs if f.rule == "JP100"]
    assert drift and all("manifest drift" in f.message for f in drift)
    assert any("state" in f.message for f in drift)   # names the alias


def test_mutated_lock_file_is_drift(tmp_path):
    path = tmp_path / "lock.json"
    runner.audit(specs=_good_specs(), ticks=(), manifest_path=path,
                 update=True)
    lock = json.loads(path.read_text())
    entry = next(iter(lock["programs"]["fx.prog"]["entries"].values()))
    entry["flops"] += 999
    path.write_text(json.dumps(lock))
    findings = runner.audit(specs=_good_specs(), ticks=(),
                            manifest_path=path)
    assert any(f.rule == "JP100" and "flops" in f.message
               for f in errors(findings))


# --------------------------------------------------------------------------
# suppression policy (registry-level, same rules as jaxlint comments)
# --------------------------------------------------------------------------

def test_spec_suppression_with_reason_is_honored(tmp_path):
    spec = mkspec(_fx_undonated, **STATE_SPEC,
                  suppress=(("JP101", "fixture: donation intentionally "
                                      "omitted for the bad-fires test"),))
    findings = runner.audit(specs=(spec,), ticks=(),
                            manifest_path=tmp_path / "m.json", update=True)
    assert not any(f.rule == "JP101" for f in errors(findings))
    assert "JP101" in codes(findings, suppressed=True)


def test_spec_suppression_without_reason_is_rejected(tmp_path):
    spec = mkspec(_fx_undonated, **STATE_SPEC, suppress=(("JP101", ""),))
    findings = runner.audit(specs=(spec,), ticks=(),
                            manifest_path=tmp_path / "m.json", update=True)
    assert any(f.rule == "JP100" and "no reason" in f.message
               for f in errors(findings))
    # the unsuppressed JP101 still reports too
    assert any(f.rule == "JP101" for f in errors(findings))


# --------------------------------------------------------------------------
# the real registry: tier-1 gate
# --------------------------------------------------------------------------

@pytest.fixture(scope="module")
def real_audit():
    return runner.audit()


def test_real_registry_zero_unsuppressed_errors(real_audit):
    errs = errors(real_audit)
    assert errs == [], "\n".join(f.render() for f in errs)


def test_real_registry_covers_fp8_and_bf16_grids():
    """Every single-chip form of the tick (steady, admission wave, pure
    chunk, speculative, int4 weights) is audited over BOTH pool storages."""
    (tick,) = [s for s in real_registry() if s.name == "serving.ragged_tick"]
    forms: dict = {}
    for pt in tick.grid:
        if "tp" in pt:
            continue
        form = tuple(sorted((k, v) for k, v in pt.items() if k != "kv"))
        forms.setdefault(form, set()).add(pt["kv"])
    assert len(forms) >= 10
    for form, kvs in forms.items():
        assert kvs == {"bf16", "fp8"}, form


def test_the_registry_audits_only_programs_an_entry_point_reaches():
    """Every ``serving.*`` program the audit locks IS the tick: a spec
    registered for a jitted entry no engine path dispatches (an oracle, a
    retired mode) would make every device-side PR regenerate manifest
    entries for programs nobody runs."""
    from ipex_llm_tpu.serving import engine

    serving = [s for s in real_registry() if s.name.startswith("serving.")]
    assert [s.name for s in serving] == ["serving.ragged_tick"]
    assert all(s.fn is engine._ragged_tick for s in serving)
    lock = json.loads(manifest_mod.DEFAULT_PATH.read_text())
    assert [n for n in lock["programs"] if n.startswith("serving.")] == [
        "serving.ragged_tick"]


def test_real_registry_covers_weight_qtype_axis():
    """The tick audits over stacked int4-packed
    weight planes too: the wq axis covers steady decode at both horizons
    on bf16+fp8 pools plus the admission-wave joiner tick — the grid
    JP107's packed-weight protection actually runs on."""
    specs = {s.name: s for s in real_registry()}
    tick_wq = [pt for pt in specs["serving.ragged_tick"].grid
               if pt.get("wq") == "sym_int4"]
    assert {(pt["width"], pt["horizon"]) for pt in tick_wq} == {
        (0, 1), (0, 8), (8, 1)}
    assert {pt["kv"] for pt in tick_wq} == {"bf16", "fp8"}
    assert {pt.get("wd", True) for pt in tick_wq} == {True, False}


def _dead_set(key: str) -> set:
    """What a tick variant has to donate: the cache and, a steady tick,
    the row state it carries in.  An admission-wave variant (width > 0)
    takes the row state out of the packed epoch, so it is handed none."""
    steady = "width=0" in key.split(",")
    return {"cache"} | ({"toks", "row_lens", "active", "steps", "remain"}
                        if steady else set())


def test_manifest_locks_int4_tick_donation_map():
    """The int4 grid points keep the tick's donation contract: the
    device-state set aliases, while the packed weight planes (params) and
    the rest of the held set never do — a donated plane would be freed
    under the host's feet on the very next tick."""
    lock = json.loads(manifest_mod.DEFAULT_PATH.read_text())
    entries = lock["programs"]["serving.ragged_tick"]["entries"]
    wq_entries = {k: v for k, v in entries.items() if "wq=sym_int4" in k}
    assert wq_entries, "weight-qtype grid points missing from the manifest"
    for key, entry in wq_entries.items():
        aliased = {a.split("[")[0] for a in entry["aliases"]}
        assert _dead_set(key) <= aliased, key
        assert not aliased & {"params", "temps", "top_ps", "seeds",
                              "top_ks", "eos", "key"}, key


def test_real_registry_names_every_issue_entry():
    names = {s.name for s in real_registry()}
    assert {"serving.ragged_tick",
            "generation.prefill_step", "generation.decode_loop",
            "generation.decode_one", "multimodal.mm_prefill",
            "multimodal.mm_decode",
            "structured.json_decode_step"} <= names


def test_checked_in_manifest_matches_tree(real_audit):
    # drift against ipex_llm_tpu/analysis/programs.lock.json IS a finding
    assert not any(f.rule == "JP100" and "drift" in f.message
                   for f in real_audit), \
        "\n".join(f.render() for f in real_audit if f.rule == "JP100")
    assert manifest_mod.DEFAULT_PATH.exists()


def test_manifest_locks_engine_donation_map():
    lock = json.loads(manifest_mod.DEFAULT_PATH.read_text())
    entries = lock["programs"]["serving.ragged_tick"]["entries"]
    plain = {k: v for k, v in entries.items()
             if "spec=" not in k and "wq=" not in k}   # those: tests below
    assert len(plain) >= 14
    for key, entry in plain.items():
        aliased_args = {a.split("[")[0] for a in entry["aliases"]}
        # the full dead set aliases; the held set never does — including
        # the PRNG key, which _checkpoint snapshots by reference for the
        # transient-retry contract (donating it hands rollback a deleted
        # buffer; tests/test_serving_faults.py replays that fault path)
        assert _dead_set(key) <= aliased_args, key
        assert not aliased_args & {"temps", "top_ps", "seeds", "top_ks",
                                   "eos", "key"}, key


def test_manifest_locks_spec_tick_donation_map():
    """The speculative tick variants (spec=4 grid points) donate the full
    device-state set INCLUDING the token-history ring ``hist`` (the host
    rebinds _dev["hist"] per tick), while the held set — sampling params,
    eos, and the checkpoint-held PRNG key — still never aliases."""
    lock = json.loads(manifest_mod.DEFAULT_PATH.read_text())
    entries = lock["programs"]["serving.ragged_tick"]["entries"]
    spec_entries = {k: v for k, v in entries.items() if "spec=4" in k}
    assert spec_entries, "spec grid points missing from the manifest"
    kvs = {k.split("kv=")[1].split(",")[0] for k in spec_entries}
    assert kvs == {"bf16", "fp8"}
    for key, entry in spec_entries.items():
        aliased = {a.split("[")[0] for a in entry["aliases"]}
        assert _dead_set(key) | {"hist"} <= aliased, key
        assert not aliased & {"temps", "top_ps", "seeds", "top_ks",
                              "eos", "key"}, key


def test_alias_parse_tolerates_quoted_sharding_braces():
    """mhlo.sharding attrs carry quoted nested braces; a flat brace regex
    truncated the attr dict and silently dropped real aliases (which
    would fail JP101 on a correct sharded tree)."""
    from ipex_llm_tpu.analysis.trace.tracer import parse_output_aliases

    line = ('  func.func public @main(%arg0: tensor<8x4xf32> '
            '{mhlo.sharding = "{maximal device=0}", '
            'tf.aliasing_output = 0 : i32}, '
            '%arg1: tensor<8x4xf32> {mhlo.sharding = "{replicated}"}, '
            '%arg2: tensor<4xf32> {tf.aliasing_output = 2 : i32}) '
            '-> (tensor<8x4xf32> {jax.result_info = "[0]"}) {')
    assert parse_output_aliases("module {\n" + line + "\n}") \
        == {0: 0, 2: 2}


# --------------------------------------------------------------------------
# CLI: exit codes and schema
# --------------------------------------------------------------------------

def test_trace_findings_carry_tier_in_json():
    spec = mkspec(_fx_undonated, **STATE_SPEC)
    found = list(jp.check_donation(spec, _entry(spec)))
    data = json.loads(core.to_json(found))
    assert data["version"] == 1
    assert data["findings"][0]["tier"] == "trace"
    # AST findings carry tier="ast" (additive schema-v1 field)
    from ipex_llm_tpu.analysis import analyze_source

    ast_f = analyze_source("import jax.numpy as jnp\n"
                           "def up(buf):\n    return jnp.asarray(buf)\n",
                           "ipex_llm_tpu/serving/snippet.py")
    assert json.loads(core.to_json(ast_f))["findings"][0]["tier"] == "ast"


def test_cli_distinct_exit_code_for_internal_error(monkeypatch, capsys):
    from ipex_llm_tpu.analysis import __main__ as cli

    def boom(**kw):
        raise RuntimeError("tracer exploded")

    monkeypatch.setattr(runner, "audit", boom)
    assert cli.main(["--trace"]) == 3
    assert "tracer exploded" in capsys.readouterr().err


def test_cli_usage_error_exit_code():
    from ipex_llm_tpu.analysis import __main__ as cli

    assert cli.main(["--update"]) == 2          # --update needs --trace
    assert cli.main(["/nonexistent/path"]) == 2


def test_cli_findings_exit_code(tmp_path):
    from ipex_llm_tpu.analysis import __main__ as cli

    bad = tmp_path / "ipex_llm_tpu" / "serving" / "snippet.py"
    bad.parent.mkdir(parents=True)
    bad.write_text("import jax.numpy as jnp\n"
                   "def up(buf):\n    return jnp.asarray(buf)\n")
    assert cli.main([str(bad)]) == 1
