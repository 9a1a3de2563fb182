"""Backend dispatch for the op library.

Every hot op has two implementations:
  - a Pallas TPU kernel (the ``xe_linear``/``xe_addons`` equivalent, §2.3), and
  - a pure-jnp XLA reference (the reference's CPU-fallback pattern,
    models/common.py:289-306), which doubles as the test oracle.

Selection is per-process: Pallas on TPU backends, jnp elsewhere, overridable
with IPEX_LLM_TPU_DISABLE_PALLAS=1 (mirrors the reference's env-flag style,
SURVEY.md §5 config system).
"""

from __future__ import annotations

import json
import os
import threading
from functools import lru_cache

import jax


# When a >1-device mesh drives the model, a bare ``pallas_call`` inside
# ``jit`` does not partition under GSPMD sharding propagation — kernels must
# be wrapped in ``jax.shard_map`` with per-shard block specs.  The generate/
# forward drivers record the active mesh here; the op dispatchers use it to
# emit shard_map-wrapped kernel calls (ops/pallas/*::*_sharded), falling back
# to the jnp/GSPMD path when no sharded wrapper applies.
_spmd_mesh = None
_spmd_active: bool = False


from contextlib import contextmanager


@contextmanager
def spmd(mesh_or_active):
    """Scoped SPMD context (nesting-safe).

    Pass the active ``jax.sharding.Mesh`` so kernel dispatch can emit
    shard_map-wrapped Pallas calls; a bare ``True`` marks SPMD tracing with
    an unknown mesh (kernels then fall back to the jnp path, the pre-r3
    behaviour).  Falsy values are a no-op passthrough.
    """
    global _spmd_mesh, _spmd_active
    prev_mesh, prev_active = _spmd_mesh, _spmd_active
    if mesh_or_active is None or mesh_or_active is False:
        pass
    elif mesh_or_active is True:
        # unknown mesh: kernels must fall back to jnp, so the outer scope's
        # recorded mesh must not leak into this scope
        _spmd_mesh = None
        _spmd_active = True
    else:
        _spmd_mesh = mesh_or_active
        _spmd_active = True
    try:
        yield
    finally:
        _spmd_mesh, _spmd_active = prev_mesh, prev_active


def spmd_mesh():
    """The mesh recorded by the innermost ``spmd(mesh)`` scope (or None)."""
    return _spmd_mesh


# Manual-mesh tensor parallelism (parallel/manual.py): INSIDE the fully-
# manual shard_map region the arrays are per-shard slices and GSPMD sees
# nothing, so dispatch must treat the trace as single-device compute with
# EXPLICIT collectives at the row-parallel combine points.  The record is
# (axis_name, collective_qtype): ops/linear.py reads it to psum row-
# parallel partials through ops/collectives.py under the engine's wire
# family, and models/decoder.logits_tail reads it to all-gather the
# vocab-sharded logits before sampling.
_manual_tp = threading.local()   # thread-local: engines trace on their
# own threads, and a mesh-slice fleet runs several in one process — a
# plain global would leak one engine's manual marker into a concurrent
# non-manual trace


@contextmanager
def manual_tp(axis: str, collective_qtype: str = "bf16"):
    """Scoped manual-TP marker for code tracing INSIDE a fully-manual
    shard_map region (mutually exclusive with ``spmd`` — the manual tick
    never enters the GSPMD dispatch path)."""
    prev = getattr(_manual_tp, "state", None)
    _manual_tp.state = (axis, collective_qtype)
    try:
        yield
    finally:
        _manual_tp.state = prev


def manual_tp_state() -> tuple[str, str] | None:
    """(axis_name, collective_qtype) inside a manual-TP region, else None."""
    return getattr(_manual_tp, "state", None)


# Context-parallel ring attention (ops/ring_attention.py): set by the
# training/prefill caller that guarantees full-sequence causal semantics
# (no left-pad, no sliding window).  None = dense attention.
_ring_mesh = None


def ring_mesh():
    return _ring_mesh


@contextmanager
def ring(mesh):
    """Scoped context-parallel mesh for sdpa dispatch."""
    global _ring_mesh
    prev = _ring_mesh
    _ring_mesh = mesh
    try:
        yield
    finally:
        _ring_mesh = prev


# --------------------------------------------------------------------------
# measured-ladder policy
# --------------------------------------------------------------------------
#
# The auto backend choice is DATA-DRIVEN from the microbench ladder
# (benchmark/microbench.py collect(): pallas_us vs xla_us per op family,
# per backend).  The builtin snapshot below records the repo's latest
# measured rounds; operators can re-point it at a fresh collect() dump via
# IPEX_LLM_TPU_DISPATCH_LADDER=/path/to/microbench.json (either the raw
# collect() row list or the {"backend": {op: {...}}} table form).  Env
# overrides (FORCE/DISABLE) always outrank the ladder.
#
# cpu: Pallas runs in the INTERPRETER, which the ladder shows losing to
# the XLA reference on every decode-path op (BENCH_r05: decode_attn
# 539.9us interpret vs 267.7us XLA bf16; 561.1 vs 493.2 fp8) — so the CPU
# auto policy selects XLA and interpret-mode stays opt-in via
# IPEX_LLM_TPU_FORCE_PALLAS=1.  tpu: no pair has been measured on a chip
# yet, so every family takes the platform default (the compiled kernel).
# A collect() dump taken on a chip and pointed at via
# IPEX_LLM_TPU_DISPATCH_LADDER keys under "tpu" automatically (its rows
# carry no "interpret" flag).
_BUILTIN_LADDER: dict[str, dict[str, dict[str, object]]] = {
    # every row carries a "recorded" bench-round stamp (surfaced via
    # ladder_provenance() in /health's dispatch block): the decision a
    # row drives is only as fresh as the round that measured it, and a
    # stale ladder should be VISIBLE, not silently trusted
    "cpu": {   # interpret-mode records, BENCH_r05
        "decode_attn": {"pallas_us": 539.9, "xla_us": 267.7,
                        "recorded": "BENCH_r05"},
        "decode_attn_fp8": {"pallas_us": 561.1, "xla_us": 493.2,
                            "recorded": "BENCH_r05"},
        "paged_decode_attn": {"pallas_us": 540.0, "xla_us": 268.0,
                              "recorded": "BENCH_r05"},
        "paged_decode_attn_fp8": {"pallas_us": 561.0, "xla_us": 493.0,
                                  "recorded": "BENCH_r05"},
    },
}


def _op_family(row_op: str) -> str:
    """Microbench row op name -> ladder family key: strip the shape
    suffixes, keep the dtype axis ('decode_attn_b1_h8/4_s256_d64_float8_
    e5m2' -> 'decode_attn_fp8')."""
    fam = row_op.split("_b", 1)[0].split("_r", 1)[0].split("_m", 1)[0]
    if "float8" in row_op or "fp8" in row_op.rsplit("_", 1)[-1]:
        fam += "_fp8"
    return fam


def _override_stamp(path: str, row: dict | None = None) -> str:
    """Recorded-at provenance for an override-ladder row: the row's own
    bench-round stamp when the dump carries one, else the dump file's
    mtime date — an override is a measurement too, and /health must show
    WHEN it was taken, not just that it exists."""
    if row:
        for key in ("recorded", "round", "bench_round"):
            if row.get(key):
                return str(row[key])
    try:
        import datetime

        mtime = os.path.getmtime(path)
        day = datetime.datetime.fromtimestamp(mtime).date().isoformat()
        return f"override:{os.path.basename(path)}@{day}"
    except OSError:
        return f"override:{os.path.basename(path)}"


@lru_cache(maxsize=1)
def _ladder() -> dict[str, dict[str, dict[str, float]]]:
    path = os.environ.get("IPEX_LLM_TPU_DISPATCH_LADDER", "")
    if not path:
        return _BUILTIN_LADDER
    with open(path, encoding="utf-8") as f:
        data = json.load(f)
    if isinstance(data, list):   # raw microbench collect() rows
        table: dict[str, dict[str, float]] = {}
        for row in data:
            if "pallas_us" in row and "xla_us" in row:
                table[_op_family(row.get("op", ""))] = {
                    "pallas_us": float(row["pallas_us"]),
                    "xla_us": float(row["xla_us"]),
                    "recorded": _override_stamp(path, row)}
        # collect() marks interpret-mode rows, so the dump itself records
        # which backend family it measured: interpret rows = CPU, plain
        # rows = compiled TPU.  Keying on the dump, NOT the loading
        # host's platform, means a TPU-recorded dump inspected on a CPU
        # box lands under "tpu" — CPU lookups miss it and fall back to
        # the platform default (XLA) instead of silently applying TPU
        # wins to the interpreter.
        backend = ("cpu" if any(r.get("interpret") for r in data)
                   else "tpu")
        return {backend: table}
    # table form: stamp any row missing provenance with the file's
    for fams in data.values():
        if isinstance(fams, dict):
            for rec in fams.values():
                if isinstance(rec, dict) and "recorded" not in rec:
                    rec["recorded"] = _override_stamp(path)
    return data


def backend_platform() -> str:
    return "tpu" if jax.default_backend() == "tpu" else "cpu"


def ladder_prefers_pallas(op: str | None) -> bool | None:
    """What the measured ladder says for this op family on this backend:
    True/False when a (pallas_us, xla_us) pair is recorded, None when the
    ladder is silent (caller falls back to the platform default)."""
    if not op:
        return None
    rec = _ladder().get(backend_platform(), {}).get(op)
    if not rec:
        return None
    try:
        return float(rec["pallas_us"]) <= float(rec["xla_us"])
    except (KeyError, TypeError, ValueError):
        return None


def use_pallas(op: str | None = None) -> bool:
    """Kernel eligibility for the *unsharded* (single-device) call form.

    Under SPMD the per-op dispatchers instead consult :func:`spmd_mesh` and
    route through the shard_map-wrapped kernel entry points; a bare kernel
    would not partition, so this returns False while a mesh without a
    sharded wrapper is active.

    ``op`` names the caller's ladder family (e.g. ``"ragged_attn"``): the
    auto policy then picks whichever backend the recorded microbench
    ladder measured faster for that op on this platform, instead of a
    blanket per-platform rule.  Env overrides still win.
    """
    if _spmd_active:
        return False
    return _use_pallas_env(op)


@lru_cache(maxsize=None)
def _use_pallas_env(op: str | None = None) -> bool:
    if os.environ.get("IPEX_LLM_TPU_DISABLE_PALLAS", "0") == "1":
        return False
    if os.environ.get("IPEX_LLM_TPU_FORCE_PALLAS", "0") == "1":
        return True  # kernel testing: interpret-mode Pallas off-TPU
    measured = ladder_prefers_pallas(op)
    if measured is not None:
        return measured
    # ladder silent for this op: platform default — only real TPU
    # backends run the Pallas kernels (on CPU they would execute in the
    # interpreter, which the ladder's recorded rows all measure slower
    # than the XLA reference path)
    return backend_platform() == "tpu"


def use_pallas_sharded(op: str | None = None) -> bool:
    """Kernel eligibility for shard_map-wrapped entry points."""
    return _use_pallas_env(op)


def ladder_provenance() -> dict:
    """The /health ``dispatch`` block: where every Pallas-vs-XLA auto
    decision on THIS platform comes from and when it was measured.

    Per op family: the recorded pair, the winner the pair selects, and
    the ``recorded`` bench-round stamp (builtin rows carry the round that
    measured them — BENCH_r05/r06/r12 as of this writing; an
    ``IPEX_LLM_TPU_DISPATCH_LADDER`` override is stamped from the dump's
    own round field or its file mtime).  ``recorded: "unstamped"`` means
    a hand-edited table with no provenance at all — the loudest kind of
    stale."""
    platform = backend_platform()
    table = _ladder().get(platform, {})
    fams = {}
    for fam, rec in sorted(table.items()):
        try:
            prefers = ("pallas" if float(rec["pallas_us"])
                       <= float(rec["xla_us"]) else "xla")
        except (KeyError, TypeError, ValueError):
            prefers = None
        fams[fam] = {
            "pallas_us": rec.get("pallas_us"),
            "xla_us": rec.get("xla_us"),
            "prefers": prefers,
            "recorded": rec.get("recorded", "unstamped"),
        }
    return {
        "platform": platform,
        "source": (os.environ.get("IPEX_LLM_TPU_DISPATCH_LADDER")
                   or "builtin"),
        "default": "pallas" if platform == "tpu" else "xla",
        "families": fams,
    }


def clear_cache() -> None:
    _use_pallas_env.cache_clear()
    _ladder.cache_clear()
