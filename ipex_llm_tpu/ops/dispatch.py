"""Backend dispatch for the op library.

Every hot op has two implementations:
  - a Pallas TPU kernel (the ``xe_linear``/``xe_addons`` equivalent, §2.3), and
  - a pure-jnp XLA reference (the reference's CPU-fallback pattern,
    models/common.py:289-306), which doubles as the test oracle.

Selection is per-process: Pallas on TPU backends, jnp elsewhere, overridable
with IPEX_LLM_TPU_DISABLE_PALLAS=1 and, for interpret-mode kernel tests off a
TPU, IPEX_LLM_TPU_FORCE_PALLAS=1 (mirrors the reference's env-flag style,
SURVEY.md §5 config system).
"""

from __future__ import annotations

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


def use_pallas() -> bool:
    """Kernel eligibility for the *unsharded* (single-device) call form.

    Under SPMD the per-op dispatchers instead consult :func:`spmd_mesh` and
    route through the shard_map-wrapped kernel entry points; a bare kernel
    would not partition, so this returns False while a mesh without a
    sharded wrapper is active.
    """
    if _spmd_active:
        return False
    return _use_pallas_env()


@lru_cache(maxsize=None)
def _use_pallas_env() -> bool:
    if os.environ.get("IPEX_LLM_TPU_DISABLE_PALLAS", "0") == "1":
        return False
    if os.environ.get("IPEX_LLM_TPU_FORCE_PALLAS", "0") == "1":
        return True  # kernel testing: interpret-mode Pallas off-TPU
    # only real TPU backends run the Pallas kernels (on CPU they would
    # execute in the interpreter)
    return jax.default_backend() == "tpu"


def use_pallas_sharded() -> bool:
    """Kernel eligibility for shard_map-wrapped entry points."""
    return _use_pallas_env()


def clear_cache() -> None:
    _use_pallas_env.cache_clear()
