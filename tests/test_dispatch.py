"""The kernel choice (ops/dispatch.py): one function that reads the
platform, two variables that tests set, and the module-level seam
``perfbench/rehearse_memory.force_chip_dispatch`` steers it through."""

from contextlib import nullcontext

import jax
import pytest
from jax.sharding import Mesh

from ipex_llm_tpu.ops import dispatch

FORCE = {"IPEX_LLM_TPU_FORCE_PALLAS": "1"}
DISABLE = {"IPEX_LLM_TPU_DISABLE_PALLAS": "1"}


def _tp_mesh():
    return Mesh(jax.devices()[:2], ("tp",))


@pytest.mark.parametrize("env, scope, single, sharded", [
    # off a TPU the XLA path runs, not interpret-mode Pallas
    ({}, nullcontext, False, False),
    (FORCE, nullcontext, True, True),       # the tests' interpret switch
    (DISABLE, nullcontext, False, False),
    ({**FORCE, **DISABLE}, nullcontext, False, False),
    # under a mesh a bare kernel would not partition: only the shard_map
    # wrappers follow the variables
    ({}, lambda: dispatch.spmd(True), False, False),
    (FORCE, lambda: dispatch.spmd(True), False, True),
    (FORCE, lambda: dispatch.spmd(_tp_mesh()), False, True),
    # inside the manual tick the trace is single-device: as outside
    ({}, lambda: dispatch.manual_tp("tp"), False, False),
    (FORCE, lambda: dispatch.manual_tp("tp"), True, True),
])
def test_use_pallas_reads_platform_and_variables(monkeypatch, env, scope,
                                                 single, sharded):
    assert jax.default_backend() == "cpu"
    for name in (*FORCE, *DISABLE):
        monkeypatch.delenv(name, raising=False)
    for name, value in env.items():
        monkeypatch.setenv(name, value)
    dispatch.clear_cache()
    try:
        with scope():
            assert dispatch.use_pallas() is single
            assert dispatch.use_pallas_sharded() is sharded
    finally:
        dispatch.clear_cache()


def test_benchmark_rehearsal_seam_forces_both_entries(monkeypatch):
    """What ``perfbench/rehearse_memory.force_chip_dispatch`` does: it
    assigns the module-level ``_use_pallas_env`` and expects both entries to
    look it up at call time."""
    assert hasattr(dispatch, "use_pallas")
    for name in (*FORCE, *DISABLE):
        monkeypatch.delenv(name, raising=False)
    kept = dispatch._use_pallas_env
    dispatch.clear_cache()
    assert dispatch.use_pallas() is False
    dispatch._use_pallas_env = lambda op=None: True
    try:
        assert dispatch.use_pallas() is True
        assert dispatch.use_pallas_sharded() is True
    finally:
        dispatch._use_pallas_env = kept
        dispatch.clear_cache()
