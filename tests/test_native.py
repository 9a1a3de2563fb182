"""Native C++ quantizer: must be bit-exact with the jnp codec (its oracle)."""

import numpy as np
import pytest

from ipex_llm_tpu.native import quantizer as nq
from ipex_llm_tpu.quantize import core as qcore

pytestmark = pytest.mark.skipif(
    not nq.available(), reason="native quantizer did not build"
)

RNG = np.random.default_rng(55)


@pytest.mark.parametrize("bits,qtype", [(4, "sym_int4"), (8, "sym_int8")])
@pytest.mark.parametrize("shape", [(64, 48), (100, 33), (256, 128)])
def test_native_bit_exact(bits, qtype, shape, monkeypatch):
    w = (RNG.standard_normal(shape) * 0.5).astype(np.float32)
    # jnp oracle (force the pure path)
    monkeypatch.setenv("IPEX_LLM_TPU_DISABLE_NATIVE", "1")
    ref = qcore.quantize(w, qtype)
    monkeypatch.delenv("IPEX_LLM_TPU_DISABLE_NATIVE")

    info_bs = ref.block_size
    out = nq.quantize_sym_native(w, bits, info_bs)
    assert out is not None
    data, scales = out
    np.testing.assert_array_equal(np.asarray(ref.data), data)
    np.testing.assert_array_equal(
        np.asarray(ref.scale_bits), scales.view(np.uint16)
    )


def test_core_dispatches_to_native():
    w = RNG.standard_normal((64, 32)).astype(np.float32)
    qt = qcore.quantize(w, "sym_int4")  # goes through the native path
    deq = np.asarray(qcore.dequantize(qt))
    # reconstruction sanity
    assert np.abs(deq - w).max() < np.abs(w).max() * 0.2


def test_native_speedup_on_large_matrix():
    """The point of the C++ path: quantize-on-load throughput."""
    import time

    w = RNG.standard_normal((4096, 4096)).astype(np.float32)
    t0 = time.perf_counter()
    out = nq.quantize_sym_native(w, 4, 64)
    native_s = time.perf_counter() - t0
    assert out is not None
    assert native_s < 5.0  # 16M weights well under seconds


@pytest.mark.parametrize("qtype", ["asym_int4"])
def test_native_asym_matches_jnp(qtype, monkeypatch):
    """quantize_asym planes (data, f16 scales, f16 zeros) bit-equal the jnp
    codec's."""
    w = RNG.standard_normal((96, 24)).astype(np.float32) * 0.4
    monkeypatch.setenv("IPEX_LLM_TPU_DISABLE_NATIVE", "1")
    ref = qcore.quantize(w, qtype)
    monkeypatch.delenv("IPEX_LLM_TPU_DISABLE_NATIVE")
    bits = 4
    out = nq.quantize_asym_native(w, bits, ref.block_size)
    assert out is not None
    data, scales, zeros = out
    np.testing.assert_array_equal(np.asarray(ref.data), data)
    np.testing.assert_array_equal(
        np.asarray(ref.scale_bits), scales.view(np.uint16))
    np.testing.assert_array_equal(
        np.asarray(ref.zeros).view(np.uint16), zeros.view(np.uint16))


@pytest.mark.parametrize("qtype", ["nf4", "fp4"])
def test_native_codebook_matches_jnp(qtype, monkeypatch):
    """quantize_codebook nibbles + f16 scales bit-equal the jnp codec's
    (first-minimum tie-break included)."""
    from ipex_llm_tpu.quantize.core import _codebook_table

    w = RNG.standard_normal((64, 16)).astype(np.float32) * 0.3
    monkeypatch.setenv("IPEX_LLM_TPU_DISABLE_NATIVE", "1")
    ref = qcore.quantize(w, qtype)
    monkeypatch.delenv("IPEX_LLM_TPU_DISABLE_NATIVE")
    out = nq.quantize_codebook_native(w, _codebook_table(qtype),
                                      ref.block_size)
    assert out is not None
    data, scales = out
    np.testing.assert_array_equal(np.asarray(ref.data), data)
    np.testing.assert_array_equal(
        np.asarray(ref.scale_bits), scales.view(np.uint16))


def test_core_dispatches_asym_and_codebook_to_native():
    for q in ("asym_int4", "nf4", "fp4", "asym_int5"):
        w = RNG.standard_normal((64, 32)).astype(np.float32)
        qt = qcore.quantize(w, q)
        deq = np.asarray(qcore.dequantize(qt))
        assert np.abs(deq - w).max() < np.abs(w).max() * 0.5, q
