"""ctypes loader + builder for the C++ block quantizer (csrc/quantize.cpp)."""

from __future__ import annotations

import ctypes
import hashlib
import os
import platform
import subprocess
import threading

import numpy as np

_LOCK = threading.Lock()
_LIB: ctypes.CDLL | None | bool = None  # None=untried, False=unavailable

_SRC = os.path.join(os.path.dirname(__file__), "..", "..", "csrc",
                    "quantize.cpp")


class NativeBuildError(RuntimeError):
    """The compiler is missing or refused csrc/quantize.cpp."""


def _cpu_identity() -> str:
    """What ``-march=native`` specializes the library for: the machine type
    and this CPU's feature flags."""
    ident = platform.machine()
    try:
        with open("/proc/cpuinfo", encoding="utf-8") as f:
            for line in f:
                if line.startswith(("flags", "Features")):
                    ident += line
                    break
    except OSError:
        pass
    return ident


def _out_path(src: str) -> str:
    """The library's path carries a digest of the source and of the host
    CPU: the tree is copied between machines with whatever it holds, and a
    ``-march=native`` build for another CPU (or an older source) must never
    be the one loaded."""
    with open(src, "rb") as f:
        digest = hashlib.sha256(f.read() + _cpu_identity().encode())
    return os.path.join(os.path.dirname(__file__),
                        f"_libquantize-{digest.hexdigest()[:16]}.so")


def build(force: bool = False) -> str:
    """Compile the shared library (g++ -O3 -march=native -fopenmp) unless
    this host already built this source; raises NativeBuildError."""
    src = os.path.abspath(_SRC)
    if not os.path.exists(src):
        raise NativeBuildError(f"{src} is missing")
    out = _out_path(src)
    if os.path.exists(out) and not force:
        return out
    tmp = f"{out}.{os.getpid()}.tmp"   # concurrent builders never see half
    cmd = ["g++", "-O3", "-march=native", "-fopenmp", "-shared", "-fPIC",
           src, "-o", tmp]
    try:
        subprocess.run(cmd, check=True, capture_output=True, timeout=120)
        os.replace(tmp, out)
    except FileNotFoundError as e:
        raise NativeBuildError("g++ not found") from e
    except subprocess.CalledProcessError as e:
        raise NativeBuildError(
            f"g++ failed: {e.stderr.decode(errors='replace')}") from e
    except subprocess.TimeoutExpired as e:
        raise NativeBuildError("g++ timed out") from e
    finally:
        if os.path.exists(tmp):
            os.remove(tmp)
    return out


def _load() -> ctypes.CDLL | None:
    global _LIB
    with _LOCK:
        if _LIB is None:
            try:
                path = build()
            except NativeBuildError:
                _LIB = False   # callers fall back to the jnp codec
            else:
                try:
                    lib = ctypes.CDLL(path)
                    lib.quantize_sym.restype = ctypes.c_int
                    lib.quantize_sym.argtypes = [
                        ctypes.POINTER(ctypes.c_float),
                        ctypes.c_int64, ctypes.c_int64,
                        ctypes.c_int, ctypes.c_int,
                        ctypes.POINTER(ctypes.c_uint8),
                        ctypes.POINTER(ctypes.c_uint16),
                    ]
                    lib.dequantize_sym.restype = ctypes.c_int
                    lib.dequantize_sym.argtypes = [
                        ctypes.POINTER(ctypes.c_uint8),
                        ctypes.POINTER(ctypes.c_uint16),
                        ctypes.c_int64, ctypes.c_int64,
                        ctypes.c_int, ctypes.c_int,
                        ctypes.POINTER(ctypes.c_float),
                    ]
                    lib.quantize_asym.restype = ctypes.c_int
                    lib.quantize_asym.argtypes = [
                        ctypes.POINTER(ctypes.c_float),
                        ctypes.c_int64, ctypes.c_int64,
                        ctypes.c_int, ctypes.c_int,
                        ctypes.POINTER(ctypes.c_uint8),
                        ctypes.POINTER(ctypes.c_uint16),
                        ctypes.POINTER(ctypes.c_uint16),
                    ]
                    lib.quantize_codebook.restype = ctypes.c_int
                    lib.quantize_codebook.argtypes = [
                        ctypes.POINTER(ctypes.c_float),
                        ctypes.c_int64, ctypes.c_int64, ctypes.c_int,
                        ctypes.POINTER(ctypes.c_float), ctypes.c_int,
                        ctypes.POINTER(ctypes.c_uint8),
                        ctypes.POINTER(ctypes.c_uint16),
                    ]
                    _LIB = lib
                except OSError:
                    _LIB = False
        return _LIB or None


def available() -> bool:
    if os.environ.get("IPEX_LLM_TPU_DISABLE_NATIVE", "0") == "1":
        return False
    return _load() is not None


def quantize_sym_native(w: np.ndarray, bits: int, bs: int):
    """Bit-exact native counterpart of core._quant_int_sym for fp32 numpy
    input.  Returns (data uint8, scales float16) or None when unavailable."""
    lib = _load()
    if lib is None:
        return None
    w = np.ascontiguousarray(w, np.float32)
    n_in, n_out = w.shape
    pad = (-n_in) % bs
    if pad:
        w = np.concatenate([w, np.zeros((pad, n_out), np.float32)], axis=0)
        n_in += pad
    n_blocks = n_in // bs
    data_rows = n_in // 2 if bits == 4 else n_in
    data = np.empty((data_rows, n_out), np.uint8)
    scales = np.empty((n_blocks, n_out), np.uint16)
    rc = lib.quantize_sym(
        w.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
        n_in, n_out, bs, bits,
        data.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)),
        scales.ctypes.data_as(ctypes.POINTER(ctypes.c_uint16)),
    )
    if rc != 0:
        return None
    return data, scales.view(np.float16)


def quantize_asym_native(w: np.ndarray, bits: int, bs: int):
    """Bit-exact native counterpart of core._quant_int_asym (q4_1/q5_1
    style).  Returns (data uint8, scales f16, zeros f16) or None."""
    lib = _load()
    if lib is None:
        return None
    w = np.ascontiguousarray(w, np.float32)
    n_in, n_out = w.shape
    pad = (-n_in) % bs
    if pad:
        w = np.concatenate([w, np.zeros((pad, n_out), np.float32)], axis=0)
        n_in += pad
    n_blocks = n_in // bs
    data_rows = n_in // 2 if bits == 4 else n_in
    data = np.empty((data_rows, n_out), np.uint8)
    scales = np.empty((n_blocks, n_out), np.uint16)
    zeros = np.empty((n_blocks, n_out), np.uint16)
    rc = lib.quantize_asym(
        w.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
        n_in, n_out, bs, bits,
        data.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)),
        scales.ctypes.data_as(ctypes.POINTER(ctypes.c_uint16)),
        zeros.ctypes.data_as(ctypes.POINTER(ctypes.c_uint16)),
    )
    if rc != 0:
        return None
    return data, scales.view(np.float16), zeros.view(np.float16)


def quantize_codebook_native(w: np.ndarray, table: np.ndarray, bs: int):
    """Bit-exact native counterpart of core._quant_codebook for 16-entry
    codebooks (nf4/fp4).  Returns (data uint8 nibbles, scales f16) or
    None."""
    lib = _load()
    if lib is None or len(table) > 16 or bs > 512:
        return None
    w = np.ascontiguousarray(w, np.float32)
    n_in, n_out = w.shape
    pad = (-n_in) % bs
    if pad:
        w = np.concatenate([w, np.zeros((pad, n_out), np.float32)], axis=0)
        n_in += pad
    n_blocks = n_in // bs
    t = np.ascontiguousarray(table, np.float32)
    data = np.empty((n_in // 2, n_out), np.uint8)
    scales = np.empty((n_blocks, n_out), np.uint16)
    rc = lib.quantize_codebook(
        w.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
        n_in, n_out, bs,
        t.ctypes.data_as(ctypes.POINTER(ctypes.c_float)), len(t),
        data.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)),
        scales.ctypes.data_as(ctypes.POINTER(ctypes.c_uint16)),
    )
    if rc != 0:
        return None
    return data, scales.view(np.float16)
