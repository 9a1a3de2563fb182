"""k-quant decoder tests.

Oracle: a literal, loop-by-loop scalar transcription of the *published* GGUF
superblock format spec (how llama.cpp documents dequantization), compared
against the vectorized jnp decoders in ipex_llm_tpu/quantize/kquants.py on
random block bytes.  Catches any vectorization/layout mistake.
"""

import numpy as np
import pytest

from ipex_llm_tpu.quantize.core import QTensor
from ipex_llm_tpu.quantize import kquants

RNG = np.random.default_rng(7)


def _f16(b: bytes) -> float:
    return float(np.frombuffer(b, dtype=np.float16)[0])


def _scale_min_k4(j, scales):
    if j < 4:
        return scales[j] & 63, scales[j + 4] & 63
    sc = (scales[j + 4] & 0x0F) | ((scales[j - 4] >> 6) << 4)
    m = (scales[j + 4] >> 4) | ((scales[j] >> 6) << 4)
    return sc, m


def scalar_q4_k(raw: np.ndarray) -> np.ndarray:
    d = _f16(raw[0:2].tobytes())
    dmin = _f16(raw[2:4].tobytes())
    scales = raw[4:16]
    qs = raw[16:144]
    y = np.zeros(256, np.float32)
    yi = 0
    for j in range(0, 256, 64):
        q = qs[(j // 64) * 32 : (j // 64) * 32 + 32]
        sc, m = _scale_min_k4(2 * (j // 64), scales)
        for l in range(32):
            y[yi] = d * sc * (q[l] & 0xF) - dmin * m
            yi += 1
        sc, m = _scale_min_k4(2 * (j // 64) + 1, scales)
        for l in range(32):
            y[yi] = d * sc * (q[l] >> 4) - dmin * m
            yi += 1
    return y


def scalar_q5_k(raw: np.ndarray) -> np.ndarray:
    d = _f16(raw[0:2].tobytes())
    dmin = _f16(raw[2:4].tobytes())
    scales = raw[4:16]
    qh = raw[16:48]
    ql = raw[48:176]
    y = np.zeros(256, np.float32)
    yi = 0
    u1, u2 = 1, 2
    is_ = 0
    qoff = 0
    for j in range(0, 256, 64):
        sc1, m1 = _scale_min_k4(is_, scales)
        sc2, m2 = _scale_min_k4(is_ + 1, scales)
        for l in range(32):
            y[yi] = d * sc1 * ((ql[qoff + l] & 0xF) + (16 if qh[l] & u1 else 0)) - dmin * m1
            yi += 1
        for l in range(32):
            y[yi] = d * sc2 * ((ql[qoff + l] >> 4) + (16 if qh[l] & u2 else 0)) - dmin * m2
            yi += 1
        qoff += 32
        is_ += 2
        u1 <<= 2
        u2 <<= 2
    return y


def scalar_q6_k(raw: np.ndarray) -> np.ndarray:
    ql = raw[0:128]
    qh = raw[128:192]
    sc = raw[192:208].astype(np.int8).astype(np.int32)
    d = _f16(raw[208:210].tobytes())
    y = np.zeros(256, np.float32)
    for n in range(2):
        yo = 128 * n
        lo = 64 * n
        ho = 32 * n
        so = 8 * n
        for l in range(32):
            is_ = l // 16
            q1 = int((ql[lo + l] & 0xF) | (((qh[ho + l] >> 0) & 3) << 4))
            q2 = int((ql[lo + l + 32] & 0xF) | (((qh[ho + l] >> 2) & 3) << 4))
            q3 = int((ql[lo + l] >> 4) | (((qh[ho + l] >> 4) & 3) << 4))
            q4 = int((ql[lo + l + 32] >> 4) | (((qh[ho + l] >> 6) & 3) << 4))
            y[yo + l] = d * sc[so + is_] * (q1 - 32)
            y[yo + l + 32] = d * sc[so + is_ + 2] * (q2 - 32)
            y[yo + l + 64] = d * sc[so + is_ + 4] * (q3 - 32)
            y[yo + l + 96] = d * sc[so + is_ + 6] * (q4 - 32)
    return y


def scalar_q2_k(raw: np.ndarray) -> np.ndarray:
    scales = raw[0:16]
    qs = raw[16:80]
    d = _f16(raw[80:82].tobytes())
    dmin = _f16(raw[82:84].tobytes())
    y = np.zeros(256, np.float32)
    yi = 0
    is_ = 0
    qoff = 0
    for n in range(0, 256, 128):
        shift = 0
        for j in range(4):
            sc = scales[is_]
            is_ += 1
            for l in range(16):
                y[yi] = d * (sc & 0xF) * ((qs[qoff + l] >> shift) & 3) - dmin * (sc >> 4)
                yi += 1
            sc = scales[is_]
            is_ += 1
            for l in range(16, 32):
                y[yi] = d * (sc & 0xF) * ((qs[qoff + l] >> shift) & 3) - dmin * (sc >> 4)
                yi += 1
            shift += 2
        qoff += 32
    return y


def scalar_q3_k(raw: np.ndarray) -> np.ndarray:
    hmask = raw[0:32]
    qs = raw[32:96]
    scales_b = raw[96:108]
    d = _f16(raw[108:110].tobytes())
    scales = np.zeros(16, np.int32)
    for j in range(16):
        low4 = (scales_b[j] & 0x0F) if j < 8 else (scales_b[j - 8] >> 4)
        high2 = (scales_b[8 + j % 4] >> (2 * (j // 4))) & 3
        scales[j] = int(low4 | (high2 << 4)) - 32
    y = np.zeros(256, np.float32)
    yi = 0
    is_ = 0
    m = 1
    qoff = 0
    for n in range(0, 256, 128):
        shift = 0
        for j in range(4):
            dl = d * scales[is_]
            is_ += 1
            for l in range(16):
                q = int((qs[qoff + l] >> shift) & 3)
                y[yi] = dl * (q - (0 if hmask[l] & m else 4))
                yi += 1
            dl = d * scales[is_]
            is_ += 1
            for l in range(16, 32):
                q = int((qs[qoff + l] >> shift) & 3)
                y[yi] = dl * (q - (0 if hmask[l] & m else 4))
                yi += 1
            shift += 2
            m <<= 1
        qoff += 32
    return y


def scalar_q8_k(raw: np.ndarray) -> np.ndarray:
    d = float(np.frombuffer(raw[0:4].tobytes(), dtype=np.float32)[0])
    qs = raw[4:260].astype(np.int8).astype(np.float32)
    return d * qs


SCALAR = {
    "q2_k": scalar_q2_k,
    "q3_k": scalar_q3_k,
    "q4_k": scalar_q4_k,
    "q5_k": scalar_q5_k,
    "q6_k": scalar_q6_k,
    "q8_k": scalar_q8_k,
}


def _random_raw(qtype: str, n_super: int) -> np.ndarray:
    ts = kquants.TYPE_SIZES[qtype]
    raw = RNG.integers(0, 256, size=(n_super, ts), dtype=np.uint8)
    # keep the fp16 d/dmin fields finite and small: overwrite with benign values
    offs = {"q2_k": [80, 82], "q3_k": [108], "q4_k": [0, 2], "q5_k": [0, 2], "q6_k": [208]}
    for i in range(n_super):
        if qtype == "q8_k":
            raw[i, 0:4] = np.frombuffer(
                np.float32(RNG.uniform(0.001, 0.1)).tobytes(), np.uint8
            )
        else:
            for off in offs[qtype]:
                raw[i, off : off + 2] = np.frombuffer(
                    np.float16(RNG.uniform(0.001, 0.1)).tobytes(), np.uint8
                )
    return raw


@pytest.mark.parametrize("qtype", sorted(SCALAR))
def test_kquant_matches_scalar_spec(qtype):
    n_out, nb = 3, 2  # 3 output rows, 2 superblocks each -> in=512
    raw = np.stack([_random_raw(qtype, nb) for _ in range(n_out)])  # [out, nb, ts]
    expected = np.stack(
        [np.concatenate([SCALAR[qtype](raw[o, b]) for b in range(nb)]) for o in range(n_out)]
    )  # [out, in]
    qt = QTensor(
        data=raw.reshape(n_out, -1),
        scale_bits=None,
        zeros=None,
        qtype=qtype,
        shape=(nb * 256, n_out),
        block_size=256,
    )
    got = np.asarray(kquants.dequantize(qt))  # [in, out]
    np.testing.assert_allclose(got, expected.T, rtol=1e-4, atol=1e-4)
