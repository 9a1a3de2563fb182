"""Op-library tests (SDPA masking, RoPE, sampling, qmatmul oracle)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ipex_llm_tpu.ops import (
    SamplingParams,
    apply_rope,
    cos_sin,
    qmatmul_reference,
    sample,
    sdpa_reference,
)
from ipex_llm_tpu.ops.rope import RopeScaling
from ipex_llm_tpu.quantize import quantize

RNG = np.random.default_rng(3)


def _naive_attn(q, k, v, mask):
    """[B,T,H,D]x[B,S,H,D] with explicit bool mask [B,T,S] (True=keep)."""
    scores = np.einsum("bthd,bshd->bhts", q, k) / np.sqrt(q.shape[-1])
    scores = np.where(mask[:, None], scores, -1e30)
    e = np.exp(scores - scores.max(-1, keepdims=True))
    p = e / e.sum(-1, keepdims=True)
    return np.einsum("bhts,bshd->bthd", p, v)


def test_sdpa_causal_matches_naive():
    b, t, h, d = 2, 8, 4, 16
    q = RNG.standard_normal((b, t, h, d)).astype(np.float32)
    k = RNG.standard_normal((b, t, h, d)).astype(np.float32)
    v = RNG.standard_normal((b, t, h, d)).astype(np.float32)
    mask = np.tril(np.ones((t, t), bool))[None].repeat(b, 0)
    want = _naive_attn(q, k, v, mask)
    got = np.asarray(sdpa_reference(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v)))
    np.testing.assert_allclose(got, want, atol=1e-4)


def test_sdpa_gqa_and_kv_len():
    """GQA (Hq=4, Hkv=2) + kv_len masking == naive over the valid prefix."""
    b, t, s, hq, hkv, d = 1, 4, 12, 4, 2, 8
    q = RNG.standard_normal((b, t, hq, d)).astype(np.float32)
    k = RNG.standard_normal((b, s, hkv, d)).astype(np.float32)
    v = RNG.standard_normal((b, s, hkv, d)).astype(np.float32)
    kv_len = np.array([9], np.int32)
    q_pos = np.arange(5, 9)[None]  # decode continuing at slots 5..8
    got = np.asarray(
        sdpa_reference(
            jnp.asarray(q), jnp.asarray(k), jnp.asarray(v),
            q_positions=jnp.asarray(q_pos), kv_len=jnp.asarray(kv_len),
        )
    )
    krep = k.repeat(2, axis=2)
    vrep = v.repeat(2, axis=2)
    kv_pos = np.arange(s)
    mask = (kv_pos[None, None, :] <= q_pos[:, :, None]) & (kv_pos < 9)[None, None, :]
    want = _naive_attn(q, krep, vrep, mask)
    np.testing.assert_allclose(got, want, atol=1e-4)


def test_sdpa_sliding_window():
    b, t, h, d, w = 1, 10, 2, 8, 4
    q = RNG.standard_normal((b, t, h, d)).astype(np.float32)
    k = RNG.standard_normal((b, t, h, d)).astype(np.float32)
    v = RNG.standard_normal((b, t, h, d)).astype(np.float32)
    got = np.asarray(
        sdpa_reference(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v), window=w)
    )
    qp = np.arange(t)
    kp = np.arange(t)
    mask = (kp[None, :] <= qp[:, None]) & (kp[None, :] > qp[:, None] - w)
    want = _naive_attn(q, k, v, mask[None])
    np.testing.assert_allclose(got, want, atol=1e-4)
    # window_on=False must fall back to full causal
    got_off = np.asarray(
        sdpa_reference(
            jnp.asarray(q), jnp.asarray(k), jnp.asarray(v), window=w,
            window_on=jnp.asarray(False),
        )
    )
    full = np.asarray(sdpa_reference(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v)))
    np.testing.assert_allclose(got_off, full, atol=1e-6)


def test_rope_half_matches_hf_formula():
    """rotate_half convention: out = x*cos + rotate_half(x)*sin."""
    b, t, h, d = 1, 6, 2, 16
    x = RNG.standard_normal((b, t, h, d)).astype(np.float32)
    rs = RopeScaling(head_dim=d, base=10000.0)
    inv = rs.inv_freq()
    pos = np.arange(t)[None]
    cos, sin = cos_sin(jnp.asarray(pos), jnp.asarray(inv))
    got = np.asarray(apply_rope(jnp.asarray(x), cos, sin, "half"))

    angles = pos[..., None] * inv  # [1, T, D/2]
    c = np.concatenate([np.cos(angles)] * 2, -1)[:, :, None, :]
    s = np.concatenate([np.sin(angles)] * 2, -1)[:, :, None, :]
    rot = np.concatenate([-x[..., d // 2:], x[..., : d // 2]], -1)
    want = x * c + rot * s
    np.testing.assert_allclose(got, want, atol=1e-5)


def test_llama3_rope_scaling_shape():
    rs = RopeScaling(
        head_dim=128, base=500000.0, kind="llama3", factor=8.0,
        low_freq_factor=1.0, high_freq_factor=4.0, original_max_position=8192,
    )
    inv = rs.inv_freq()
    base = RopeScaling(head_dim=128, base=500000.0).inv_freq()
    assert inv.shape == (64,)
    # low frequencies (long wavelengths) get divided by factor, high kept
    assert np.isclose(inv[0], base[0])
    assert np.isclose(inv[-1], base[-1] / 8.0)


def test_greedy_sampling_and_penalty():
    logits = jnp.asarray(np.array([[0.0, 2.0, 1.0], [3.0, 0.0, -1.0]], np.float32))
    tok = sample(logits, jax.random.PRNGKey(0), SamplingParams())
    np.testing.assert_array_equal(np.asarray(tok), [1, 0])
    prev = jnp.asarray(np.array([[1, -1], [2, -1]], np.int32))
    tok2 = sample(
        logits, jax.random.PRNGKey(0),
        SamplingParams(repetition_penalty=100.0), prev_tokens=prev,
    )
    np.testing.assert_array_equal(np.asarray(tok2), [2, 0])


def test_topk_topp_restrict_support():
    logits = jnp.asarray(
        np.log(np.array([[0.5, 0.3, 0.15, 0.05]], np.float32))
    )
    counts = np.zeros(4, int)
    for i in range(50):
        t = sample(
            logits, jax.random.PRNGKey(i),
            SamplingParams(do_sample=True, top_k=2),
        )
        counts[int(t[0])] += 1
    assert counts[2:].sum() == 0 and counts[:2].sum() == 50
    counts = np.zeros(4, int)
    for i in range(50):
        t = sample(
            logits, jax.random.PRNGKey(i),
            SamplingParams(do_sample=True, top_p=0.6),
        )
        counts[int(t[0])] += 1
    assert counts[2:].sum() == 0


@pytest.mark.parametrize("qtype", ["sym_int4", "sym_int8", "nf4", "fp8_e4m3"])
def test_qmatmul_reference_accuracy(qtype):
    x = RNG.standard_normal((2, 64)).astype(np.float32) * 0.1
    w = RNG.standard_normal((64, 32)).astype(np.float32) * 0.1
    qt = quantize(w, qtype)
    got = np.asarray(qmatmul_reference(jnp.asarray(x), qt))
    want = x @ w
    denom = np.sqrt(np.mean(want**2)) + 1e-9
    rel = np.sqrt(np.mean((got - want) ** 2)) / denom
    assert rel < 0.2, f"{qtype} rel err {rel}"
