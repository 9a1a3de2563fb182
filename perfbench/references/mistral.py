"""The plain reference for ``model_type: mistral``: the published Mistral
decoder block (Jiang et al. 2023, and transformers' ``modeling_mistral.py``)
in float32 ``jax.numpy``, matmul precision "highest", no kernels, no cache,
no batching.  It is independent of the program: it reads the HF config keys
itself, unpacks and dequantizes the served int4 planes by its own code one
layer at a time (a Mistral-7B layer in float32 is 0.87 GB, so it fits beside
the loaded engine), and runs the full causal forward over prompt + emitted
tokens.

Departures from the published model: none in the mathematics.  The weights
are the SERVED ones (block-quantized, so the reference checks the serving
arithmetic, not the quantizer), the embedding table is bf16 as served.

Plane layout it assumes (``quantize/core.py`` docstrings; a mismatch shows as
``correct: false``): a weight is ``[in, out]``; ``scales`` is ``[in/32,
out]`` fp16; ``data`` is ``[in/2, out]`` uint8 where, inside each block of 32
input rows, byte j holds row j in its low nibble and row j + 16 in its high
nibble; a code c stands for (c - 8) * scale.
"""

from __future__ import annotations

import functools

BLOCK = 32

# --- tolerance -------------------------------------------------------------
# The server computes with bf16 activations (8 significant bits) and float32
# accumulation, as the configuration states; the reference keeps float32
# throughout.  Measured on the chip at the published widths (my chip runs,
# PR 24; two probes of 8 positions a run): the chosen token's
# log-probability differs from the reference's by at most 0.141 (Mistral-7B,
# 4 probes), and the chosen token's reference logit trails the reference's
# largest by at most 0.060.  The limits are about three times the largest
# seen.  An 8-bit float for activations or the KV cache (2-3 mantissa bits:
# rounding 16-32 times coarser than bf16's) scales the same differences past
# 1, and a wrong mask, rope pairing, head mapping or nibble order gives
# differences of order 1-10, so a run that computed in a lower precision
# than the configuration states fails.
LOGPROB_TOL = 0.4
ARGMAX_MARGIN_TOL = 0.4


def dequantize(data, scales):
    """[in/2, out] uint8 + [in/32, out] fp16 -> [in, out] float32."""
    import jax.numpy as jnp

    half = BLOCK // 2
    nb, out = scales.shape
    p = data.reshape(nb, half, out)
    codes = jnp.concatenate([p & 0x0F, p >> 4], axis=1)       # [nb, 32, out]
    w = (codes.astype(jnp.float32) - 8.0) * scales.astype(
        jnp.float32)[:, None, :]
    return w.reshape(nb * BLOCK, out)


def _rms_norm(x, w, eps):
    import jax
    import jax.numpy as jnp

    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _rope(x, theta):
    """Rotary embedding, the published pairing (x[i], x[i + D/2]).
    x: [T, H, D]."""
    import jax.numpy as jnp

    t, _, d = x.shape
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv[None]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


@functools.lru_cache(maxsize=4)
def _forward_fn(n_q: int, n_kv: int, d: int, eps: float, theta: float):
    """The whole forward as ONE jitted call (so that set-up pays one cached
    compile and one dispatch): a scan over the stacked layers, which
    dequantizes one layer's planes at a time."""
    import jax
    import jax.numpy as jnp

    def layer(x, lp):
        t = x.shape[0]
        qkv = _rms_norm(x, lp["attn_norm"], eps) @ dequantize(*lp["qkv"])
        q = _rope(qkv[:, : n_q * d].reshape(t, n_q, d), theta)
        k = _rope(qkv[:, n_q * d: (n_q + n_kv) * d].reshape(t, n_kv, d), theta)
        v = qkv[:, (n_q + n_kv) * d:].reshape(t, n_kv, d)
        k = jnp.repeat(k, n_q // n_kv, axis=1)
        v = jnp.repeat(v, n_q // n_kv, axis=1)
        s = jnp.einsum("qhd,khd->hqk", q, k) / jnp.sqrt(jnp.float32(d))
        causal = jnp.tril(jnp.ones((t, t), bool))
        s = jnp.where(causal[None], s, -jnp.inf)
        a = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, -1), v)
        x = x + a.reshape(t, n_q * d) @ dequantize(*lp["o"])
        gu = _rms_norm(x, lp["mlp_norm"], eps) @ dequantize(*lp["gate_up"])
        ffn = gu.shape[-1] // 2
        x = x + (jax.nn.silu(gu[:, :ffn]) * gu[:, ffn:]) @ dequantize(
            *lp["down"])
        return x, None

    def forward(ids, embed, stack, final_norm, head):
        x = embed[ids].astype(jnp.float32)
        x, _ = jax.lax.scan(layer, x, stack)
        logits = _rms_norm(x, final_norm, eps) @ dequantize(*head)
        return jax.nn.log_softmax(logits, -1)

    return jax.jit(forward)


def logprobs(hf_config: dict, params: dict, ids: list[int]):
    """log-softmax of the next-token logits at every position of ``ids``:
    a numpy ``[T, vocab]`` float32 array."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    n_q = int(hf_config["num_attention_heads"])
    n_kv = int(hf_config["num_key_value_heads"])
    d = int(hf_config.get("head_dim")
            or hf_config["hidden_size"] // n_q)
    if hf_config.get("sliding_window"):
        raise ValueError("this reference has no sliding window")
    forward = _forward_fn(n_q, n_kv, d, float(hf_config["rms_norm_eps"]),
                          float(hf_config["rope_theta"]))
    layers = params["layers"]
    if layers["qkv"].data.shape[0] != int(hf_config["num_hidden_layers"]):
        raise ValueError("the parameter tree's depth is not the config's")
    stack = {n: (layers[n].data, layers[n].scales)
             for n in ("qkv", "o", "gate_up", "down")}
    stack["attn_norm"] = layers["attn_norm"]
    stack["mlp_norm"] = layers["mlp_norm"]
    head = params["lm_head"]
    with jax.default_matmul_precision("highest"):
        out = forward(jnp.asarray(ids, jnp.int32), params["embed"], stack,
                      params["final_norm"], (head.data, head.scales))
    return np.asarray(out, np.float32)


def compare(ref_logprobs, prompt_len: int, tokens: list[int],
            server_logprobs: list[float]) -> dict:
    """Per emitted position: the server's chosen-token log-probability
    against the reference's log-probability of that token, and how far the
    chosen token's reference logit trails the reference's argmax."""
    import numpy as np

    lp_err, margin = 0.0, 0.0
    for j, (tok, lp) in enumerate(zip(tokens, server_logprobs)):
        row = ref_logprobs[prompt_len - 1 + j]
        lp_err = max(lp_err, abs(float(row[tok]) - float(lp)))
        margin = max(margin, float(np.max(row) - row[tok]))
    ok = (len(tokens) == len(server_logprobs) and len(tokens) > 0
          and lp_err <= LOGPROB_TOL and margin <= ARGMAX_MARGIN_TOL)
    return {"ok": bool(ok), "logprob_err": lp_err, "argmax_margin": margin,
            "logprob_tol": LOGPROB_TOL, "margin_tol": ARGMAX_MARGIN_TOL}
