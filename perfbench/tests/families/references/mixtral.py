"""The plain reference of the tests' throw-away family ``mixtral`` (Jiang et
al. 2024, transformers' ``modeling_mixtral.py``): the Mistral attention
block, then a sparse feed-forward.  A float32 router scores every expert; a
token's top ``num_experts_per_tok`` logits are softmaxed among themselves and
weigh those experts' SwiGLU outputs.  Every expert is computed for every
token and the others weighed by zero: plain, and exact.  float32, matmul
precision "highest", no kernels, no cache; the helpers shared with the dense
reference (plane layout, norm, rotary, the comparison and its limits) are
that file's.

One thing a sparse family adds to the comparison.  Where a token's last
chosen and first passed-over router logits lie within rounding of each
other, float32 here and bf16 activations in the program choose different
experts, and the logits then differ by order 1 though neither side is wrong
(seen on the CPU: ties of 0.012 and 0.015 gave a log-probability 1.15
apart, PR 27).  So ``logprobs`` also returns each position's narrowest such
gap over the layers, and ``compare`` leaves out the positions whose gap is
under ROUTER_TIE, and fails where that leaves under half of them."""

from __future__ import annotations

from references import mistral as dense
from references.mistral import _rms_norm, _rope, dequantize

# router logits have standard deviation ~1.2 here and reach the program
# through bf16 activations; the widest gap seen to flip was 0.015
ROUTER_TIE = 0.05


def logprobs(hf_config: dict, params: dict, ids: list[int]):
    import jax
    import jax.numpy as jnp
    import numpy as np

    n_q = int(hf_config["num_attention_heads"])
    n_kv = int(hf_config["num_key_value_heads"])
    d = int(hf_config["hidden_size"]) // n_q
    top_k = int(hf_config["num_experts_per_tok"])
    eps, theta = float(hf_config["rms_norm_eps"]), float(hf_config["rope_theta"])
    lay = params["layers"]
    t = len(ids)
    tie = jnp.full((t,), jnp.inf)

    def plane(qt, *at):
        return dequantize(qt.data[at], qt.scales[at])

    with jax.default_matmul_precision("highest"):
        x = params["embed"][jnp.asarray(ids, jnp.int32)].astype(jnp.float32)
        for i in range(int(hf_config["num_hidden_layers"])):
            qkv = _rms_norm(x, lay["attn_norm"][i], eps) @ plane(lay["qkv"], i)
            q = _rope(qkv[:, : n_q * d].reshape(t, n_q, d), theta)
            k = _rope(qkv[:, n_q * d: (n_q + n_kv) * d].reshape(t, n_kv, d),
                      theta)
            v = qkv[:, (n_q + n_kv) * d:].reshape(t, n_kv, d)
            k, v = (jnp.repeat(a, n_q // n_kv, axis=1) for a in (k, v))
            s = jnp.einsum("qhd,khd->hqk", q, k) / jnp.sqrt(jnp.float32(d))
            s = jnp.where(jnp.tril(jnp.ones((t, t), bool))[None], s, -jnp.inf)
            a = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, -1), v)
            x = x + a.reshape(t, n_q * d) @ plane(lay["o"], i)
            h = _rms_norm(x, lay["mlp_norm"][i], eps)
            logits = h @ lay["router"][i]                       # [T, E]
            best, chosen = jax.lax.top_k(logits, top_k)
            passed_over = jnp.where(
                jnp.zeros_like(logits).at[jnp.arange(t)[:, None], chosen]
                .set(1.0) > 0, -jnp.inf, logits).max(-1)
            tie = jnp.minimum(tie, best[:, -1] - passed_over)
            gate = jnp.zeros_like(logits).at[
                jnp.arange(t)[:, None], chosen].set(jax.nn.softmax(best, -1))
            for e in range(logits.shape[-1]):
                gu = h @ plane(lay["moe_gate_up"], i, e)
                ffn = gu.shape[-1] // 2
                y = (jax.nn.silu(gu[:, :ffn]) * gu[:, ffn:]) @ plane(
                    lay["moe_down"], i, e)
                x = x + gate[:, e: e + 1] * y
        head = params["lm_head"]
        out = jax.nn.log_softmax(
            _rms_norm(x, params["final_norm"], eps)
            @ dequantize(head.data, head.scales), -1)
    return np.asarray(out, np.float32), np.asarray(tie, np.float32)


def compare(ref, prompt_len: int, tokens: list[int],
            server_logprobs: list[float]) -> dict:
    rows, tie = ref
    kept = [j for j in range(len(tokens))
            if tie[prompt_len - 1 + j] >= ROUTER_TIE]
    res = dense.compare(rows[[prompt_len - 1 + j for j in kept]], 1,
                        [tokens[j] for j in kept],
                        [server_logprobs[j] for j in kept])
    res["router_ties_left_out"] = len(tokens) - len(kept)
    res["ok"] = bool(res["ok"] and len(tokens) == len(server_logprobs)
                     and 2 * len(kept) >= len(tokens))
    return res
