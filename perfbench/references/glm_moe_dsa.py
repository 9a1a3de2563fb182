"""The plain reference for ``model_type: glm_moe_dsa`` (zai-org GLM-5.x; its
config's keys are the DeepSeek-V3 block's, Liu et al. 2024, sections
2.1.1-2.1.2, with DeepSeek-V3.2's learned sparse attention, whose indexer the
``index_*`` keys name): float32 ``jax.numpy``, matmul precision "highest", the
full forward over the whole sequence.  No cache, no latent pool, no absorbed
products, no kernels, no batching, no threshold search: a query's selection
is ``lax.top_k`` of its scores.  It imports nothing of the program: it reads
the HF config keys itself, builds its own rotary frequencies, and unpacks the
SERVED int4 planes by its own code, a layer (and inside an expert layer an
expert) at a time, so it fits beside the loaded engine at the published
widths.

The model.  ``x <- x + Attn(RMSNorm(x))``; ``x <- x + FFN(RMSNorm(x))``; after
the last layer the final norm and the head.  With ``h`` the normed hidden
state of token t:

- Latent attention (MLA): ``c_q = RMSNorm(h W_qa)``; ``q_i = c_q W_qb,i =
  [q_nope_i (192) ; q_rope_i (64)]`` a head; ``[c_kv (512) ; k_r (64)] = h
  W_kva``; ``c = RMSNorm(c_kv)``; ``[k_nope_i (192) ; v_i (256)] = c W_kvb,i``.
  ``q_rope_i`` and the ONE ``k_r`` all heads share are rotated: interleaved
  pairs ``(x[2j], x[2j+1])`` by ``pos * theta^(-2j/64)`` (``rope_parameters``:
  theta 8e6, type "default": no YaRN).  Logit of query t, key s, head i:
  ``256^-1/2 (q_nope_i . k_nope_is + q_rope_i . k_r_s)``; softmax over the
  SELECTED keys ``s in S_t`` only; ``o_i = sum_s p_is v_is``; the output is
  ``[o_1 .. o_H] W_o``.
- The indexer, in a layer whose ``indexer_types`` entry is "full":
  ``q^I_tj`` = head j of ``c_q W^I_q`` (``index_n_heads`` heads of
  ``index_head_dim``), ``k^I_s = LayerNorm(h_s W^I_k)`` (weight and bias,
  epsilon 1e-6: DeepSeek-V3.2's published indexer), both rotated on their
  FIRST ``qk_rope_head_dim`` dims (interleaved pairs, the same frequencies);
  ``w_t = h_t W^I_w * index_n_heads^-1/2 * index_head_dim^-1/2``; ``I_ts =
  sum_j w_tj relu(q^I_tj . k^I_s)`` for ``s <= t``; ``S_t`` = the
  ``index_topk`` positions of largest score (all ``s <= t`` while ``t <
  index_topk``), equal scores to the earlier position.  A layer whose entry
  is "shared" has no indexer weights and uses ``S_t`` of the nearest full
  layer below it.
- Layers ``< first_k_dense_replace``: ``W_d (silu(h W_g) * h W_u)``.
- Expert layers: ``sigma = sigmoid(h W_r)`` in float32 over ALL routed experts
  of the published model (the router's width, ``n_router_experts``); the
  ``num_experts_per_tok`` largest ``sigma + b`` (``e_score_correction_bias``;
  ``n_group`` 1: no group step) are chosen; ``w_e = routed_scaling_factor
  sigma_e / (sum over the chosen + 1e-20)`` (the RAW scores); ``y = sum_e w_e
  E_e(h) + E_shared(h)`` with the dense layer's form at the experts' width.

Departures from the published code, each also the served path's.  (1) It
rotates ``q^I`` and ``k^I`` by a Hadamard matrix and quantizes them to fp8
before their product: the rotation is orthogonal and leaves every dot product
as it is; both are left out, the products are float32 here (bf16 in the
served path, the index key cached in bf16).  (2) The multi-token-prediction
layer (``num_nextn_predict_layers``) is not built: a draft head, on which the
model's logits do not depend.  (3) ``assumed`` in the configuration's file:
a shared layer holds no indexer weights and no index-key cache; the
LayerNorm's epsilon and the weights' scale as above.

The share.  The configuration is one chip of a deployment that divides the
routed experts and the vocabulary: the tree holds experts
``first_routed_expert .. + n_routed_experts`` and ``vocab_size`` rows.  The
reference computes exactly that share: it routes over the router's full
width, normalizes over all chosen experts, adds the held ones' outputs and
leaves the absent ones' out (no stand-in for the other chips), and takes the
log-softmax over the held vocabulary slice.

Ties.  Router: ``lax.top_k`` in float32, the lower index first among equals;
``logprobs`` returns every position's narrowest gap over the expert layers
between the last chosen and the first passed-over ``sigma + b``, and
``compare`` leaves the all but exact ties (under ROUTER_TIE) out of its
LARGEST-error limits, counted, as the other expert families' references do.
Selection: ``lax.top_k`` likewise.  A cached position whose score lies within
INDEX_TIE (a share of the spread of the query's visible scores) of the
``index_topk``-th may fall either side in the served path, whose scores are
bf16 products: ``logprobs`` returns, for every position, how many such
positions its full layers saw, and ``compare`` says how many the emitted
positions had (``index_ties``).  They are NOT left out: at the cell's lengths
every query has some, one of 2048 attended tokens weighs ~1/2048 of a head's
output, and what they move is inside the limits below (readings there).

Plane layout assumed (``quantize/core.py`` docstrings; a mismatch shows as
``correct: false``): a weight is ``[in, out]``; ``scales`` ``[in/32, out]``
fp16; ``data`` ``[in/2, out]`` uint8 where, inside each block of 32 input
rows, byte j holds row j in its low nibble and row j + 16 in its high nibble;
a code c stands for (c - 8) * scale.  Planes may be PADDED with inert rows and
columns past the logical ``shape``: the reference cuts them off.  ``gate_up``
planes hold the gate's columns first, then the up projection's.
"""

from __future__ import annotations

import functools

BLOCK = 32
QUERY_BLOCK = 256          # queries attended at a time (memory, not math)
INDEX_NORM_EPS = 1e-6

# --- limits ------------------------------------------------------------------
# Set from two readings at the published widths on the chip (PERF.md section
# 6, PR 39; my chip runs), each limit between them with room on both sides.
# (1) The served path, bf16 activations and index keys with float32
# accumulation, router, softmax and norms as the configuration states: 14
# probes of 8 positions over 7 seeds read a median error of 0.0043-0.0165
# (the four largest 0.0165, 0.0151, 0.0135, 0.0124), a largest error of at
# most 0.0411 and an argmax margin of 0; at the cell's lengths (a 16384-token
# prompt and 64 decoded tokens, alone and beside 15 rows, the same tokens)
# 0.0117 / 0.1283 / 0.0089 with 41,819 cached positions inside INDEX_TIE of a
# selection's edge.  This reference against itself with the activations
# rounded to bf16 (``lower="bf16"``) reads medians of 0.0024 and 0.0018 at
# the probes' length and 0.0045 at the cell's, where its largest error is
# 0.1251: what falls the other side of a selection's edge under bf16 scores
# moves ONE position of 64 by an eighth and the rest by nothing, in the
# served path and in this control alike.
# (2) The nearest precision below the configuration's bf16 activations, fp8
# (``lower="fp8"``: every activation through e4m3), playing the server
# greedily on four probes (two seeds): medians 0.0514, 0.0422, 0.0514 and
# 0.0326, largest errors 0.0547-0.2032; teacher-forced at the cell's lengths
# (two seeds): medians 0.0297 and 0.0357, largest 0.2216 and 0.2556.  It is
# not correct by MEDIAN_TOL in all six (and by LOGPROB_TOL in one).
# MEDIAN_TOL 0.026 lies 1.58x over the served path's largest median and 1.25x
# under fp8's smallest at the probes' length, which is what ``correct``
# compares (at the cell's lengths fp8 reads 0.0297 at least, 1.14x over it:
# the room there is thin and is the builder's check, not ``correct``'s; the
# served path reads 0.0117 and 0.0122 there, largest 0.1283 and 0.1338).
# LOGPROB_TOL and ARGMAX_MARGIN_TOL stand 1.9x and 2.2x over the largest the
# served path read at the cell's lengths (6x over the probes') and catch a
# gross fault (a dropped rotary slice reads 0.89 / 0.48 at a tiny size);
# fp8's largest errors straddle them.
LOGPROB_TOL = 0.25          # largest |log-probability error|, kept positions
MEDIAN_TOL = 0.026          # median error over all positions
ARGMAX_MARGIN_TOL = 0.25    # how far a chosen token may trail the argmax
ROUTER_TIE = 1e-4           # a narrower gap than this leaves a position out
KEEP_SHARE = 0.5            # at least this share of positions must be kept
INDEX_TIE = 2.0 ** -8       # of the spread of a query's visible scores


def dequantize(data, scales, shape):
    """One served sym_int4 plane -> float32 ``shape`` = [in, out]."""
    import jax.numpy as jnp

    half = BLOCK // 2
    blocks = data.shape[0] // half
    d = data.reshape(blocks, half, data.shape[1])
    codes = jnp.concatenate([d & 0x0F, d >> 4], axis=1).astype(jnp.float32)
    w = (codes - 8.0) * scales.astype(jnp.float32)[:, None, :]
    return w.reshape(blocks * BLOCK, data.shape[1])[: shape[0], : shape[1]]


def rope_frequencies(hf: dict):
    """Inverse frequencies [rope/2] float32 from ``rope_parameters`` (or the
    older top-level ``rope_theta``); anything but the default type is
    refused."""
    import numpy as np

    rp = hf.get("rope_parameters") or {}
    kind = rp.get("rope_type", rp.get("type", "default"))
    if kind != "default" or hf.get("rope_scaling"):
        raise ValueError(f"this reference rotates by the default rule, not "
                         f"{kind!r}")
    d = int(hf["qk_rope_head_dim"])
    base = float(rp.get("rope_theta", hf.get("rope_theta", 10000.0)))
    return (1.0 / base ** (np.arange(0, d, 2, dtype=np.float64) / d)
            ).astype(np.float32)


def _act(x, lower):
    """An activation as the served path holds it between operations."""
    import jax.numpy as jnp

    if lower == "bf16":
        return x.astype(jnp.bfloat16).astype(jnp.float32)
    if lower == "fp8":
        return x.astype(jnp.float8_e4m3fn).astype(jnp.float32)
    return x


def _mm(a, w):
    import jax.numpy as jnp

    return jnp.matmul(a.astype(jnp.float32), w.astype(jnp.float32))


def _rms_norm(x, w, eps):
    import jax
    import jax.numpy as jnp

    x = x.astype(jnp.float32)
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * w.astype(jnp.float32)


def _layer_norm(x, w, b, eps):
    import jax
    import jax.numpy as jnp

    x = x.astype(jnp.float32)
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, axis=-1, keepdims=True)
    return ((x - mu) * jax.lax.rsqrt(var + eps) * w.astype(jnp.float32)
            + b.astype(jnp.float32))


def _rope(x, pos, inv_freq):
    """Interleaved pairs (x[2j], x[2j+1]) of x [T, ..., d] rotated by pos *
    inv_freq[j]."""
    import jax.numpy as jnp

    ang = pos.astype(jnp.float32)[:, None] * inv_freq[None, :]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    while cos.ndim < x.ndim:
        cos, sin = cos[:, None], sin[:, None]
    x1, x2 = x[..., 0::2].astype(jnp.float32), x[..., 1::2].astype(jnp.float32)
    out = jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.reshape(x.shape)


def _rope_first(x, pos, inv_freq, rope: int):
    """``_rope`` on the first ``rope`` dims of x's last axis."""
    import jax.numpy as jnp

    return jnp.concatenate(
        [_rope(x[..., :rope], pos, inv_freq), x[..., rope:]], axis=-1)


@functools.lru_cache(maxsize=8)
def _select_fn(n_heads: int, dim: int, rope: int, top: int, shapes: tuple,
               lower):
    """The indexer of a full layer: (selection mask [T, T] bool, each
    query's count of positions inside INDEX_TIE of its ``top``-th score)."""
    import jax
    import jax.numpy as jnp

    wq_s, wk_s = shapes
    drop_rope = lower == "no_rope"
    lower = None if drop_rope else lower

    def select(h, c_q, ix, inv_freq):
        t = h.shape[0]
        pos = jnp.arange(t)
        q = _act(_mm(c_q, dequantize(*ix["wq_b"], wq_s)), lower).reshape(
            t, n_heads, dim)
        k = _act(_layer_norm(_act(_mm(h, dequantize(*ix["wk"], wk_s)), lower),
                             ix["k_norm"], ix["k_norm_bias"], INDEX_NORM_EPS),
                 lower)
        if not drop_rope:
            q = _act(_rope_first(q, pos, inv_freq, rope), lower)
            k = _act(_rope_first(k, pos, inv_freq, rope), lower)
        w = _mm(h, ix["w_proj"]) * (n_heads ** -0.5 * dim ** -0.5)
        keep = min(top, t)
        masks, ties = [], []
        for q0 in range(0, t, QUERY_BLOCK):
            q1 = min(q0 + QUERY_BLOCK, t)
            dots = jnp.einsum("qjd,kd->qjk", q[q0:q1], k)
            score = jnp.einsum("qj,qjk->qk", w[q0:q1],
                               jnp.maximum(dots, 0.0))
            seen = pos[None, :] <= pos[q0:q1, None]
            score = jnp.where(seen, score, -jnp.inf)
            vals, idx = jax.lax.top_k(score, keep)
            rows = jnp.arange(q1 - q0)[:, None]
            chosen = jnp.zeros((q1 - q0, t), bool).at[rows, idx].set(
                vals > -jnp.inf)
            masks.append(chosen)
            # positions within INDEX_TIE of the last chosen score, where
            # the query sees more than it keeps
            spread = (jnp.max(score, axis=-1)
                      - jnp.min(jnp.where(seen, score, jnp.inf), axis=-1))
            near = (jnp.abs(score - vals[:, -1:])
                    <= INDEX_TIE * spread[:, None]) & seen
            ties.append(jnp.where(pos[q0:q1] >= top,
                                  jnp.sum(near, axis=-1) - 1, 0))
        return jnp.concatenate(masks, 0), jnp.concatenate(ties, 0)

    return jax.jit(select)


@functools.lru_cache(maxsize=8)
def _attn_fn(heads: int, nope: int, rope: int, vdim: int, rank: int,
             eps: float, scale: float, shapes: tuple, lower):
    import jax
    import jax.numpy as jnp

    qa_s, qb_s, kva_s, kvb_s, o_s = shapes
    drop_rope = lower == "no_rope"
    lower = None if drop_rope else lower

    def pre(x, lp):
        """(the normed hidden state, the low-rank query) of a layer: what
        the indexer shares with the main heads."""
        h = _act(_rms_norm(x, lp["attn_norm"], eps), lower)
        c_q = _act(_rms_norm(_act(_mm(h, dequantize(*lp["q_a"], qa_s)),
                                  lower), lp["q_a_norm"], eps), lower)
        return h, c_q

    def block(x, h, c_q, lp, inv_freq, chosen):
        t = x.shape[0]
        q = _act(_mm(c_q, dequantize(*lp["q_b"], qb_s)),
                 lower).reshape(t, heads, nope + rope)
        ckv = _act(_mm(h, dequantize(*lp["kv_a"], kva_s)), lower)
        c = _act(_rms_norm(ckv[:, :rank], lp["kv_a_norm"], eps), lower)
        pos = jnp.arange(t)
        q_r = _act(_rope(q[..., nope:], pos, inv_freq), lower)
        k_r = _act(_rope(ckv[:, rank:], pos, inv_freq), lower)
        kv = _act(_mm(c, dequantize(*lp["kv_b"], kvb_s)),
                  lower).reshape(t, heads, nope + vdim)
        q_n, k_n, v = q[..., :nope], kv[..., :nope], kv[..., nope:]
        outs = []
        for q0 in range(0, t, QUERY_BLOCK):
            q1 = min(q0 + QUERY_BLOCK, t)
            s = jnp.einsum("qhd,khd->hqk", q_n[q0:q1], k_n[:q1])
            if not drop_rope:
                s = s + jnp.einsum("qhd,kd->hqk", q_r[q0:q1], k_r[:q1])
            s = s * scale
            p = jax.nn.softmax(
                jnp.where(chosen[q0:q1, :q1][None], s, -jnp.inf), axis=-1)
            outs.append(jnp.einsum("hqk,khd->qhd", p, v[:q1]))
        a = _act(jnp.concatenate(outs, axis=0).reshape(t, heads * vdim), lower)
        return _act(x + _act(_mm(a, dequantize(*lp["o"], o_s)), lower), lower)

    return jax.jit(pre), jax.jit(block)


def _gated_mlp(h, gate_up, down, width: int, lower):
    import jax

    g = _mm(h, gate_up[:, :width])
    u = _mm(h, gate_up[:, width:])
    return _mm(_act(jax.nn.silu(g) * u, lower), down)


@functools.lru_cache(maxsize=8)
def _dense_fn(eps: float, shapes: tuple, lower):
    import jax

    lower = None if lower == "no_rope" else lower
    gu_s, d_s = shapes

    def block(x, lp):
        h = _act(_rms_norm(x, lp["mlp_norm"], eps), lower)
        y = _gated_mlp(h, dequantize(*lp["gate_up"], gu_s),
                       dequantize(*lp["down"], d_s), gu_s[1] // 2, lower)
        return _act(x + _act(y, lower), lower)

    return jax.jit(block)


@functools.lru_cache(maxsize=8)
def _moe_fn(top_k: int, first: int, scaling: float, eps: float, shapes: tuple,
            lower):
    import jax
    import jax.numpy as jnp

    lower = None if lower == "no_rope" else lower
    gu_s, d_s, sgu_s, sd_s = shapes

    def block(x, lp):
        h = _act(_rms_norm(x, lp["mlp_norm"], eps), lower)
        sigma = jax.nn.sigmoid(_mm(h, lp["router"]))              # [T, E]
        s_sorted, e_idx = jax.lax.top_k(sigma + lp["router_bias"], top_k + 1)
        idx = e_idx[:, :top_k]
        chosen = jnp.take_along_axis(sigma, idx, axis=1)
        w = chosen / (jnp.sum(chosen, -1, keepdims=True) + 1e-20) * scaling
        gap = s_sorted[:, top_k - 1] - s_sorted[:, top_k]
        y = _gated_mlp(h, dequantize(*lp["shared_gate_up"], sgu_s),
                       dequantize(*lp["shared_down"], sd_s), sgu_s[1] // 2,
                       lower)
        held = lp["moe_gate_up"][0].shape[0]
        for e in range(held):
            gate = jnp.sum(jnp.where(idx == first + e, w, 0.0), axis=1)
            out = _gated_mlp(
                h, dequantize(lp["moe_gate_up"][0][e], lp["moe_gate_up"][1][e],
                              gu_s),
                dequantize(lp["moe_down"][0][e], lp["moe_down"][1][e], d_s),
                gu_s[1] // 2, lower)
            y = y + gate[:, None] * _act(out, lower)
        return _act(x + _act(y, lower), lower), gap

    return jax.jit(block)


@functools.lru_cache(maxsize=8)
def _head_fn(eps: float, shape: tuple, lower):
    import jax

    lower = None if lower == "no_rope" else lower

    def head(x, norm, plane):
        logits = _mm(_act(_rms_norm(x, norm, eps), lower),
                     dequantize(*plane, shape))
        return jax.nn.log_softmax(logits, -1)

    return jax.jit(head)


def _plane(q):
    return (q.data, q.scales)


def _layer_tree(stack: dict, i: int) -> dict:
    """Layer ``i`` of a stacked tree, QTensors as their (data, scales); the
    ``indexer`` sub-tree, stacked by FULL layer, is left to its caller."""
    out = {}
    for name, leaf in stack.items():
        if name == "indexer":
            continue
        if hasattr(leaf, "scales"):
            out[name] = (leaf.data[i], leaf.scales[i])
        else:
            out[name] = leaf[i]
    return out


def indexer_types(hf: dict) -> list[str]:
    """Every layer's indexer type, from the list or from the frequency keys
    (``index_skip_topk_offset`` leading full layers, then a full one closing
    every ``index_topk_freq``)."""
    n = int(hf["num_hidden_layers"])
    if hf.get("indexer_types") is not None:
        return list(hf["indexer_types"])
    freq = int(hf.get("index_topk_freq") or 1)
    skip = int(hf.get("index_skip_topk_offset",
                      hf.get("first_k_dense_replace", 0)))
    return ["full" if i < skip or (i - skip) % freq == freq - 1 else "shared"
            for i in range(n)]


def logprobs(hf_config: dict, params: dict, ids: list[int], lower=None,
             first_row: int = 0, selections: dict | None = None):
    """(log-softmax of the next-token logits at positions ``first_row`` on of
    ``ids`` over the held vocabulary, ``[T - first_row, vocab]`` float32; each
    of those positions' narrowest router gap over the expert layers; each of
    those positions' count of cached positions inside INDEX_TIE of its
    selection's edge, over the full layers), as numpy.

    ``lower="fp8"`` passes the activations between operations (where the
    served path holds bf16) through float8_e4m3 and keeps every operation
    float32: the nearest precision below the configuration's bf16, the
    reading that has to fail ``compare``.  ``lower="bf16"`` rounds them to
    bfloat16 where the served path does: what rounding alone, at the
    configuration's own precision, does to the log-probabilities (and to the
    selections).  ``lower="no_rope"`` drops the rotated slices' part of every
    logit and score: what a dropped slice reads.  ``selections`` {layer: mask
    [T, T]} REPLACES what those layers would attend over (a full layer's own
    choice, or the one a shared layer would borrow): what another selection
    reads."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    hf = hf_config
    eps = float(hf.get("rms_norm_eps", 1e-6))
    heads = int(hf["num_attention_heads"])
    nope, rope = int(hf["qk_nope_head_dim"]), int(hf["qk_rope_head_dim"])
    vdim, rank = int(hf["v_head_dim"]), int(hf["kv_lora_rank"])
    n_dense = int(hf.get("first_k_dense_replace", 0))
    n_layers = int(hf["num_hidden_layers"])
    if hf.get("topk_method", "noaux_tc") != "noaux_tc":
        raise ValueError("this reference selects on score + bias (noaux_tc)")
    if hf.get("scoring_func", "sigmoid") != "sigmoid":
        raise ValueError("this reference scores with a sigmoid")
    if not hf.get("norm_topk_prob", True):
        raise ValueError("this reference normalizes the chosen weights")
    if int(hf.get("n_group") or 1) != 1:
        raise ValueError("this reference has no group step (n_group 1)")
    types = indexer_types(hf)
    inv_freq = jnp.asarray(rope_frequencies(hf))
    scale = (nope + rope) ** -0.5
    dense, moe_tree = params.get("layers_dense"), params["layers"]

    def shapes(tree, *names):
        return tuple(tuple(tree[m].shape) for m in names)

    attn_names = ("q_a", "q_b", "kv_a", "kv_b", "o")
    moe = _moe_fn(int(hf["num_experts_per_tok"]),
                  int(hf.get("first_routed_expert", 0)),
                  float(hf["routed_scaling_factor"]), eps,
                  shapes(moe_tree, "moe_gate_up", "moe_down",
                         "shared_gate_up", "shared_down"), lower)
    head = params["lm_head"]
    t = len(ids)
    tie = jnp.full((t,), jnp.inf)
    edge = jnp.zeros((t,), jnp.int32)
    chosen, full_seen = None, {"dense": 0, "moe": 0}
    with jax.default_matmul_precision("highest"):
        x = params["embed"][jnp.asarray(ids, jnp.int32)].astype(jnp.float32)
        for i in range(n_layers):
            group = "dense" if i < n_dense else "moe"
            tree = dense if i < n_dense else moe_tree
            lp = _layer_tree(tree, i if i < n_dense else i - n_dense)
            pre, block = _attn_fn(heads, nope, rope, vdim, rank, eps, scale,
                                  shapes(tree, *attn_names), lower)
            h, c_q = pre(x, lp)
            if types[i] == "full":
                ix = _layer_tree(tree["indexer"], full_seen[group])
                full_seen[group] += 1
                chosen, near = _select_fn(
                    int(hf["index_n_heads"]), int(hf["index_head_dim"]), rope,
                    int(hf["index_topk"]),
                    shapes(tree["indexer"], "wq_b", "wk"), lower)(
                    h, c_q, ix, inv_freq)
                edge = edge + near
            if selections and i in selections:
                chosen = jnp.asarray(selections[i], bool)
            x = block(x, h, c_q, lp, inv_freq, chosen)
            if i < n_dense:
                x = _dense_fn(eps, shapes(tree, "gate_up", "down"), lower)(
                    x, lp)
            else:
                x, gap = moe(x, lp)
                tie = jnp.minimum(tie, gap)
        out = _head_fn(eps, tuple(head.shape), lower)(
            x[first_row:], params["final_norm"], _plane(head))
    return (np.asarray(out, np.float32), np.asarray(tie[first_row:],
                                                    np.float32),
            np.asarray(edge[first_row:], np.int64))


def compare(ref, prompt_len: int, tokens: list[int],
            server_logprobs: list[float]) -> dict:
    """Per emitted position: the server's chosen-token log-probability
    against the reference's, and how far the chosen token's reference logit
    trails the reference's argmax.  The largest of each over the positions
    whose narrowest router gap is at least ROUTER_TIE, and the median error
    over all of them; ``index_ties`` counts the cached positions inside
    INDEX_TIE of a selection's edge over the emitted positions (they stay
    in).  ``prompt_len`` counts from the first row ``ref`` holds."""
    import numpy as np

    rows, tie, edge = ref
    errs, margins, gaps, near = [], [], [], 0
    for j, (tok, lp) in enumerate(zip(tokens, server_logprobs)):
        row = rows[prompt_len - 1 + j]
        errs.append(abs(float(row[tok]) - float(lp)))
        margins.append(float(np.max(row) - row[tok]))
        gaps.append(float(tie[prompt_len - 1 + j]))
        near += int(edge[prompt_len - 1 + j])
    kept = [j for j, g in enumerate(gaps) if g >= ROUTER_TIE]
    lp_err = max((errs[j] for j in kept), default=0.0)
    margin = max((margins[j] for j in kept), default=0.0)
    median = float(np.median(errs)) if errs else 0.0
    ok = (len(tokens) == len(server_logprobs) and len(tokens) > 0
          and len(kept) >= KEEP_SHARE * len(tokens)
          and lp_err <= LOGPROB_TOL and margin <= ARGMAX_MARGIN_TOL
          and median <= MEDIAN_TOL)
    return {"ok": bool(ok), "logprob_err": lp_err, "argmax_margin": margin,
            "logprob_err_median": median,
            "router_ties_left_out": len(tokens) - len(kept),
            "index_ties": near,
            "logprob_tol": LOGPROB_TOL, "margin_tol": ARGMAX_MARGIN_TOL,
            "median_tol": MEDIAN_TOL, "router_tie": ROUTER_TIE,
            "index_tie": INDEX_TIE,
            "errors": [round(e, 4) for e in errs],
            "gaps": [round(g, 6) for g in gaps]}
