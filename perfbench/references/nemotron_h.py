"""The plain reference for ``model_type: nemotron_h`` (NVIDIA Nemotron-H /
Nemotron-3-Nano: ``modeling_nemotron_h.py`` of the published checkpoint, and
Dao & Gu 2024 for the Mamba-2 recurrence): float32 ``jax.numpy``, matmul
precision "highest", token by token.  No chunking (the state is a sequential
``lax.scan`` over positions), no cache, no batching, no kernels.  It imports
nothing of the program: it reads the HF config keys itself and unpacks the
SERVED int4 planes by its own code, one block (and inside an expert layer one
expert) at a time, so it fits beside the loaded engine at the published
widths.

The model.  A block is ``x <- x + Mixer(RMSNorm(x))`` with ONE mixer, chosen
by ``hybrid_override_pattern`` (``M`` Mamba-2, ``E`` experts, ``*``
attention); after the last block ``norm_f`` and the head.

- ``M``: ``[z | xBC | dt] = in_proj(u)`` (widths d_inner | d_inner + 2 G N |
  H); ``xBC <- silu(causal depthwise conv_4(xBC) + bias)``; ``[x | B | C]``
  with x as [H, P], B and C as [G, N] shared by the H/G heads of a group;
  ``dt <- softplus(dt + dt_bias)``; ``A = -exp(A_log)``; ``h_t = exp(dt_t A)
  h_{t-1} + dt_t x_t (x) B_t``; ``y_t = C_t . h_t + D x_t``; ``y <-
  GroupRMSNorm(y * silu(z)) * w`` (gate BEFORE the norm, groups of
  d_inner / G); ``out_proj(y)``.
- ``E``: ``s = sigmoid(x W_r)`` over ALL routed experts of the published
  model (the router's width, ``n_router_experts``); the top
  ``num_experts_per_tok`` of ``s + e_score_correction_bias`` are chosen; their
  weights are ``s`` (without the bias) divided by their sum (+1e-20) times
  ``routed_scaling_factor``; an expert is ``W_down relu(W_up x)^2``; the
  shared expert (same form, its own width) is added for every token.
- ``*``: grouped-query causal attention, scale 1/sqrt(head_dim), no bias and
  NO rotary embedding (the published modeling code applies none in these
  blocks; position comes from the Mamba blocks).

The share.  The configuration is one chip of a deployment that divides the
routed experts and the vocabulary: the tree holds experts
``first_routed_expert .. + n_routed_experts`` and ``vocab_size`` rows.  The
reference computes exactly that share: it routes over the router's full
width, normalizes over all chosen experts, adds the held ones' outputs and
leaves the absent ones' out (no stand-in for the other chip), and takes the
log-softmax over the held vocabulary slice.

Router ties.  The rule here: ``lax.top_k`` over ``s + bias`` in float32, the
lower index first among equals.  ``logprobs`` also returns, for every
position, the narrowest gap over the expert blocks between the last chosen
and the first passed-over selection score.  With 128 experts and top 6 the
gap's mean is about 0.012 a block, and a position passes 23 blocks: bf16
activations (relative rounding 2**-9 a residual add) move a score by
0.0005-0.003, so in the served path about one routing decision in ten
differs from this float32 one though neither is wrong, and a flip between a
held and an absent expert changes the sum as well.  ``compare`` therefore
holds two limits: the LARGEST error over the positions whose narrowest gap is
at least ROUTER_TIE (the others are left out and counted), and the MEDIAN
error over all positions, which a few flipped positions do not move and a
lower precision everywhere does.

Plane layout assumed (``quantize/core.py`` docstrings; a mismatch shows as
``correct: false``): a weight is ``[in, out]``; ``scales`` ``[in/32, out]``
fp16; ``data`` ``[in/2, out]`` uint8 where, inside each block of 32 input
rows, byte j holds row j in its low nibble and row j + 16 in its high nibble;
a code c stands for (c - 8) * scale.  Planes may be PADDED with inert rows and
columns past the logical ``shape`` (the program pads once at build to its
kernel's tiles): the reference cuts them off.
"""

from __future__ import annotations

import functools

BLOCK = 32

# --- limits ------------------------------------------------------------------
# Set from two readings at the published widths (PERF.md section 6, PR 28).
# (1) The served path on the chip, bf16 activations and float32 state as the
# configuration states, 28 probes of 8 positions over 14 seeds: the median
# error of a probe 0.027-0.123 (the four largest 0.097, 0.107, 0.122, 0.123),
# its largest error over the kept positions at most 0.54, the argmax margin
# at most 0.57.  This reference against itself with the SAME places rounded
# to bf16 (``lower="bf16"``) gives a median of 0.040 and a largest error of
# 0.47 over a probe's 127 positions: the served path's error is rounding and
# the routing decisions rounding flips, not a defect.  (2) This reference
# through fp8 (``lower="fp8"``: activations and state through e4m3), playing
# the server greedily, on the chip, 4 probes: medians 0.180, 0.186, 0.330,
# 0.373, largest errors 0.43-0.79 (0.29 and 1.07 over all 127 positions).
# MEDIAN_TOL is the limit that tells the two precisions apart and lies
# between the readings' nearest ends, 0.123 and 0.180: every fp8 probe is
# over it, every served probe under it.  A probe's median is over 8 positions
# only (the harness compares a probe at a time), which is why the two ends
# are that near.  The other two limits catch what is WRONG, not what is
# narrow: they lie above the served path's largest (0.54, 0.57) and above
# the fp8 readings too.  A position whose narrowest router gap is under
# ROUTER_TIE is left out of the two maxima (its routing is decided by the
# last bit) and counted; the median takes all.  A wrong mask, head mapping,
# conv tap order, nibble order or state carried to the wrong row gives
# errors of order 1-10 at every position.
LOGPROB_TOL = 0.9            # largest |error| over the kept positions
MEDIAN_TOL = 0.15            # median |error| over ALL positions
ARGMAX_MARGIN_TOL = 0.9
ROUTER_TIE = 1e-4            # a narrower gap than this leaves a position out
KEEP_SHARE = 0.25            # fewer kept positions than this share fails


def dequantize(data, scales, shape):
    """[kp/2, np] uint8 + [kp/32, np] fp16 -> the logical [k, n] float32."""
    import jax.numpy as jnp

    half = BLOCK // 2
    nb, out = scales.shape
    p = data.reshape(nb, half, out)
    codes = jnp.concatenate([p & 0x0F, p >> 4], axis=1)       # [nb, 32, out]
    w = (codes.astype(jnp.float32) - 8.0) * scales.astype(
        jnp.float32)[:, None, :]
    return w.reshape(nb * BLOCK, out)[: shape[0], : shape[1]]


def _plane(qt):
    """(data, scales) of a program QTensor: only its arrays cross into jit."""
    return qt.data, qt.scales


def _rms_norm(x, w, eps):
    import jax
    import jax.numpy as jnp

    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _lower(x, lower):
    """``x`` through a narrower float and back.  By ``lax.reduce_precision``
    (exponent and mantissa bits of e4m3 / bf16): a pair of converts is what
    the compiler may drop as excess precision, and on the chip it did."""
    import jax

    if lower is None:
        return x
    exponent, mantissa = {"fp8": (4, 3), "bf16": (8, 7)}[lower]
    return jax.lax.reduce_precision(x, exponent, mantissa)


def parse_pattern(pattern: str) -> list[tuple[str, int]]:
    """[(kind, index among the blocks of its kind)] in the model's order."""
    seen: dict[str, int] = {}
    out = []
    for ch in pattern:
        if ch not in "ME*":
            raise ValueError(f"this reference has no block kind {ch!r}")
        out.append((ch, seen.get(ch, 0)))
        seen[ch] = seen.get(ch, 0) + 1
    return out


@functools.lru_cache(maxsize=8)
def _mamba_fn(heads: int, hd: int, groups: int, n: int, eps: float,
              shapes: tuple, lower):
    import jax
    import jax.numpy as jnp

    d_in, gn = heads * hd, groups * n
    in_shape, out_shape = shapes

    def block(x, lp):
        t = x.shape[0]
        u = _lower(_rms_norm(x, lp["norm"], eps), lower)
        zxd = u @ dequantize(*lp["in_proj"], in_shape)
        z, xbc, dt = (zxd[:, :d_in], zxd[:, d_in: 2 * d_in + 2 * gn],
                      zxd[:, 2 * d_in + 2 * gn:])
        # causal depthwise conv over time, kernel [taps, channels]: tap j
        # multiplies the input taps - 1 - j positions back
        taps = lp["conv_w"].shape[0]
        padded = jnp.concatenate(
            [jnp.zeros((taps - 1, xbc.shape[1]), jnp.float32), xbc], 0)
        conv = sum(padded[j: j + t] * lp["conv_w"][j] for j in range(taps))
        xbc = jax.nn.silu(conv + lp["conv_b"])
        xs = xbc[:, :d_in].reshape(t, heads, hd)
        b = xbc[:, d_in: d_in + gn].reshape(t, groups, n)
        c = xbc[:, d_in + gn:].reshape(t, groups, n)
        b = jnp.repeat(b, heads // groups, axis=1)             # [T, H, N]
        c = jnp.repeat(c, heads // groups, axis=1)
        dt = jax.nn.softplus(dt + lp["dt_bias"])               # [T, H]
        a = -jnp.exp(lp["A_log"])                              # [H]

        def step(h, inp):
            x_t, b_t, c_t, dt_t = inp
            h = (jnp.exp(dt_t * a)[:, None, None] * h
                 + (dt_t[:, None] * x_t)[:, :, None] * b_t[:, None, :])
            h = _lower(h, lower)
            y = jnp.einsum("hpn,hn->hp", h, c_t) + lp["D"][:, None] * x_t
            return h, y

        _, y = jax.lax.scan(step, jnp.zeros((heads, hd, n), jnp.float32),
                            (xs, b, c, dt))
        y = y.reshape(t, d_in) * jax.nn.silu(z)
        yg = y.reshape(t, groups, d_in // groups)
        yg = yg * jax.lax.rsqrt(jnp.mean(yg * yg, -1, keepdims=True) + eps)
        y = _lower(yg.reshape(t, d_in) * lp["gate_norm"], lower)
        return _lower(x + y @ dequantize(*lp["out_proj"], out_shape), lower)

    return jax.jit(block)


@functools.lru_cache(maxsize=8)
def _moe_fn(top_k: int, first: int, scale: float, eps: float, shapes: tuple,
            lower):
    import jax
    import jax.numpy as jnp

    up_shape, down_shape, sup_shape, sdown_shape = shapes

    def relu2(v):
        return jnp.square(jax.nn.relu(v))

    def block(x, lp):
        t = x.shape[0]
        h = _rms_norm(x, lp["mlp_norm"], eps)
        s = jax.nn.sigmoid(h @ lp["router"])                   # [T, router]
        sel = s + lp["router_bias"]
        best, chosen = jax.lax.top_k(sel, top_k)
        rows = jnp.arange(t)[:, None]
        passed = jnp.where(
            jnp.zeros_like(sel).at[rows, chosen].set(1.0) > 0, -jnp.inf,
            sel).max(-1)
        gap = best[:, -1] - passed
        w = jnp.take_along_axis(s, chosen, -1)
        w = w / (w.sum(-1, keepdims=True) + 1e-20) * scale
        gate = jnp.zeros_like(s).at[rows, chosen].set(w)       # [T, router]
        hl = _lower(h, lower)

        def expert(acc, xs):
            e, up, down = xs
            y = _lower(relu2(hl @ dequantize(*up, up_shape)), lower)
            y = y @ dequantize(*down, down_shape)
            g = jax.lax.dynamic_index_in_dim(gate, first + e, 1, False)
            return acc + g[:, None] * y, None

        held = lp["moe_gate_up"][0].shape[0]
        routed, _ = jax.lax.scan(
            expert, jnp.zeros_like(x),
            (jnp.arange(held), lp["moe_gate_up"], lp["moe_down"]))
        shared = _lower(relu2(hl @ dequantize(*lp["shared_gate_up"],
                                              sup_shape)), lower)
        shared = shared @ dequantize(*lp["shared_down"], sdown_shape)
        return _lower(x + routed + shared, lower), gap

    return jax.jit(block)


@functools.lru_cache(maxsize=8)
def _attn_fn(n_q: int, n_kv: int, d: int, eps: float, shapes: tuple, lower):
    import jax
    import jax.numpy as jnp

    qkv_shape, o_shape = shapes

    def block(x, lp):
        t = x.shape[0]
        h = _lower(_rms_norm(x, lp["attn_norm"], eps), lower)
        qkv = h @ dequantize(*lp["qkv"], qkv_shape)
        q = qkv[:, : n_q * d].reshape(t, n_q, d)
        k = qkv[:, n_q * d: (n_q + n_kv) * d].reshape(t, n_kv, d)
        v = qkv[:, (n_q + n_kv) * d:].reshape(t, n_kv, d)
        k = jnp.repeat(_lower(k, lower), n_q // n_kv, axis=1)
        v = jnp.repeat(_lower(v, lower), n_q // n_kv, axis=1)
        s = jnp.einsum("qhd,khd->hqk", q, k) / jnp.sqrt(jnp.float32(d))
        s = jnp.where(jnp.tril(jnp.ones((t, t), bool))[None], s, -jnp.inf)
        a = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, -1), v)
        a = _lower(a.reshape(t, n_q * d), lower)
        return _lower(x + a @ dequantize(*lp["o"], o_shape), lower)

    return jax.jit(block)


@functools.lru_cache(maxsize=8)
def _head_fn(eps: float, shape: tuple):
    import jax

    def head(x, norm, plane):
        logits = _rms_norm(x, norm, eps) @ dequantize(*plane, shape)
        return jax.nn.log_softmax(logits, -1)

    return jax.jit(head)


def _block_tree(stack: dict, i: int) -> dict:
    """Block ``i`` of a stacked tree, QTensors as their (data, scales)."""
    out = {}
    for name, leaf in stack.items():
        if hasattr(leaf, "scales"):
            out[name] = (leaf.data[i], leaf.scales[i])
        else:
            out[name] = leaf[i]
    return out


def logprobs(hf_config: dict, params: dict, ids: list[int], lower=None):
    """(log-softmax of the next-token logits at every position of ``ids``
    over the held vocabulary, ``[T, vocab]`` float32; each position's
    narrowest router gap over the expert blocks, ``[T]``), as numpy.

    ``lower="fp8"`` computes the same model with its activations (the
    residual stream after every block, every mixer's input, the experts'
    inner activations, attention's keys, values and output) and its
    recurrent state passed through float8_e4m3: the precision below the
    configuration's, for the reading that has to fail ``compare``.  ``lower="bf16"`` passes the same places
    through bfloat16: what rounding alone, at the configuration's own
    precision, does to this model's log-probabilities."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    hf = hf_config
    eps = float(hf.get("layer_norm_epsilon", hf.get("norm_eps", 1e-5)))
    heads, hd = int(hf["mamba_num_heads"]), int(hf["mamba_head_dim"])
    groups, n = int(hf["n_groups"]), int(hf["ssm_state_size"])
    if int(hf.get("n_group", 1)) != 1 or int(hf.get("topk_group", 1)) != 1:
        raise ValueError("this reference has no grouped routing")
    if not hf.get("norm_topk_prob", True):
        raise ValueError("this reference normalizes the chosen weights")
    tree = params["hybrid"]
    schedule = parse_pattern(hf["hybrid_override_pattern"])
    if len(schedule) != int(hf["num_hidden_layers"]):
        raise ValueError("the pattern's length is not num_hidden_layers")

    def shapes(kind, *names):
        return tuple(tuple(tree[kind][m].shape) for m in names)

    mamba = _mamba_fn(heads, hd, groups, n, eps,
                      shapes("mamba", "in_proj", "out_proj"), lower)
    moe = _moe_fn(int(hf["num_experts_per_tok"]),
                  int(hf.get("first_routed_expert", 0)),
                  float(hf["routed_scaling_factor"]), eps,
                  shapes("moe", "moe_gate_up", "moe_down", "shared_gate_up",
                         "shared_down"), lower)
    attn = _attn_fn(int(hf["num_attention_heads"]),
                    int(hf["num_key_value_heads"]), int(hf["head_dim"]), eps,
                    shapes("attn", "qkv", "o"), lower)
    head = params["lm_head"]
    t = len(ids)
    tie = jnp.full((t,), jnp.inf)
    with jax.default_matmul_precision("highest"):
        x = params["embed"][jnp.asarray(ids, jnp.int32)].astype(jnp.float32)
        for kind, i in schedule:
            if kind == "M":
                x = mamba(x, _block_tree(tree["mamba"], i))
            elif kind == "E":
                x, gap = moe(x, _block_tree(tree["moe"], i))
                tie = jnp.minimum(tie, gap)
            else:
                x = attn(x, _block_tree(tree["attn"], i))
        out = _head_fn(eps, tuple(head.shape))(
            x, params["final_norm"], _plane(head))
    return np.asarray(out, np.float32), np.asarray(tie, np.float32)


def compare(ref, prompt_len: int, tokens: list[int],
            server_logprobs: list[float]) -> dict:
    """Per emitted position: the server's chosen-token log-probability
    against the reference's, and how far the chosen token's reference logit
    trails the reference's argmax.  The largest of each over the positions
    whose narrowest router gap is at least ROUTER_TIE, and the median error
    over all of them."""
    import numpy as np

    rows, tie = ref
    errs, margins, gaps = [], [], []
    for j, (tok, lp) in enumerate(zip(tokens, server_logprobs)):
        row = rows[prompt_len - 1 + j]
        errs.append(abs(float(row[tok]) - float(lp)))
        margins.append(float(np.max(row) - row[tok]))
        gaps.append(float(tie[prompt_len - 1 + j]))
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
            "logprob_tol": LOGPROB_TOL, "margin_tol": ARGMAX_MARGIN_TOL,
            "median_tol": MEDIAN_TOL, "router_tie": ROUTER_TIE,
            "errors": [round(e, 4) for e in errs],
            "gaps": [round(g, 6) for g in gaps]}
