"""Build the decoder param pytree from a weight source.

Replaces the reference's ``ggml_convert_low_bit`` module-tree walk
(convert.py:1092, ``_replace_with_low_bit_linear`` convert.py:472): instead of
mutating a torch model in place, we *construct* the JAX param pytree directly
from any name->tensor source (safetensors reader, a torch state_dict, a GGUF
file), merging QKV / gate-up before quantization and stacking layers for the
scan-based decoder.
"""

from __future__ import annotations

from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np

from ipex_llm_tpu.hostutil import PARALLEL_MIN_ELEMS, for_row_blocks
from ipex_llm_tpu.models.config import ModelConfig
from ipex_llm_tpu.models.families import WeightScheme
from ipex_llm_tpu.quantize import core as qcore
from ipex_llm_tpu.quantize.core import QTensor
from ipex_llm_tpu.quantize.qtypes import resolve as qtypes_resolve

NORM_DTYPE = jnp.float32


def _transposed(w: np.ndarray) -> np.ndarray:
    """HF ``[out, in]`` -> contiguous ``[in, out]``.  A strided copy of
    10^8 elements on one core is most of what is left of a 7B load once the
    quantizer is native, so large weights are copied in row blocks by a
    thread pool."""
    if w.size < PARALLEL_MIN_ELEMS:
        return np.ascontiguousarray(w.T)
    out = np.empty(w.shape[::-1], w.dtype)
    for_row_blocks(out.shape[0],
                   lambda _, lo, hi: np.copyto(out[lo:hi], w.T[lo:hi]))
    return out


def quantize_weight(w: np.ndarray, qtype: str,
                    imatrix: np.ndarray | None = None) -> QTensor:
    """Quantize one HF-layout [out, in] weight to a [in, out] QTensor.

    ``mixed_fp4``/``mixed_fp8`` implement the reference's
    Mixture-of-Formats policy (ggml/quantize.py:36-37): try the float format
    and the int format, keep whichever reconstructs this tensor better.
    ``imatrix`` is a per-input-channel importance vector enabling the
    reference's weighted quantization (ggml_quantize_tensor_with_weights).
    """
    wt = _transposed(w)
    if qtype in ("mixed_fp4", "mixed_fp8"):
        fp = "fp4" if qtype == "mixed_fp4" else "fp8_e4m3"
        alt = "sym_int4" if qtype == "mixed_fp4" else "sym_int8"
        # importance weights both the candidate codecs (where their kind
        # supports it) and the format-pick metric itself
        imw = (jnp.asarray(imatrix, jnp.float32)[:, None]
               if imatrix is not None else 1.0)
        cand = []
        for q in (fp, alt):
            qt = qcore.quantize(wt, q, imatrix=imatrix)
            err = float(jnp.mean(
                imw * (qcore.dequantize(qt) - jnp.asarray(wt)) ** 2))
            cand.append((err, qt))
        return min(cand, key=lambda c: c[0])[1]
    return qcore.quantize(wt, qtype, imatrix=imatrix)


def _imx(imatrix_data, layer: int, slot: str, expert: int | None = None):
    from ipex_llm_tpu.quantize.imatrix import slot_importance

    return slot_importance(imatrix_data, layer, slot, expert)


def stack_layer_trees(trees: list[dict[str, Any]]) -> dict[str, Any]:
    """Stack per-layer pytrees (QTensor-aware) along a new leading axis."""
    return jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *trees)


# kinds requantize_params can re-pack a native-width weight into (what
# quantize/core.py ships a codec for; kquant is import/dequant-only)
_REQUANT_KINDS = ("int_sym", "int_asym", "codebook", "minifloat", "iquant")


def _requant_leaf(qt: QTensor, qtype: str, imatrix=None) -> QTensor:
    """Re-pack one native-width QTensor — per-layer 2-D or stacked with any
    leading axes ([L, in, out] layer stacks, [L, E, in, out] expert stacks)
    — into the block-quantized format ``qtype``.

    Each logical ``[in, out]`` matrix quantizes independently through
    ``quantize/core.quantize`` (the exact codec a load-time
    ``load_in_low_bit`` build uses, here over the tree's stored — i.e.
    bf16-rounded — values), then the packed planes restack along the
    original leading axes.  ``imatrix`` is a
    per-input-channel importance vector shared by every matrix in the
    stack (callers with per-layer calibration pass a callable
    ``imatrix(i)`` over the flattened leading index)."""
    lead = qt.data.shape[:-2]
    flat = qt.data.reshape((-1,) + tuple(qt.shape))
    n = flat.shape[0]
    qts = []
    for i in range(n):
        im = imatrix(i) if callable(imatrix) else imatrix
        qts.append(qcore.quantize(flat[i], qtype, imatrix=im))
    q0 = qts[0]

    def restack(leaves):
        s = jnp.stack(leaves) if n > 1 else leaves[0][None]
        return s.reshape(lead + s.shape[1:]) if lead else s[0]

    data = restack([q.data for q in qts])
    scales = (restack([q.scale_bits for q in qts])
              if q0.scale_bits is not None else None)
    zeros = (restack([q.zeros for q in qts])
             if q0.zeros is not None else None)
    return QTensor(data, scales, zeros, q0.qtype, qt.shape, q0.block_size,
                   qt.tp_mode)


def requantize_params(params: dict[str, Any], qtype: str,
                      imatrix_data: dict | None = None) -> dict[str, Any]:
    """Re-pack every native-width (bf16/fp16) linear QTensor in a built
    param tree as block-quantized ``qtype`` planes — the serving engine's
    ``EngineConfig.weight_qtype`` axis (reference ``load_in_low_bit``, but
    applied AFTER build so an engine can low-bit a tree that was loaded or
    fabricated full-width).

    Only QTensor leaves re-pack: plain arrays (embed table, norms, biases,
    rope buffers) keep their width, and already-quantized leaves (a tree
    loaded with ``load_in_low_bit="sym_int4"``) pass through untouched —
    requantizing packed codes would stack quantization error, so a
    different requested width on an already-low-bit tree is a no-op, not a
    lossy rewrite.  ``imatrix_data`` is the llama.cpp importance-matrix
    dict (quantize/imatrix.py), keyed "{layer}_{slot}" (+"_{expert}" for
    MoE): layer stacks index it by stack position, and expert stacks
    ``[L, E, ...]`` decompose the flat index into (layer, expert) — the
    same keys the load-time build uses, so calibrated serving repacks
    match calibrated loads."""
    info = qtypes_resolve(qtype)
    if info.kind == "native":
        return params
    packable = info.kind in _REQUANT_KINDS

    from ipex_llm_tpu.quantize.imatrix import slot_importance

    def walk(tree, path=()):
        if isinstance(tree, dict):
            return {k: walk(v, path + (k,)) for k, v in tree.items()}
        if isinstance(tree, QTensor):
            if qtypes_resolve(tree.qtype).kind != "native":
                return tree   # already low-bit: pass through
            if not packable:
                # only an error when a full-width leaf actually needs the
                # missing codec: an already-packed tree (kquant GGUF
                # import served with --low-bit q4_k) passes through above
                raise ValueError(
                    f"weight_qtype={qtype!r} (kind={info.kind}) has no "
                    f"requantize codec for full-width weight "
                    f"{'.'.join(map(str, path))}; pick a block format "
                    f"(kinds {_REQUANT_KINDS}) or a native width")
            im = None
            if imatrix_data is not None and path:
                slot = path[-1]
                lead = tree.data.shape[:-2]
                if len(lead) >= 2:
                    # [L, E, ...] expert stacks: flat index i decomposes
                    # row-major into (layer, expert), and the tree key
                    # ("moe_gate_up") maps back onto the load-time slot
                    # ("gate_up" + expert — build_params' _imx keys)
                    s = slot[4:] if slot.startswith("moe_") else slot
                    ne = 1
                    for d in lead[1:]:
                        ne *= d
                    im = lambda i, s=s, n=ne: slot_importance(  # noqa: E731
                        imatrix_data, i // n, s, i % n)
                elif lead:                # [L, ...] layer stacks
                    im = lambda i, s=slot: slot_importance(  # noqa: E731
                        imatrix_data, i, s)
                else:                     # lm_head & friends: no layer key
                    im = slot_importance(imatrix_data, 0, slot)
            return _requant_leaf(tree, info.name, imatrix=im)
        return tree

    return walk(params)


def dequantize_params(params: dict[str, Any],
                      dtype=jnp.bfloat16) -> dict[str, Any]:
    """Full-width twin of a param tree: every block-quantized QTensor
    replaced by its dequantized dense stack (plain arrays and
    native-width QTensors pass through).  The bitwise oracle the packed
    tree's qmatmul path is tested against."""

    def walk(tree):
        if isinstance(tree, dict):
            return {k: walk(v) for k, v in tree.items()}
        if not isinstance(tree, QTensor) \
                or qtypes_resolve(tree.qtype).kind == "native":
            return tree
        lead = tree.data.shape[:-2]
        n = 1
        for d in lead:
            n *= d

        def plane(leaf, i):
            return (None if leaf is None
                    else leaf.reshape((n,) + leaf.shape[len(lead):])[i])

        flat = [qcore.dequantize(
                    QTensor(plane(tree.data, i), plane(tree.scale_bits, i),
                            plane(tree.zeros, i), tree.qtype, tree.shape,
                            tree.block_size), dtype=dtype)
                for i in range(n)]
        stacked = jnp.stack(flat)
        return stacked.reshape(lead + flat[0].shape) if lead else stacked[0]

    return walk(params)


def param_bytes(params: dict[str, Any]) -> tuple[int, int]:
    """(packed_bytes, dense_bytes) for a param tree: what the tree costs
    in HBM as stored, vs what the same tree would cost with every QTensor
    at bf16 full width (non-QTensor leaves count identically on both
    sides).  The byte axis /health's ``weights`` block reports."""
    packed = dense = 0
    for leaf in jax.tree_util.tree_leaves(
            params, is_leaf=lambda x: isinstance(x, QTensor)):
        if isinstance(leaf, QTensor):
            packed += leaf.nbytes
            n_mats = 1
            for d in leaf.data.shape[:-2]:
                n_mats *= d
            dense += n_mats * leaf.in_features * leaf.out_features * 2
        elif hasattr(leaf, "nbytes"):
            packed += int(leaf.nbytes)
            dense += int(leaf.nbytes)
    return packed, dense


def build_hybrid_params(cfg: ModelConfig, get: Callable[[str], np.ndarray],
                        qtype: str = "sym_int4", head_qtype: str | None = None,
                        progress: Callable[[str], None] | None = None
                        ) -> dict[str, Any]:
    """The tree of a hybrid model (``cfg.hybrid_pattern``; nemotron_h,
    falcon_h1): one stack a block KIND under ``params["hybrid"]`` (``mamba``
    ``[M blocks, ...]``, ``moe`` ``[E blocks, experts held, ...]``, ``attn``
    ``[* blocks, ...]``; ``parallel`` ``[P blocks, ...]``: Falcon-H1's
    attention, Mamba-2 mixer and gated MLP of one block, under the names the
    three have alone), which ``models/decoder.py`` walks in the pattern's
    order.  An expert layer holds ``cfg.moe_experts_held`` experts from
    ``cfg.moe_first_expert`` on and the whole router; embedding and head
    hold the first ``cfg.vocab_size`` rows.  Every 4-bit plane is padded
    here, once, to the dequant-matmul kernel's tiles
    (``quantize/core.pad_planes``), so no call pads a weight."""
    # the checkpoint's names: Nemotron-H's ``backbone.``, Falcon-H1's ``model.``
    if set(cfg.hybrid_pattern) == {"P"}:
        pre, embed, final_norm = ("model.layers.{i}.",
                                  "model.embed_tokens.weight",
                                  "model.final_layernorm.weight")
    else:
        pre, embed, final_norm = ("backbone.layers.{i}.",
                                  "backbone.embeddings.weight",
                                  "backbone.norm_f.weight")

    def packed(w: np.ndarray, as_qtype: str = qtype) -> QTensor:
        return qcore.pad_planes(quantize_weight(w, as_qtype))

    def q(name: str) -> QTensor:
        return packed(get(name))

    def f32(name: str):
        return jnp.asarray(get(name), NORM_DTYPE)

    kinds: dict[str, list[dict[str, Any]]] = {"mamba": [], "moe": [],
                                              "attn": [], "parallel": []}
    for i, kind in enumerate(cfg.hybrid_pattern):
        if progress:
            progress(f"layer {i + 1}/{cfg.num_layers}")
        if kind == "P":
            blk = pre.format(i=i)
            a, f = blk + "self_attn.", blk + "feed_forward."
            kinds["parallel"].append({
                "attn_norm": f32(blk + "input_layernorm.weight"),
                "qkv": packed(np.concatenate(
                    [get(a + f"{p}_proj.weight") for p in "qkv"], axis=0)),
                "o": q(a + "o_proj.weight"),
                **_mamba_tensors(get, blk + "mamba.", packed),
                "mlp_norm": f32(blk + "pre_ff_layernorm.weight"),
                "gate_up": packed(np.concatenate(
                    [get(f + "gate_proj.weight"), get(f + "up_proj.weight")],
                    axis=0)),
                "down": q(f + "down_proj.weight")})
            continue
        m = pre.format(i=i) + "mixer."
        norm = f32(pre.format(i=i) + "norm.weight")
        if kind == "M":
            kinds["mamba"].append(
                {"norm": norm, **_mamba_tensors(get, m, packed)})
        elif kind == "E":
            first, held = cfg.moe_first_expert, cfg.moe_experts_held
            experts = range(first, first + held)
            kinds["moe"].append({
                "mlp_norm": norm,
                "router": jnp.asarray(
                    np.ascontiguousarray(get(m + "gate.weight").T),
                    jnp.float32),
                "router_bias": f32(m + "gate.e_score_correction_bias"),
                "moe_gate_up": stack_layer_trees(
                    [q(m + f"experts.{e}.up_proj.weight") for e in experts]),
                "moe_down": stack_layer_trees(
                    [q(m + f"experts.{e}.down_proj.weight")
                     for e in experts]),
                "shared_gate_up": q(m + "shared_experts.up_proj.weight"),
                "shared_down": q(m + "shared_experts.down_proj.weight")})
        else:
            qkv = np.concatenate([get(m + f"{p}_proj.weight")
                                  for p in "qkv"], axis=0)
            kinds["attn"].append({
                "attn_norm": norm,
                "qkv": packed(qkv),
                "o": q(m + "o_proj.weight")})
    params: dict[str, Any] = {
        "hybrid": {k: stack_layer_trees(v) for k, v in kinds.items() if v},
        "embed": jnp.asarray(get(embed)[: cfg.vocab_size], jnp.bfloat16),
        "final_norm": f32(final_norm),
    }
    if not cfg.tie_word_embeddings:
        params["lm_head"] = packed(
            get("lm_head.weight")[: cfg.vocab_size], head_qtype or qtype)
    if cfg.rope is not None:
        params["inv_freq"] = jnp.asarray(
            cfg.rope.inv_freq(cfg.max_position_embeddings), jnp.float32)
        params["rope_mscale"] = float(
            cfg.rope.mscale(cfg.max_position_embeddings))
    return params


def _mamba_tensors(get, m: str, packed) -> dict:
    """One Mamba-2 mixer's tensors as ops/ssm.py reads them, from the
    checkpoint names under the prefix ``m``."""
    def f32(name: str):
        return jnp.asarray(get(m + name), NORM_DTYPE)

    conv_w = np.asarray(get(m + "conv1d.weight"))[:, 0, :]           # [C, K]
    return {"in_proj": packed(get(m + "in_proj.weight")),
            "conv_w": jnp.asarray(conv_w.T, NORM_DTYPE),             # [K, C]
            "conv_b": f32("conv1d.bias"), "dt_bias": f32("dt_bias"),
            "A_log": f32("A_log"), "D": f32("D"),
            "gate_norm": f32("norm.weight"),
            "out_proj": packed(get(m + "out_proj.weight"))}


def _kda_tensors(get, pre: str, qtype: str) -> dict:
    """One Kimi Delta Attention mixer's tensors as ops/kda.py reads them,
    from the checkpoint names under the prefix ``pre``: ``in_proj`` = the
    q, k and v projections, the two gates' down projections and the write
    strength's, side by side [hidden, 3 H D + D + D + H]; ``conv_w`` [K, 3 H
    D] (tap j weighs the input K-1-j positions back; q's, k's, v's channels
    side by side); the gates' up projections ``f_b``, ``g_b`` [D, H D];
    ``A_log`` [H], ``dt_bias`` [H D], the per-head output norm ``o_norm``
    [D] and ``o``."""
    def f32(name: str):
        return jnp.asarray(get(pre + name), NORM_DTYPE)

    def q(name: str):
        return quantize_weight(get(pre + name), qtype)

    ins = ("q_proj", "k_proj", "v_proj", "f_a_proj", "g_a_proj", "b_proj")
    conv = np.concatenate([np.asarray(get(pre + f"{p}_conv1d.weight"))[:, 0]
                           for p in "qkv"], axis=0)                # [C, K]
    return {"in_proj": quantize_weight(np.concatenate(
                [get(pre + p + ".weight") for p in ins], axis=0), qtype),
            "conv_w": jnp.asarray(conv.T, NORM_DTYPE),
            "f_b": q("f_b_proj.weight"), "g_b": q("g_b_proj.weight"),
            "A_log": f32("A_log"), "dt_bias": f32("dt_bias"),
            "o_norm": f32("o_norm.weight"), "o": q("o_proj.weight")}


def _hyper_connection(get, i: int, sub: str) -> dict:
    """One sublayer's hyper-connection tensors as ops/hyper.py reads them:
    ``hc_<sub>_proj`` float32 [n + n + n*n, n*C] (rows: the pre-mix's, the
    post-mix's, the residual mix's row-major; columns: stream-major, as
    ``vec(X)``), ``hc_<sub>_scale`` [3] and ``hc_<sub>_bias`` [n + n + n*n]
    in the same order.  Checkpoint names
    ``model.layers.{i}.{attn,mlp}_hc.{pre,post,res}_{proj.weight,scale,bias}``
    (``proj.weight`` a Linear's [out, n*C]; ``res_bias`` [n, n])."""
    pre = f"model.layers.{i}.{sub}_hc."
    parts = ("pre", "post", "res")
    return {
        f"hc_{sub}_proj": jnp.asarray(np.concatenate(
            [get(pre + p + "_proj.weight") for p in parts], axis=0),
            jnp.float32),
        f"hc_{sub}_scale": jnp.asarray(np.stack(
            [np.asarray(get(pre + p + "_scale")).reshape(()) for p in parts]),
            jnp.float32),
        f"hc_{sub}_bias": jnp.asarray(np.concatenate(
            [np.asarray(get(pre + p + "_bias")).reshape(-1) for p in parts]),
            jnp.float32),
    }


def build_params(
    cfg: ModelConfig,
    scheme: WeightScheme,
    get: Callable[[str], np.ndarray],
    has: Callable[[str], bool],
    qtype: str = "sym_int4",
    lm_head_qtype: str | None = None,
    mixed_precision: bool = False,
    progress: Callable[[str], None] | None = None,
    moe_scheme=None,
    embedding_qtype: str | None = None,
    qkv_transform: Callable | None = None,
    transpose_weights: bool = False,
    imatrix_data: dict | None = None,
) -> dict[str, Any]:
    """Assemble the full decoder param pytree, quantizing as it streams.

    mixed_precision mirrors the reference's flag (model.py kwargs): quantize
    the lm_head at sym_int8 rather than the 4-bit body qtype.
    """

    if cfg.hybrid_pattern is not None:
        return build_hybrid_params(
            cfg, get, qtype=qtype,
            head_qtype=lm_head_qtype or ("sym_int8" if mixed_precision
                                         else qtype),
            progress=progress)

    def name(t: str | None, i: int | None = None, p: str = "weight") -> str | None:
        if t is None:
            return None
        return t.format(i=i, p=p)

    def getp(n: str) -> np.ndarray:
        """Projection-weight getter: gpt2-style Conv1D checkpoints store
        [in, out] and transpose here back to the HF Linear [out, in]."""
        w = get(n)
        return np.ascontiguousarray(w.T) if transpose_weights else w

    def get_opt(n: str | None) -> np.ndarray | None:
        if n is None or not has(n):
            return None
        return get(n)

    def norm_with_bias(lp: dict, key: str, tmpl: str | None, i: int | None,
                       required: bool = False):
        if tmpl is not None and "|" in tmpl:
            # "a|b" templates: families whose checkpoints use either name
            # (falcon old/new decoder architecture)
            for alt in tmpl.split("|"):
                if has(alt.format(i=i)):
                    tmpl = alt
                    break
            else:
                tmpl = tmpl.split("|")[0]
        n = name(tmpl, i)
        if n is None or (not required and not has(n)):
            return
        lp[key] = jnp.asarray(get(n), NORM_DTYPE)
        bias_name = n[: -len(".weight")] + ".bias" if n.endswith(".weight") else None
        if bias_name is not None and has(bias_name):
            lp[key + "_bias"] = jnp.asarray(get(bias_name), NORM_DTYPE)

    layers = []
    # a model whose mixer kind varies by layer (cfg.mixer_types): layer ->
    # (kind, that mixer's tensors), stacked per kind (``stack_of``)
    kind_trees: dict[int, tuple[str, dict]] = {}
    for i in range(cfg.num_layers):
        if progress:
            progress(f"layer {i + 1}/{cfg.num_layers}")
        lp: dict[str, Any] = {}
        norm_with_bias(lp, "attn_norm", scheme.attn_norm, i, required=True)
        norm_with_bias(lp, "mlp_norm", scheme.mlp_norm, i, required=True)
        for key, tmpl in (
            ("post_attn_norm", scheme.post_attn_norm),
            ("post_mlp_norm", scheme.post_mlp_norm),
            ("q_norm", scheme.q_norm),
            ("k_norm", scheme.k_norm),
        ):
            t = get_opt(name(tmpl, i))
            if t is not None:
                lp[key] = jnp.asarray(t, NORM_DTYPE)

        # --- a layer whose mixer is Kimi Delta Attention (cfg.mixer_types):
        # its tensors go to the ``kda`` stack, and it has no attention
        kda_here = (cfg.mixer_types is not None
                    and cfg.mixer_types[i] == "kda")
        if kda_here:
            kind_trees[i] = ("kda", _kda_tensors(
                get, f"model.layers.{i}.linear_attn.", qtype))
        # --- MLA (deepseek): low-rank q + compressed-kv projections; no
        # qkv merge possible (kv_b applies to the compressed latent)
        elif scheme.kv_a is not None and cfg.is_mla:
            if cfg.q_lora_rank is None:
                lp["q"] = quantize_weight(get(name(scheme.q, i)), qtype)
                qb = get_opt(name(scheme.q, i, "bias"))
                if qb is not None:
                    lp["q_bias"] = jnp.asarray(qb, jnp.float32)
            else:
                lp["q_a"] = quantize_weight(get(name(scheme.q_a, i)), qtype)
                qab = get_opt(name(scheme.q_a, i, "bias"))
                if qab is not None:
                    lp["q_a_bias"] = jnp.asarray(qab, jnp.float32)
                lp["q_a_norm"] = jnp.asarray(
                    get(name(scheme.q_a_norm, i)), NORM_DTYPE
                )
                lp["q_b"] = quantize_weight(get(name(scheme.q_b, i)), qtype)
            lp["kv_a"] = quantize_weight(get(name(scheme.kv_a, i)), qtype)
            kab = get_opt(name(scheme.kv_a, i, "bias"))
            if kab is not None:
                lp["kv_a_bias"] = jnp.asarray(kab, jnp.float32)
            lp["kv_a_norm"] = jnp.asarray(
                get(name(scheme.kv_a_norm, i)), NORM_DTYPE
            )
            lp["kv_b"] = quantize_weight(get(name(scheme.kv_b, i)), qtype)
        # --- qkv (merge like reference _optimize_pre merge_qkv, convert.py:890)
        elif scheme.qkv is not None:
            qkv_w = getp(name(scheme.qkv, i))
            qkv_b = get_opt(name(scheme.qkv, i, "bias"))
            if qkv_transform is not None:
                # family-specific packed layout (gpt-neox interleave,
                # internlm2 grouped wqkv) -> [q; k; v] concat order
                qkv_w = qkv_transform(qkv_w, cfg)
                if qkv_b is not None:
                    qkv_b = qkv_transform(qkv_b[:, None], cfg)[:, 0]
        else:
            qw = getp(name(scheme.q, i))
            kw = getp(name(scheme.k, i))
            vw = getp(name(scheme.v, i))
            bs = [get_opt(name(t, i, "bias")) for t in (scheme.q, scheme.k, scheme.v)]
            if cfg.kv_heads_per_layer is not None:
                # decilm variable GQA: replicate this layer's kv heads up to
                # the uniform cache width (exact for grouped-query attention)
                src = cfg.kv_heads_per_layer[i]
                r = cfg.num_kv_heads // src
                if r > 1:
                    def _expand(w):
                        if w is None:
                            return None
                        shape1 = w.shape[1:]
                        x = w.reshape(src, cfg.head_dim, -1)
                        return np.repeat(x, r, axis=0).reshape(
                            (src * r * cfg.head_dim,) + shape1)
                    kw, vw = _expand(kw), _expand(vw)
                    bs = [bs[0]] + [
                        None if b is None else _expand(b[:, None])[:, 0]
                        for b in bs[1:]
                    ]
            qkv_w = np.concatenate([qw, kw, vw], axis=0)  # [out_total, in]
            qkv_b = np.concatenate(bs) if bs[0] is not None else None
        if not (scheme.kv_a is not None and cfg.is_mla) and not kda_here:
            lp["qkv"] = quantize_weight(
                qkv_w, qtype, imatrix=_imx(imatrix_data, i, "qkv"))
            if qkv_b is not None:
                lp["qkv_bias"] = jnp.asarray(qkv_b, jnp.float32)

        if not kda_here:
            ow = getp(name(scheme.o, i))
            lp["o"] = quantize_weight(ow, qtype,
                                      imatrix=_imx(imatrix_data, i, "o"))
            ob = get_opt(name(scheme.o, i, "bias"))
            if ob is not None:
                lp["o_bias"] = jnp.asarray(ob, jnp.float32)
            if cfg.mixer_types is not None:
                # the attention's tensors go to the ``mla`` stack
                kind_trees[i] = ("mla", {k_: lp.pop(k_) for k_ in list(lp)
                                         if k_ not in ("attn_norm",
                                                       "mlp_norm")})

        # --- MoE block (mixtral/qwen-moe): per-expert QTensors stacked on a
        # leading E axis, scanned (or ep-sharded) in the decoder
        if cfg.layer_is_moe(i):
            if moe_scheme is None:
                raise ValueError(
                    f"model has {cfg.num_experts} experts but the family "
                    "declares no MoE weight scheme"
                )
            rw = get(moe_scheme.router.format(i=i))          # [E, hidden]
            lp["router"] = jnp.asarray(np.ascontiguousarray(rw.T), jnp.float32)
            if moe_scheme.score_bias is not None:
                lp["router_bias"] = jnp.asarray(
                    get(moe_scheme.score_bias.format(i=i)), jnp.float32
                )
            e_gu, e_down, e_ub, e_db = [], [], [], []
            # a held layer (``cfg.moe_experts_held``) holds a chip's share
            # of the experts, from ``cfg.moe_first_expert`` on, and the
            # whole router
            held = (range(cfg.moe_first_expert,
                          cfg.moe_first_expert + cfg.moe_experts_held)
                    if cfg.moe_experts_held else range(cfg.num_experts))
            for e in held:
                uw = get(moe_scheme.e_up.format(i=i, e=e))
                dw = get(moe_scheme.e_down.format(i=i, e=e))
                if moe_scheme.e_gate is not None:
                    gw = get(moe_scheme.e_gate.format(i=i, e=e))
                    fused = np.concatenate([gw, uw], 0)
                else:  # non-gated experts (phixtral fc1 -> act -> fc2)
                    fused = uw
                e_gu.append(quantize_weight(
                    fused, qtype,
                    imatrix=_imx(imatrix_data, i, "gate_up", e)))
                e_down.append(quantize_weight(
                    dw, qtype, imatrix=_imx(imatrix_data, i, "down", e)))
                ubn = moe_scheme.e_up.format(i=i, e=e)[: -len(".weight")]                     + ".bias"
                dbn = moe_scheme.e_down.format(i=i, e=e)[: -len(".weight")]                     + ".bias"
                if has(ubn):
                    e_ub.append(jnp.asarray(get(ubn), jnp.float32))
                if has(dbn):
                    e_db.append(jnp.asarray(get(dbn), jnp.float32))
            lp["moe_gate_up"] = stack_layer_trees(e_gu)
            lp["moe_down"] = stack_layer_trees(e_down)
            if e_ub:
                lp["moe_up_bias"] = jnp.stack(e_ub)      # [E, I(2I)]
            if e_db:
                lp["moe_down_bias"] = jnp.stack(e_db)    # [E, H]
            if moe_scheme.shared_gate is not None:
                sg = get(moe_scheme.shared_gate.format(i=i))
                su = get(moe_scheme.shared_up.format(i=i))
                sd = get(moe_scheme.shared_down.format(i=i))
                lp["shared_gate_up"] = quantize_weight(
                    np.concatenate([sg, su], 0), qtype
                )
                lp["shared_down"] = quantize_weight(sd, qtype)
                if moe_scheme.shared_router is not None:
                    srw = get(moe_scheme.shared_router.format(i=i))  # [1, h]
                    lp["shared_router"] = jnp.asarray(
                        np.ascontiguousarray(srw.T), jnp.float32
                    )
            layers.append(lp)
            continue

        # --- non-gated mlp (phi/gpt-neox/starcoder2: fc1 -> act -> fc2)
        if scheme.gate_up is None and scheme.gate is None:
            lp["up"] = quantize_weight(getp(name(scheme.up, i)), qtype,
                                       imatrix=_imx(imatrix_data, i, "up"))
            ub = get_opt(name(scheme.up, i, "bias"))
            if ub is not None:
                lp["up_bias"] = jnp.asarray(ub, jnp.float32)
            lp["down"] = quantize_weight(
                getp(name(scheme.down, i)), qtype,
                imatrix=_imx(imatrix_data, i, "down"))
            db = get_opt(name(scheme.down, i, "bias"))
            if db is not None:
                lp["down_bias"] = jnp.asarray(db, jnp.float32)
            layers.append(lp)
            continue

        # --- mlp (merged gate_up)
        if scheme.gate_up is not None:
            gu_w = getp(name(scheme.gate_up, i))
            gu_b = get_opt(name(scheme.gate_up, i, "bias"))
        else:
            gw = getp(name(scheme.gate, i))
            uw = getp(name(scheme.up, i))
            gu_w = np.concatenate([gw, uw], axis=0)
            gb = get_opt(name(scheme.gate, i, "bias"))
            ub = get_opt(name(scheme.up, i, "bias"))
            gu_b = np.concatenate([gb, ub]) if gb is not None else None
        lp["gate_up"] = quantize_weight(
            gu_w, qtype, imatrix=_imx(imatrix_data, i, "gate_up"))
        if gu_b is not None:
            lp["gate_up_bias"] = jnp.asarray(gu_b, jnp.float32)
        lp["down"] = quantize_weight(
            getp(name(scheme.down, i)), qtype,
            imatrix=_imx(imatrix_data, i, "down"))
        db = get_opt(name(scheme.down, i, "bias"))
        if db is not None:
            lp["down_bias"] = jnp.asarray(db, jnp.float32)
        layers.append(lp)

    # hyper-connected residual streams (cfg.hc_mult > 1; ops/hyper.py): each
    # sublayer's three projections merged into ONE float32 plane, its
    # scalars and biases beside it.  Never quantized: they are the residual
    # path itself
    if cfg.hc_mult > 1:
        for i, lp in enumerate(layers):
            for sub in ("attn", "mlp"):
                lp.update(_hyper_connection(get, i, sub))

    # a model with an indexer (cfg.index_topk): the layers whose indexer is
    # "full" carry its weights, stacked for THOSE layers only and hung on
    # the stack that holds them (models/decoder.py ``run_layers``)
    indexers: dict[int, dict] = {}
    if cfg.index_topk:
        ix = "model.layers.{i}.self_attn.indexer."
        for i, kind in enumerate(cfg.indexer_types):
            if kind != "full":
                continue
            pre = ix.format(i=i)
            indexers[i] = {
                "wq_b": quantize_weight(get(pre + "wq_b.weight"), qtype),
                "wk": quantize_weight(get(pre + "wk.weight"), qtype),
                "k_norm": jnp.asarray(get(pre + "k_norm.weight"), NORM_DTYPE),
                "k_norm_bias": jnp.asarray(get(pre + "k_norm.bias"),
                                           NORM_DTYPE),
                "w_proj": jnp.asarray(np.ascontiguousarray(
                    get(pre + "weights_proj.weight").T), jnp.float32),
            }

    def stack_of(lo, hi):
        tree = stack_layer_trees(layers[lo:hi])
        mine = [indexers[i] for i in range(lo, hi) if i in indexers]
        if mine:
            tree["indexer"] = stack_layer_trees(mine)
        for kind in ("kda", "mla"):
            mine = [kind_trees[i][1] for i in range(lo, hi)
                    if kind_trees.get(i, ("",))[0] == kind]
            if mine:
                tree[kind] = stack_layer_trees(mine)
        return tree

    # deepseek-style dense prefix: the first ``moe_layer_start`` layers have
    # a plain MLP, the rest are MoE — two param stacks, two scans
    # (decoder_forward); each stack is still one compiled layer body
    if 0 < cfg.moe_layer_start < cfg.num_layers and cfg.num_experts > 0:
        params = {
            "layers_dense": stack_of(0, cfg.moe_layer_start),
            "layers": stack_of(cfg.moe_layer_start, cfg.num_layers),
        }
    else:
        params = {"layers": stack_of(0, cfg.num_layers)}
    if embedding_qtype and not cfg.tie_word_embeddings:
        # LowBitEmbedding equivalent (reference embedding.py:179): table
        # quantized [vocab, hidden] with vocab as the block axis; rows
        # dequantize at gather time (ops/embedding.py)
        params["embed"] = qcore.quantize(get(scheme.embed), embedding_qtype)
    else:
        # (the first ``cfg.vocab_size`` rows: all of them, or a chip's
        # share of a deployment that divides the vocabulary)
        params["embed"] = jnp.asarray(get(scheme.embed)[: cfg.vocab_size],
                                      jnp.bfloat16)
    if scheme.pos_embed is not None and has(scheme.pos_embed):
        pe = get(scheme.pos_embed)
        if cfg.learned_pos and pe.shape[0] > cfg.learned_pos:
            # OPT offsets learned positions by 2: slice the pad rows off
            pe = pe[pe.shape[0] - cfg.learned_pos :]
        params["pos_embed"] = jnp.asarray(pe, jnp.bfloat16)
    if scheme.embed_norm is not None and has(scheme.embed_norm):
        params["embed_norm"] = jnp.asarray(get(scheme.embed_norm), NORM_DTYPE)
        enb = scheme.embed_norm[: -len(".weight")] + ".bias"
        if has(enb):
            params["embed_norm_bias"] = jnp.asarray(get(enb), NORM_DTYPE)
    params["final_norm"] = jnp.asarray(get(scheme.final_norm), NORM_DTYPE)
    fn_bias = scheme.final_norm[: -len(".weight")] + ".bias"
    if scheme.final_norm.endswith(".weight") and has(fn_bias):
        params["final_norm_bias"] = jnp.asarray(get(fn_bias), NORM_DTYPE)

    if cfg.tie_word_embeddings:
        pass  # decoder uses embed.T
    else:
        head_q = lm_head_qtype or ("sym_int8" if mixed_precision else qtype)
        lm_w = get(scheme.lm_head)[: cfg.vocab_size]
        # reference is_lm_head mixed-precision rule (convert.py:126): keep
        # big-vocab heads at >=8 bit when mixed_precision is requested
        params["lm_head"] = quantize_weight(lm_w, head_q)
        head_bias = scheme.lm_head[: -len(".weight")] + ".bias"
        if scheme.lm_head.endswith(".weight") and has(head_bias):
            params["lm_head_bias"] = jnp.asarray(get(head_bias), jnp.float32)

    if cfg.moe_experts_held:
        # a held expert layer reads its stacks in place by (layer, expert)
        # through the grouped dequant-matmul kernel, whose tiles have to
        # cover the planes exactly: pad every 4-bit plane here, once
        params = jax.tree_util.tree_map(
            lambda a: qcore.pad_planes(a) if isinstance(a, QTensor) else a,
            params, is_leaf=lambda a: isinstance(a, QTensor))
    if cfg.rope is not None:
        params["inv_freq"] = jnp.asarray(
            cfg.rope.inv_freq(cfg.max_position_embeddings), jnp.float32
        )
        params["rope_mscale"] = float(cfg.rope.mscale(cfg.max_position_embeddings))
        if cfg.rope_local is not None:   # gemma3 sliding-layer table
            params["inv_freq_local"] = jnp.asarray(
                cfg.rope_local.inv_freq(cfg.max_position_embeddings),
                jnp.float32,
            )
    return params
