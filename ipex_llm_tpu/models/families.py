"""Model-family registry: HF config → ModelConfig + weight naming scheme.

This is the TPU-native replacement for the reference's per-``model_type``
dispatch (convert.py:1275 ``_optimize_post``, 79 branches) and per-model
``merge_qkv`` rewrites (`_optimize_pre`, convert.py:890): each family is a
small declarative entry — config normalization plus weight-name templates —
feeding the ONE shared decoder (models/decoder.py).  QKV and gate/up merges
happen here at load time, before quantization, so each transformer layer runs
exactly three quantized matmuls plus attention.
"""

from __future__ import annotations

import math
from dataclasses import dataclass, field, replace
from typing import Callable

import numpy as np

from ipex_llm_tpu.models.config import ModelConfig
from ipex_llm_tpu.ops.rope import RopeScaling


@dataclass(frozen=True)
class WeightScheme:
    """Weight-name templates ({i} = layer index). None = not present."""

    embed: str = "model.embed_tokens.weight"
    final_norm: str = "model.norm.weight"
    lm_head: str = "lm_head.weight"
    attn_norm: str = "model.layers.{i}.input_layernorm.weight"
    mlp_norm: str = "model.layers.{i}.post_attention_layernorm.weight"
    post_attn_norm: str | None = None
    post_mlp_norm: str | None = None
    q: str | None = "model.layers.{i}.self_attn.q_proj.{p}"
    k: str | None = "model.layers.{i}.self_attn.k_proj.{p}"
    v: str | None = "model.layers.{i}.self_attn.v_proj.{p}"
    qkv: str | None = None      # pre-merged (phi3 / chatglm / baichuan W_pack)
    o: str = "model.layers.{i}.self_attn.o_proj.{p}"
    gate: str | None = "model.layers.{i}.mlp.gate_proj.{p}"
    up: str | None = "model.layers.{i}.mlp.up_proj.{p}"
    gate_up: str | None = None  # pre-merged (phi3)
    down: str = "model.layers.{i}.mlp.down_proj.{p}"
    q_norm: str | None = None
    k_norm: str | None = None
    pos_embed: str | None = None   # learned absolute positions (gpt2 wpe)
    embed_norm: str | None = None  # bloom word_embeddings_layernorm
    # MLA (deepseek): q (or q_a/q_b low-rank pair), kv_a, kv_b replace q/k/v
    q_a: str | None = None
    q_a_norm: str | None = None
    q_b: str | None = None
    kv_a: str | None = None
    kv_a_norm: str | None = None
    kv_b: str | None = None


@dataclass(frozen=True)
class MoEScheme:
    """MoE weight-name templates ({i} = layer, {e} = expert)."""

    router: str = "model.layers.{i}.mlp.gate.weight"
    e_gate: str = "model.layers.{i}.mlp.experts.{e}.gate_proj.weight"
    e_up: str = "model.layers.{i}.mlp.experts.{e}.up_proj.weight"
    e_down: str = "model.layers.{i}.mlp.experts.{e}.down_proj.weight"
    shared_gate: str | None = None
    shared_up: str | None = None
    shared_down: str | None = None
    shared_router: str | None = None  # qwen2-moe shared_expert_gate
    score_bias: str | None = None     # deepseek-v3 e_score_correction_bias


@dataclass(frozen=True)
class Family:
    name: str
    to_config: Callable[[dict], ModelConfig]
    scheme: WeightScheme = field(default_factory=WeightScheme)
    moe: MoEScheme | None = None
    # packed-qkv layout fixup -> [q_all; k_all; v_all] rows (applied before
    # quantization; the _optimize_pre weight-rewrite equivalent)
    qkv_transform: Callable | None = None
    # gpt2-style Conv1D checkpoints store projections [in, out]
    transpose_weights: bool = False


def _rope_from_hf(hf: dict, head_dim: int) -> RopeScaling:
    rs = hf.get("rope_scaling") or {}
    kind = rs.get("rope_type", rs.get("type", "default"))
    return RopeScaling(
        head_dim=head_dim,
        base=hf.get("rope_theta", 10000.0),
        kind=kind,
        factor=rs.get("factor", 1.0),
        low_freq_factor=rs.get("low_freq_factor", 1.0),
        high_freq_factor=rs.get("high_freq_factor", 4.0),
        original_max_position=rs.get(
            "original_max_position_embeddings",
            hf.get("original_max_position_embeddings",
                   hf.get("max_position_embeddings", 8192)),
        ),
        partial_rotary_factor=hf.get("partial_rotary_factor", 1.0),
        attention_factor=rs.get("attention_factor"),
        short_factor=tuple(rs["short_factor"]) if rs.get("short_factor") else None,
        long_factor=tuple(rs["long_factor"]) if rs.get("long_factor") else None,
    )


def _base_cfg(hf: dict, **over) -> dict:
    head_dim = hf.get("head_dim") or hf["hidden_size"] // hf["num_attention_heads"]
    d = dict(
        model_type=hf.get("model_type", "llama"),
        vocab_size=hf["vocab_size"],
        hidden_size=hf["hidden_size"],
        intermediate_size=hf["intermediate_size"],
        num_layers=hf["num_hidden_layers"],
        num_heads=hf["num_attention_heads"],
        num_kv_heads=hf.get("num_key_value_heads", hf["num_attention_heads"]),
        head_dim=head_dim,
        max_position_embeddings=hf.get("max_position_embeddings", 4096),
        act=hf.get("hidden_act", "silu"),
        norm_eps=hf.get("rms_norm_eps", hf.get("layer_norm_eps", 1e-5)),
        rope=_rope_from_hf(hf, head_dim),
        tie_word_embeddings=hf.get("tie_word_embeddings", False),
        attention_bias=hf.get("attention_bias", False),
        mlp_bias=hf.get("mlp_bias", False),
    )
    d.update(over)
    return d


def _llama(hf: dict) -> ModelConfig:
    return ModelConfig(**_base_cfg(hf))


def _mistral(hf: dict) -> ModelConfig:
    return ModelConfig(**_base_cfg(hf, sliding_window=hf.get("sliding_window")))


def _qwen2(hf: dict) -> ModelConfig:
    # qwen2 has attention bias on qkv but not on o_proj
    return ModelConfig(**_base_cfg(hf, attention_bias=True, attention_out_bias=False))


def _qwen3(hf: dict) -> ModelConfig:
    return ModelConfig(**_base_cfg(hf, qk_norm=True))


def _phi3(hf: dict) -> ModelConfig:
    return ModelConfig(**_base_cfg(hf, sliding_window=hf.get("sliding_window")))


def _gemma(hf: dict) -> ModelConfig:
    d = _base_cfg(
        hf,
        norm_offset=1.0,
        act=hf.get("hidden_activation", hf.get("hidden_act", "gelu_pytorch_tanh")),
        embedding_multiplier=float(np.sqrt(hf["hidden_size"])),
        tie_word_embeddings=True,
    )
    return ModelConfig(**d)


def _gemma2(hf: dict) -> ModelConfig:
    n_layers = hf["num_hidden_layers"]
    d = _base_cfg(
        hf,
        norm_offset=1.0,
        act=hf.get("hidden_activation", "gelu_pytorch_tanh"),
        embedding_multiplier=float(np.sqrt(hf["hidden_size"])),
        tie_word_embeddings=True,
        post_attn_norm=True,
        post_mlp_norm=True,
        attn_softcap=hf.get("attn_logit_softcapping", 50.0),
        logit_softcap=hf.get("final_logit_softcapping", 30.0),
        sliding_window=hf.get("sliding_window", 4096),
        # gemma2 alternates sliding (even) / full (odd) attention layers
        layer_types=tuple(
            "sliding_attention" if i % 2 == 0 else "full_attention"
            for i in range(n_layers)
        ),
        attn_scale=hf.get("query_pre_attn_scalar", hf["hidden_size"] //
                          hf["num_attention_heads"]) ** -0.5,
    )
    return ModelConfig(**d)


def _gemma3(hf: dict) -> ModelConfig:
    """gemma3 text: gemma2 block layout (pre/post feedforward norms) plus
    per-head q/k RMSNorm and DUAL rope — sliding layers (5:1 pattern) use a
    local-frequency table, full layers the global (scaled) one."""
    n_layers = hf["num_hidden_layers"]
    head_dim = hf.get("head_dim", 256)
    hf2 = dict(hf)
    hf2["head_dim"] = head_dim
    pattern = hf.get("sliding_window_pattern", 6)
    layer_types = tuple(
        hf["layer_types"] if hf.get("layer_types") else (
            "sliding_attention" if (i + 1) % pattern else "full_attention"
            for i in range(n_layers))
    )
    d = _base_cfg(
        hf2,
        norm_offset=1.0,
        act=hf.get("hidden_activation",
                   hf.get("hidden_act", "gelu_pytorch_tanh")),
        embedding_multiplier=float(np.sqrt(hf["hidden_size"])),
        tie_word_embeddings=hf.get("tie_word_embeddings", True),
        post_attn_norm=True,
        post_mlp_norm=True,
        qk_norm=True,
        sliding_window=hf.get("sliding_window", 512),
        layer_types=layer_types,
        attn_scale=float(hf.get("query_pre_attn_scalar", 256)) ** -0.5,
        rope_local=RopeScaling(
            head_dim=head_dim,
            base=hf.get("rope_local_base_freq", 10000.0),
        ),
    )
    return ModelConfig(**d)


_GEMMA_SCHEME = WeightScheme(lm_head="model.embed_tokens.weight")
_GEMMA2_SCHEME = WeightScheme(
    lm_head="model.embed_tokens.weight",
    mlp_norm="model.layers.{i}.pre_feedforward_layernorm.weight",
    post_attn_norm="model.layers.{i}.post_attention_layernorm.weight",
    post_mlp_norm="model.layers.{i}.post_feedforward_layernorm.weight",
)

def _mixtral(hf: dict) -> ModelConfig:
    return ModelConfig(**_base_cfg(
        hf,
        sliding_window=hf.get("sliding_window"),
        num_experts=hf.get("num_local_experts", 8),
        num_experts_per_tok=hf.get("num_experts_per_tok", 2),
        moe_intermediate_size=hf["intermediate_size"],
        moe_softmax_before_topk=False,   # HF Mixtral: top-k logits, softmax(k)
        moe_norm_topk_prob=True,
    ))


def _qwen2_moe(hf: dict) -> ModelConfig:
    return ModelConfig(**_base_cfg(
        hf,
        attention_bias=True,
        num_experts=hf.get("num_experts", 60),
        num_experts_per_tok=hf.get("num_experts_per_tok", 4),
        moe_intermediate_size=hf.get("moe_intermediate_size",
                                     hf["intermediate_size"]),
        num_shared_experts=1,
        moe_shared_expert_gate=True,
        moe_softmax_before_topk=True,
        moe_norm_topk_prob=hf.get("norm_topk_prob", False),
    ))


def _qwen3_moe(hf: dict) -> ModelConfig:
    return ModelConfig(**_base_cfg(
        hf,
        qk_norm=True,
        num_experts=hf.get("num_experts", 128),
        num_experts_per_tok=hf.get("num_experts_per_tok", 8),
        moe_intermediate_size=hf.get("moe_intermediate_size",
                                     hf["intermediate_size"]),
        moe_softmax_before_topk=True,
        moe_norm_topk_prob=hf.get("norm_topk_prob", True),
    ))


def _glm(hf: dict) -> ModelConfig:
    """GLM-4 (HF mainline ``glm``): interleaved half-rotary rope, merged
    gate_up MLP, QKV bias.  Reference counterpart: chatglm2/4 patches
    (transformers/models/chatglm2.py, chatglm4.py)."""
    hf2 = dict(hf)
    hf2.setdefault("partial_rotary_factor", 0.5)
    hf2.setdefault("head_dim", 128)
    return ModelConfig(**_base_cfg(
        hf2,
        rope_layout="two",
        attention_bias=hf.get("attention_bias", True),
        attention_out_bias=False,
    ))


def _glm4(hf: dict) -> ModelConfig:
    from dataclasses import replace
    return replace(_glm(hf), post_attn_norm=True, post_mlp_norm=True)


def _chatglm1(hf: dict) -> ModelConfig:
    """ChatGLM v1 (THUDM/chatglm-6b; reference models/chatglm.py, dispatched
    at convert.py:1293): pre-RMSNorm GLM — LayerNorm everywhere, GELU
    non-gated MLP, MHA with per-head-interleaved query_key_value, 2D rotary
    (half the head dim per position channel), and the GLM alpha-scaled
    post-LN residual (h = ln(x)*alpha + sublayer(ln(x)),
    alpha = (2*num_layers)**0.5)."""
    head_dim = hf["hidden_size"] // hf["num_attention_heads"]
    n_layers = hf["num_layers"]
    return ModelConfig(
        model_type="chatglm",
        vocab_size=hf["vocab_size"],
        hidden_size=hf["hidden_size"],
        intermediate_size=hf.get("inner_hidden_size",
                                 4 * hf["hidden_size"]),
        num_layers=n_layers,
        num_heads=hf["num_attention_heads"],
        num_kv_heads=hf["num_attention_heads"],
        head_dim=head_dim,
        max_position_embeddings=hf.get("max_sequence_length", 2048),
        act="gelu",
        mlp_gated=False,
        norm_kind="layer",
        norm_eps=hf.get("layernorm_epsilon", 1e-5),
        # each 2D channel rotates head_dim/2 dims -> per-channel table over
        # head_dim/4 frequencies (partial_rotary 0.5 sizes inv_freq)
        rope=RopeScaling(head_dim=head_dim, base=10000.0,
                         partial_rotary_factor=0.5),
        rope_2d=True,
        glm_alpha=float((2.0 * n_layers) ** 0.5),
        attention_bias=True,
        attention_out_bias=True,
        mlp_bias=True,
    )


_CHATGLM1_SCHEME = WeightScheme(
    embed="transformer.word_embeddings.weight",
    final_norm="transformer.final_layernorm.weight",
    lm_head="lm_head.weight",
    attn_norm="transformer.layers.{i}.input_layernorm.weight",
    mlp_norm="transformer.layers.{i}.post_attention_layernorm.weight",
    qkv="transformer.layers.{i}.attention.query_key_value.{p}",
    q=None, k=None, v=None,
    o="transformer.layers.{i}.attention.dense.{p}",
    gate=None, gate_up=None,
    up="transformer.layers.{i}.mlp.dense_h_to_4h.{p}",
    down="transformer.layers.{i}.mlp.dense_4h_to_h.{p}",
)


def _chatglm(hf: dict) -> ModelConfig:
    """Legacy THUDM ``chatglm`` checkpoints (chatglm2/3-6b, glm-4-9b-chat):
    same math as mainline glm, different config keys and weight names
    (reference chatglm2.py:118-183 config usage).  v1 checkpoints
    (position_encoding_2d / inner_hidden_size) resolve to the chatglm1
    family via get_family."""
    if not hf.get("rmsnorm", True) or hf.get("post_layer_norm") is False:
        raise NotImplementedError(
            "layernorm/post-norm chatglm variant without v1 markers; "
            "v1 (position_encoding_2d/inner_hidden_size) is supported")
    head_dim = hf.get("kv_channels",
                      hf["hidden_size"] // hf["num_attention_heads"])
    groups = (hf.get("multi_query_group_num", hf["num_attention_heads"])
              if hf.get("multi_query_attention", False)
              else hf["num_attention_heads"])
    hf2 = dict(
        model_type="chatglm",
        vocab_size=hf.get("padded_vocab_size", hf.get("vocab_size")),
        hidden_size=hf["hidden_size"],
        intermediate_size=hf["ffn_hidden_size"],
        num_hidden_layers=hf["num_layers"],
        num_attention_heads=hf["num_attention_heads"],
        num_key_value_heads=groups,
        head_dim=head_dim,
        max_position_embeddings=hf.get("seq_length", 8192),
        rms_norm_eps=hf.get("layernorm_epsilon", 1e-5),
        rope_theta=10000.0 * hf.get("rope_ratio", 1.0),
        partial_rotary_factor=0.5,
        tie_word_embeddings=hf.get("tie_word_embeddings", False),
    )
    return ModelConfig(**_base_cfg(
        hf2,
        rope_layout="two",
        attention_bias=hf.get("add_qkv_bias", hf.get("add_bias_linear", False)),
        attention_out_bias=hf.get("add_bias_linear", False),
        mlp_bias=hf.get("add_bias_linear", False),
    ))


def _deepseek_common(hf: dict) -> dict:
    qk_dim = (hf.get("qk_nope_head_dim", 128) + hf.get("qk_rope_head_dim", 64)
              if hf.get("kv_lora_rank") else
              hf.get("head_dim") or hf["hidden_size"] // hf["num_attention_heads"])
    hf2 = dict(hf)
    if hf.get("kv_lora_rank"):
        hf2["head_dim"] = qk_dim
        # rope acts on the 64-dim rope slice only; naive cache is per-head
        hf2["num_key_value_heads"] = hf["num_attention_heads"]
    d = _base_cfg(
        hf2,
        rope_layout="two",
        q_lora_rank=hf.get("q_lora_rank"),
        kv_lora_rank=hf.get("kv_lora_rank"),
        qk_nope_head_dim=hf.get("qk_nope_head_dim", 0),
        qk_rope_head_dim=hf.get("qk_rope_head_dim", 0),
        v_head_dim=hf.get("v_head_dim"),
        num_experts=hf.get("n_routed_experts") or 0,
        num_experts_per_tok=hf.get("num_experts_per_tok") or 0,
        moe_intermediate_size=hf.get("moe_intermediate_size", 0),
        num_shared_experts=hf.get("n_shared_experts") or 0,
        moe_layer_start=hf.get("first_k_dense_replace", 0),
        moe_router_scale=hf.get("routed_scaling_factor", 1.0),
        moe_norm_topk_prob=hf.get("norm_topk_prob", False),
        moe_softmax_before_topk=True,
    )
    if hf.get("kv_lora_rank"):
        # rope table spans the rope slice; attention scales by full qk dim
        rope = _rope_from_hf(hf, hf.get("qk_rope_head_dim", 64))
        d["attn_scale"] = qk_dim ** -0.5
        rs = hf.get("rope_scaling") or {}
        if rope.kind == "yarn" and rope.attention_factor is None:
            # the DeepSeek YaRN convention these keys come from: with m(s) =
            # 0.1 s ln(factor) + 1, cos and sin carry m(mscale) /
            # m(mscale_all_dim) and the SOFTMAX scale m(mscale_all_dim)^2,
            # which also scales the position-free dims (the generic YaRN
            # rule, m(1) on cos and sin alone, computes other logits)
            def m(s_):
                return (0.1 * s_ * math.log(rope.factor) + 1.0
                        if rope.factor > 1 else 1.0)

            m_all = m(float(rs.get("mscale_all_dim") or 0.0))
            rope = replace(rope, attention_factor=m(
                float(rs.get("mscale", 1.0))) / m_all)
            d["attn_scale"] = d["attn_scale"] * m_all * m_all
        d["rope"] = rope
    return d


def _deepseek_v2(hf: dict) -> ModelConfig:
    d = _deepseek_common(hf)
    if hf.get("topk_method", "greedy") == "group_limited_greedy":
        d.update(moe_n_group=hf.get("n_group") or 0,
                 moe_topk_group=hf.get("topk_group") or 0)
    return ModelConfig(**d)


def _deepseek_v3(hf: dict) -> ModelConfig:
    d = _deepseek_common(hf)
    d.update(
        moe_n_group=hf.get("n_group") or 0,
        moe_topk_group=hf.get("topk_group") or 0,
        moe_score_func="sigmoid",
        moe_group_score="top2sum",
        moe_score_bias=True,
    )
    return ModelConfig(**d)


def _axk1(hf: dict) -> ModelConfig:
    """SKT A.X-K1: the DeepSeek-V3 block (MLA with a low-rank q, one dense
    layer then expert layers beside a shared expert) with a sigmoid router,
    the V3 group score (a group's two best) and NO score-correction bias
    (``topk_method: "none"``; ``noaux_tc``, the key that carries one, is
    read by ``deepseek_v3`` and, over held experts, by ``glm_moe_dsa`` and
    ``xing4_0``).
    Its expert layers are HELD layers (models/decoder.py
    ``_held_moe_block``): ``n_router_experts`` and ``first_routed_expert``
    describe a chip's share of a deployment that divides the experts, the
    router's width and the first expert held (``n_routed_experts`` of them;
    all of them by default), as ``_nemotron_h`` reads them."""
    if hf.get("topk_method", "none") != "none":
        raise ValueError(
            f"axk1: topk_method {hf['topk_method']!r} is not supported "
            "(\"none\": selection on the raw scores, no "
            "e_score_correction_bias; the held-expert families that carry "
            "\"noaux_tc\" are glm_moe_dsa and xing4_0)")
    d = _deepseek_common(hf)
    d.update(_held_experts(hf))
    return ModelConfig(**d)


def _held_experts(hf: dict) -> dict:
    """The keys of a DeepSeek-V3 expert layer that is told which experts it
    holds (``_axk1``): ``n_routed_experts`` of the router's
    ``n_router_experts``, from ``first_routed_expert`` on; sigmoid scores,
    the V3 group score."""
    held = hf["n_routed_experts"]
    return dict(
        num_experts=hf.get("n_router_experts", held),
        moe_first_expert=hf.get("first_routed_expert", 0),
        moe_experts_held=held,
        moe_n_group=hf.get("n_group") or 0,
        moe_topk_group=hf.get("topk_group") or 0,
        moe_score_func=hf.get("scoring_func", "sigmoid"),
        moe_group_score="top2sum",
    )


def _require_noaux_tc(hf: dict) -> None:
    if hf.get("topk_method", "noaux_tc") != "noaux_tc":
        raise ValueError(
            f"{hf['model_type']}: topk_method {hf['topk_method']!r} is not "
            "supported (\"noaux_tc\": selection on score + "
            "e_score_correction_bias)")


def _glm_moe_dsa(hf: dict) -> ModelConfig:
    """GLM-5.x (``glm_moe_dsa``): the DeepSeek-V3 block (MLA with a low-rank
    q, interleaved rope on the rope slice, dense layers then sigmoid
    ``noaux_tc`` expert layers beside a shared expert; ``n_group`` 1, so no
    group step) with DeepSeek-V3.2's learned sparse attention on top: the
    layers whose ``indexer_types`` entry is "full" carry an INDEXER
    (``index_n_heads`` heads of ``index_head_dim`` over the low-rank query, a
    LayerNormed index key a token) that keeps the ``index_topk`` best cached
    tokens a query, and the "shared" layers after one attend over ITS
    selection (models/decoder.py ``_mla_block``).  Expert layers are HELD
    layers as ``_axk1`` reads them (``n_router_experts``,
    ``first_routed_expert``).  The multi-token-prediction layer is a draft
    head and is not built."""
    _require_noaux_tc(hf)
    n = hf["num_hidden_layers"]
    hf2 = dict(hf)
    rp = hf.get("rope_parameters") or {}
    if "rope_theta" not in hf2 and "rope_theta" in rp:
        hf2["rope_theta"] = rp["rope_theta"]
    if rp.get("rope_type", "default") != "default":
        hf2.setdefault("rope_scaling", rp)
    mlp_types = hf.get("mlp_layer_types")
    n_dense = hf.get("first_k_dense_replace", 0)
    if mlp_types is not None:
        want = ["dense"] * n_dense + ["sparse"] * (n - n_dense)
        if list(mlp_types) != want:
            raise ValueError(
                "glm_moe_dsa: mlp_layer_types must be first_k_dense_replace "
                f"dense layers then sparse ones, got {list(mlp_types)}")
    types = hf.get("indexer_types")
    if types is None:
        freq = hf.get("index_topk_freq") or 1
        skip = hf.get("index_skip_topk_offset", n_dense)
        types = ["full" if i < skip or (i - skip) % freq == freq - 1
                 else "shared" for i in range(n)]
    types = tuple(types)
    if len(types) != n or set(types) - {"full", "shared"} \
            or types[0] != "full":
        raise ValueError(
            "glm_moe_dsa: indexer_types must name every layer \"full\" or "
            f"\"shared\" and start with a full one, got {list(types)}")
    d = _deepseek_common(hf2)
    d.update(
        _held_experts(hf),
        moe_score_bias=True,
        index_topk=hf["index_topk"],
        index_n_heads=hf["index_n_heads"],
        index_head_dim=hf["index_head_dim"],
        indexer_types=types,
    )
    return ModelConfig(**d)


def _xing4_0(hf: dict) -> ModelConfig:
    """Xing4.0 (``xing4_0``): the DeepSeek-V3 block (MLA with a low-rank q,
    YaRN by the DeepSeek convention of ``_deepseek_common``, dense layers
    then sigmoid ``noaux_tc`` expert layers beside a shared expert;
    ``n_group`` 1, so no group step) on a residual path of ``hc_mult``
    streams: every attention and MLP / expert sublayer is wrapped in a
    manifold-constrained hyper-connection (ops/hyper.py; models/decoder.py
    ``run_layers``).  Expert layers are HELD layers as ``_axk1`` reads them
    (``n_router_experts``, ``first_routed_expert``).  The multi-token-
    prediction layer is a draft head and is not built."""
    _require_noaux_tc(hf)
    if hf.get("hc_mult", 1) < 2:
        raise ValueError(
            f"xing4_0: hc_mult {hf.get('hc_mult')!r}: the family's residual "
            "path is two or more hyper-connected streams")
    d = _deepseek_common(hf)
    d.update(
        _held_experts(hf),
        moe_score_bias=True,
        hc_mult=hf["hc_mult"],
        hc_sinkhorn_iters=hf["hc_sinkhorn_iters"],
        hc_eps=hf["hc_eps"],
        hc_res_clamp=(float(hf["mhc_h_res_clamp_min"]),
                      float(hf["mhc_h_res_clamp_max"])),
    )
    return ModelConfig(**d)


def _glm5_next_text(hf: dict) -> ModelConfig:
    """GLM-5.3 (``glm5_next_text``): a per-layer MIXER kind
    (``layer_types``) on ``hc_mult`` hyper-connected streams.  A
    ``linear_attention`` layer is Kimi Delta Attention (ops/kda.py;
    ``linear_attn_config``: heads, head dim, the short conv's kernel, the
    gate's lower bound): a delta-rule state a row and no KV.  A
    ``deepseek_sparse_attention`` layer is the DeepSeek-V3 latent attention
    WITHOUT a rotated slice (``qk_rope_head_dim`` 0: nothing in the model
    rotates) under an indexer whose keys are POOLED (``index_kpool``: one
    key a complete block of that many positions, ``index_topk /
    index_kpool`` blocks kept, the positions after the last complete block
    always attended).  Every sublayer sits inside a manifold-constrained
    hyper-connection as ``xing4_0``'s do (no clamp on the residual mix's
    logits: the config publishes none).  MLPs: ``mlp_layer_types`` dense
    then sparse, the sparse ones HELD expert layers as ``_axk1`` reads them,
    every gated activation clamped at ``swiglu_limit``.  Only the sparse-
    attention layers carry an indexer, so ``cfg.indexer_types`` here is
    "full" for them and "none" for the others.  The multi-token-prediction
    layer is a draft head and is not built."""
    _require_noaux_tc(hf)
    n = hf["num_hidden_layers"]
    kinds = {"linear_attention": "kda", "deepseek_sparse_attention": "mla"}
    layer_types = list(hf.get("layer_types") or ())
    if len(layer_types) != n or set(layer_types) - set(kinds):
        raise ValueError(
            "glm5_next_text: layer_types must name every layer "
            f"\"linear_attention\" or \"deepseek_sparse_attention\", got "
            f"{layer_types}")
    if not hf.get("mhc", False) or hf.get("hc_mult", 1) < 2:
        raise ValueError(
            f"glm5_next_text: mhc {hf.get('mhc')!r}, hc_mult "
            f"{hf.get('hc_mult')!r}: the family's residual path is two or "
            "more hyper-connected streams")
    if set(hf.get("indexer_types") or ["full"]) != {"full"}:
        raise ValueError(
            "glm5_next_text: every indexer_types entry must be \"full\" "
            f"(no layer shares a selection), got {hf['indexer_types']}")
    if hf.get("qk_rope_head_dim", 0) or not hf.get("mla_use_nope", True):
        raise ValueError(
            "glm5_next_text: the latent attention is position-free "
            "(qk_rope_head_dim 0, mla_use_nope true)")
    if not hf.get("index_kpool_compress", True) or not hf.get(
            "index_kpool_always_select_tail", True):
        raise ValueError(
            "glm5_next_text: the index cache holds pooled keys "
            "(index_kpool_compress) and the tail is always attended "
            "(index_kpool_always_select_tail)")
    pool = int(hf.get("index_kpool", 1))
    if hf["index_topk"] % pool:
        raise ValueError(
            f"glm5_next_text: index_topk {hf['index_topk']} is not a "
            f"multiple of index_kpool {pool}")
    mlp_types = list(hf.get("mlp_layer_types") or ())
    n_dense = hf.get("first_k_dense_replace", 0)
    if mlp_types and mlp_types != ["dense"] * n_dense + ["sparse"] * (
            n - n_dense):
        raise ValueError(
            "glm5_next_text: mlp_layer_types must be first_k_dense_replace "
            f"dense layers then sparse ones, got {mlp_types}")
    la = hf["linear_attn_config"]
    mixers = tuple(kinds[t] for t in layer_types)
    d = _deepseek_common(hf)
    d.update(
        _held_experts(hf),
        moe_score_bias=True,
        rope=None,
        attn_scale=(hf["qk_nope_head_dim"]) ** -0.5,
        index_topk=hf["index_topk"],
        index_n_heads=hf["index_n_heads"],
        index_head_dim=hf["index_head_dim"],
        index_kpool=pool,
        indexer_types=tuple("full" if m == "mla" else "none"
                            for m in mixers),
        hc_mult=hf["hc_mult"],
        hc_sinkhorn_iters=hf["hc_sinkhorn_iters"],
        hc_eps=hf["hc_eps"],
        hc_res_clamp=(-float("inf"), float("inf")),
        mixer_types=mixers,
        kda_num_heads=la["num_heads"],
        kda_head_dim=la["head_dim"],
        kda_conv_kernel=la.get("short_conv_kernel_size", 4),
        kda_gate_lower_bound=float(la.get("gate_lower_bound", -5.0)),
        mlp_act_limit=(float(hf["swiglu_limit"])
                       if hf.get("swiglu_limit") else None),
    )
    return ModelConfig(**d)


def _phi(hf: dict) -> ModelConfig:
    """phi-1/phi-2: parallel attn+mlp off ONE shared layernorm, partial
    rotary, non-gated gelu MLP, biases everywhere."""
    return ModelConfig(**_base_cfg(
        hf,
        norm_kind="layer",
        norm_eps=hf.get("layer_norm_eps", 1e-5),
        act=hf.get("hidden_act", "gelu_new"),
        mlp_gated=False,
        parallel_blocks=True,
        attention_bias=True,
        attention_out_bias=True,
    ))


def _phixtral(hf: dict) -> ModelConfig:
    """phixtral (model_type 'phi-msft'): phi-2 blocks (parallel residual off
    one shared LN, partial rotary, biases) with an MoE of NON-gated
    fc1->gelu->fc2 experts, softmax-before-topk routing renormalized over
    the top-k (reference models/phixtral.py:phixtral_moeblock_forward).
    The msft config spells dimensions n_embd/n_head/n_layer."""
    n_embd = hf.get("n_embd", hf.get("hidden_size", 2560))
    n_head = hf.get("n_head", hf.get("num_attention_heads", 32))
    head_dim = n_embd // n_head
    hf2 = dict(hf)
    hf2.setdefault("hidden_size", n_embd)
    hf2.setdefault("num_attention_heads", n_head)
    hf2.setdefault("num_hidden_layers", hf.get("n_layer", 32))
    hf2.setdefault("num_key_value_heads", hf.get("n_head_kv") or n_head)
    hf2.setdefault("intermediate_size", hf.get("n_inner") or 4 * n_embd)
    hf2.setdefault("max_position_embeddings", hf.get("n_positions", 2048))
    hf2.setdefault("partial_rotary_factor",
                   hf.get("rotary_dim", head_dim) / head_dim)
    return ModelConfig(**_base_cfg(
        hf2,
        norm_kind="layer",
        norm_eps=hf.get("layer_norm_epsilon", 1e-5),
        act=hf.get("activation_function", "gelu_new"),
        mlp_gated=False,
        parallel_blocks=True,
        attention_bias=True,
        attention_out_bias=True,
        num_experts=hf.get("num_local_experts", 4),
        num_experts_per_tok=hf.get("num_experts_per_tok", 2),
        moe_intermediate_size=hf.get("n_inner") or 4 * n_embd,
        moe_softmax_before_topk=True,
        moe_norm_topk_prob=True,
    ))


# phixtral checkpoints keep the msft phi-2 module tree (transformer.h.*,
# mixer.Wqkv packed [q;k;v], lm_head.{ln,linear}); experts live under
# moe.mlp.{e} with plain fc1/fc2 (reference models/phixtral.py)
_PHIXTRAL_SCHEME = WeightScheme(
    embed="transformer.embd.wte.weight",
    final_norm="lm_head.ln.weight",
    lm_head="lm_head.linear.weight",
    attn_norm="transformer.h.{i}.ln.weight",
    mlp_norm="transformer.h.{i}.ln.weight",
    q=None, k=None, v=None,
    qkv="transformer.h.{i}.mixer.Wqkv.{p}",
    o="transformer.h.{i}.mixer.out_proj.{p}",
    gate=None, up=None, gate_up=None,
    down="transformer.h.{i}.moe.mlp.0.fc2.weight",  # unused (MoE layers)
)
_PHIXTRAL_MOE = MoEScheme(
    router="transformer.h.{i}.moe.gate.weight",
    e_gate=None,
    e_up="transformer.h.{i}.moe.mlp.{e}.fc1.weight",
    e_down="transformer.h.{i}.moe.mlp.{e}.fc2.weight",
)


def _gptneox(hf: dict) -> ModelConfig:
    hf2 = dict(hf)
    hf2.setdefault("partial_rotary_factor", hf.get("rotary_pct", 1.0))
    hf2.setdefault("rope_theta", hf.get("rotary_emb_base", 10000.0))
    return ModelConfig(**_base_cfg(
        hf2,
        norm_kind="layer",
        norm_eps=hf.get("layer_norm_eps", 1e-5),
        act=hf.get("hidden_act", "gelu"),
        mlp_gated=False,
        parallel_blocks=hf.get("use_parallel_residual", True),
        attention_bias=hf.get("attention_bias", True),
        attention_out_bias=True,
    ))


def _starcoder2(hf: dict) -> ModelConfig:
    return ModelConfig(**_base_cfg(
        hf,
        norm_kind="layer",
        norm_eps=hf.get("norm_epsilon", hf.get("layer_norm_eps", 1e-5)),
        act=hf.get("hidden_act", "gelu_pytorch_tanh"),
        mlp_gated=False,
        attention_bias=hf.get("use_bias", True),
        attention_out_bias=hf.get("use_bias", True),
        sliding_window=hf.get("sliding_window"),
        tie_word_embeddings=hf.get("tie_word_embeddings", True),
    ))


def _baichuan(hf: dict) -> ModelConfig:
    if hf.get("hidden_size", 0) >= 5120:
        # baichuan-13B: ALiBi instead of rope (reference baichuan.py
        # patches); the W_pack layout is unchanged
        return ModelConfig(**_base_cfg(hf, rope=None, alibi=True))
    return ModelConfig(**_base_cfg(hf))


def _internlm2(hf: dict) -> ModelConfig:
    return ModelConfig(**_base_cfg(hf, attention_bias=hf.get("bias", False)))


def _bloom(hf: dict) -> ModelConfig:
    """bloom: ALiBi, no rope, layernorm everywhere incl. an embedding
    layernorm, fused per-head-interleaved QKV (reference bloom patches)."""
    h = hf["hidden_size"]
    hf2 = dict(
        model_type="bloom", vocab_size=hf["vocab_size"], hidden_size=h,
        intermediate_size=hf.get("intermediate_size") or 4 * h,
        num_hidden_layers=hf.get("n_layer", hf.get("num_hidden_layers")),
        num_attention_heads=hf.get("n_head", hf.get("num_attention_heads")),
        layer_norm_eps=hf.get("layer_norm_epsilon", 1e-5),
        max_position_embeddings=2048,
    )
    return ModelConfig(**_base_cfg(
        hf2, rope=None, alibi=True, embed_norm=True,
        norm_kind="layer", act="gelu_new", mlp_gated=False,
        attention_bias=True, attention_out_bias=True, mlp_bias=True,
        tie_word_embeddings=True,
    ))


def _mpt(hf: dict) -> ModelConfig:
    """mpt: ALiBi (attn_config), no biases, exact-gelu MLP."""
    h = hf["d_model"]
    attn = hf.get("attn_config") or {}
    hf2 = dict(
        model_type="mpt", vocab_size=hf["vocab_size"], hidden_size=h,
        intermediate_size=int(hf.get("expansion_ratio", 4) * h),
        num_hidden_layers=hf["n_layers"], num_attention_heads=hf["n_heads"],
        layer_norm_eps=hf.get("layer_norm_epsilon", 1e-5),
        max_position_embeddings=hf.get("max_seq_len", 2048),
    )
    if not attn.get("alibi", True):
        raise NotImplementedError("mpt without alibi (learned pos) unsupported")
    return ModelConfig(**_base_cfg(
        hf2, rope=None, alibi=True, norm_kind="layer", act="gelu",
        mlp_gated=False, tie_word_embeddings=True,
    ))


def _gpt2(hf: dict) -> ModelConfig:
    h = hf["n_embd"]
    hf2 = dict(
        model_type="gpt2", vocab_size=hf["vocab_size"], hidden_size=h,
        intermediate_size=hf.get("n_inner") or 4 * h,
        num_hidden_layers=hf["n_layer"], num_attention_heads=hf["n_head"],
        layer_norm_eps=hf.get("layer_norm_epsilon", 1e-5),
        max_position_embeddings=hf.get("n_positions", 1024),
    )
    return ModelConfig(**_base_cfg(
        hf2, rope=None, learned_pos=hf.get("n_positions", 1024),
        norm_kind="layer", act=hf.get("activation_function", "gelu_new"),
        mlp_gated=False, attention_bias=True, attention_out_bias=True,
        mlp_bias=True, tie_word_embeddings=True,
    ))


def _opt(hf: dict) -> ModelConfig:
    if hf.get("word_embed_proj_dim", hf["hidden_size"]) != hf["hidden_size"]:
        raise NotImplementedError("OPT word_embed projections unsupported")
    if not hf.get("do_layer_norm_before", True):
        raise NotImplementedError("OPT-350m post-norm layout unsupported")
    hf2 = dict(hf)
    hf2["intermediate_size"] = hf.get("ffn_dim", 4 * hf["hidden_size"])
    return ModelConfig(**_base_cfg(
        hf2, rope=None,
        learned_pos=hf.get("max_position_embeddings", 2048),
        norm_kind="layer", act=hf.get("activation_function", "relu"),
        mlp_gated=False,
        attention_bias=hf.get("enable_bias", True),
        attention_out_bias=hf.get("enable_bias", True),
        mlp_bias=hf.get("enable_bias", True),
        tie_word_embeddings=True,
    ))


def _gptj(hf: dict) -> ModelConfig:
    h = hf["n_embd"]
    head_dim = h // hf["n_head"]
    hf2 = dict(
        model_type="gptj", vocab_size=hf["vocab_size"], hidden_size=h,
        intermediate_size=hf.get("n_inner") or 4 * h,
        num_hidden_layers=hf["n_layer"], num_attention_heads=hf["n_head"],
        layer_norm_eps=hf.get("layer_norm_epsilon", 1e-5),
        max_position_embeddings=hf.get("n_positions", 2048),
        partial_rotary_factor=hf.get("rotary_dim", head_dim) / head_dim,
    )
    return ModelConfig(**_base_cfg(
        hf2, rope_layout="two", norm_kind="layer",
        act=hf.get("activation_function", "gelu_new"), mlp_gated=False,
        parallel_blocks=True, mlp_bias=True,
    ))


def _cohere(hf: dict) -> ModelConfig:
    if hf.get("use_qk_norm"):
        raise NotImplementedError("cohere use_qk_norm variant unsupported")
    return ModelConfig(**_base_cfg(
        hf,
        rope_layout="two",               # cohere applies rope interleaved
        norm_kind="layer",               # LayerNorm without bias
        norm_eps=hf.get("layer_norm_eps", 1e-5),
        parallel_blocks=True,            # x + attn(ln(x)) + mlp(ln(x))
        logit_scale=hf.get("logit_scale", 1.0),
        tie_word_embeddings=True,
    ))


def _stablelm(hf: dict) -> ModelConfig:
    if hf.get("qk_layernorm") or hf.get("use_parallel_residual"):
        raise NotImplementedError(
            "stablelm qk_layernorm / parallel-residual variants (e.g. "
            "stablelm-2-12b) are not supported yet"
        )
    return ModelConfig(**_base_cfg(
        hf,
        norm_kind="layer",
        norm_eps=hf.get("layer_norm_eps", 1e-5),
        attention_bias=hf.get("use_qkv_bias", False),
        attention_out_bias=False,
    ))


def _olmo2(hf: dict) -> ModelConfig:
    return ModelConfig(**_base_cfg(
        hf,
        qk_norm=True,        # flat RMSNorm over the whole q/k projection
        norm_after=True,     # x + norm(attn(x)) reordered-norm blocks
    ))


def _falcon(hf: dict) -> ModelConfig:
    h = hf["hidden_size"]
    new_arch = hf.get("new_decoder_architecture", False)
    if new_arch:
        kv = hf.get("num_kv_heads") or hf["num_attention_heads"]
    elif hf.get("multi_query", True):
        kv = 1
    else:
        kv = hf["num_attention_heads"]
    hf2 = dict(hf)
    hf2["intermediate_size"] = hf.get("ffn_hidden_size") or 4 * h
    hf2["num_key_value_heads"] = kv
    if hf.get("alibi"):
        return ModelConfig(**_base_cfg(
            hf2, rope=None, alibi=True, norm_kind="layer",
            norm_eps=hf.get("layer_norm_epsilon", 1e-5), act="gelu_new",
            mlp_gated=False, parallel_blocks=hf.get("parallel_attn", True),
            attention_bias=hf.get("bias", False),
            attention_out_bias=hf.get("bias", False),
            tie_word_embeddings=True,
        ))
    return ModelConfig(**_base_cfg(
        hf2, norm_kind="layer",
        norm_eps=hf.get("layer_norm_epsilon", 1e-5), act="gelu_new",
        mlp_gated=False, parallel_blocks=hf.get("parallel_attn", True),
        attention_bias=hf.get("bias", False),
        attention_out_bias=hf.get("bias", False),
        tie_word_embeddings=True,
    ))


def _decilm(hf: dict) -> ModelConfig:
    """DeciLM: llama layout with a DIFFERENT kv-head count per layer
    (``num_key_value_heads_per_layer``; reference decilm.py reads it off
    each attention module).  The loader replicates kv heads up to the max
    so the scan decoder keeps one homogeneous cache."""
    per = hf.get("num_key_value_heads_per_layer")
    if per:
        per = tuple(int(x) for x in per)
        mx = max(per)
        for p in per:
            if mx % p:
                raise NotImplementedError(
                    f"kv head counts {per} are not divisors of {mx}")
        hf2 = dict(hf)
        hf2["num_key_value_heads"] = mx
        return ModelConfig(**_base_cfg(hf2, kv_heads_per_layer=per))
    return ModelConfig(**_base_cfg(hf))


def _internlm(hf: dict) -> ModelConfig:
    """internlm (v1): llama layout with a single ``bias`` flag covering
    q/k/v/o (reference transformers/models/internlm.py)."""
    b = hf.get("bias", True)
    return ModelConfig(**_base_cfg(hf, attention_bias=b,
                                   attention_out_bias=b))


def _qwen(hf: dict) -> ModelConfig:
    """Qwen (v1, e.g. Qwen-7B/14B): fused ``c_attn`` [q;k;v] with bias,
    no o/mlp bias, RMSNorm, half-layout full rotary, and a silu-gated MLP
    where ``intermediate_size`` counts BOTH branches (per-branch ffn dim is
    intermediate_size//2; reference qwen.py:261 c_proj(silu(w2)·w1))."""
    head_dim = hf.get("kv_channels",
                      hf["hidden_size"] // hf["num_attention_heads"])
    hf2 = dict(
        model_type="qwen",
        vocab_size=hf["vocab_size"],
        hidden_size=hf["hidden_size"],
        intermediate_size=hf["intermediate_size"] // 2,
        num_hidden_layers=hf["num_hidden_layers"],
        num_attention_heads=hf["num_attention_heads"],
        head_dim=head_dim,
        rms_norm_eps=hf.get("layer_norm_epsilon", 1e-6),
        max_position_embeddings=hf.get("seq_length", 8192),
        rope_theta=hf.get("rotary_emb_base", 10000.0),
    )
    return ModelConfig(**_base_cfg(
        hf2, attention_bias=not hf.get("no_bias", False),
        attention_out_bias=False,
    ))


def _gptbigcode(hf: dict) -> ModelConfig:
    """gpt_bigcode (starcoder-1/santacoder): gpt2-style learned positions +
    LayerNorm, non-gated gelu MLP, and MQA (kv_heads=1) via a fused
    ``c_attn`` that is a straight [q; k; v] concat (reference
    gptbigcode.py:61-66; the non-MQA variant interleaves per head)."""
    h = hf["n_embd"]
    hf2 = dict(
        model_type="gpt_bigcode", vocab_size=hf["vocab_size"], hidden_size=h,
        intermediate_size=hf.get("n_inner") or 4 * h,
        num_hidden_layers=hf["n_layer"],
        num_attention_heads=hf["n_head"],
        num_key_value_heads=1 if hf.get("multi_query", True) else hf["n_head"],
        layer_norm_eps=hf.get("layer_norm_epsilon", 1e-5),
        max_position_embeddings=hf.get("n_positions", 8192),
    )
    return ModelConfig(**_base_cfg(
        hf2, rope=None, learned_pos=hf.get("n_positions", 8192),
        norm_kind="layer", act=hf.get("activation_function", "gelu_pytorch_tanh"),
        mlp_gated=False, attention_bias=True, attention_out_bias=True,
        mlp_bias=True, tie_word_embeddings=True,
    ))


def _minicpm(hf: dict) -> ModelConfig:
    """minicpm (1/2): llama layout plus muP-style scalings — embeddings
    × scale_emb, every residual branch × scale_depth/sqrt(L), and logits
    × dim_model_base/hidden_size (reference minicpm.py:58
    apply_residual_scale + modeling's hidden/(hidden/dim_model_base))."""
    return ModelConfig(**_base_cfg(
        hf,
        embedding_multiplier=float(hf.get("scale_emb", 1.0)),
        residual_multiplier=float(hf.get("scale_depth", 1.0))
        / float(np.sqrt(hf["num_hidden_layers"])),
        logit_scale=float(hf.get("dim_model_base", hf["hidden_size"]))
        / hf["hidden_size"],
    ))


def _minicpm3(hf: dict) -> ModelConfig:
    """minicpm3: DeepSeek-style MLA attention (same q_a/kv_a low-rank
    weight names) combined with the minicpm muP scalings (reference
    minicpm3.py; MLA math deepseek.py:274-343)."""
    d = _deepseek_common(hf)
    d.update(
        model_type="minicpm3",
        embedding_multiplier=float(hf.get("scale_emb", 1.0)),
        residual_multiplier=float(hf.get("scale_depth", 1.0))
        / float(np.sqrt(hf["num_hidden_layers"])),
        logit_scale=float(hf.get("dim_model_base", hf["hidden_size"]))
        / hf["hidden_size"],
    )
    return ModelConfig(**d)


def _nemotron_h(hf: dict) -> ModelConfig:
    """Nemotron-H / Nemotron-3-Nano: one mixer a block by
    ``hybrid_override_pattern`` (Mamba-2, experts, attention), no rotary
    embedding in the attention blocks (the published modeling code applies
    none; position comes from the Mamba blocks), a sigmoid router with a
    correction bias and ungated relu2 experts.  ``n_router_experts`` and
    ``first_routed_expert`` describe a chip's share of a deployment that
    divides the experts: the router's width and the first expert held
    (``n_routed_experts`` of them).  Weights: models/build.py
    ``build_hybrid_params``."""
    pattern = hf["hybrid_override_pattern"]
    if len(pattern) != hf["num_hidden_layers"] or set(pattern) - set("ME*"):
        raise ValueError(
            f"nemotron_h: hybrid_override_pattern {pattern!r} must name "
            f"{hf['num_hidden_layers']} blocks with the letters M, E, * "
            "(a dense '-' MLP block is not supported)")
    if hf.get("n_group", 1) != 1 or hf.get("topk_group", 1) != 1:
        raise ValueError("nemotron_h: grouped routing is not supported")
    held = hf["n_routed_experts"]
    return ModelConfig(
        model_type="nemotron_h",
        vocab_size=hf["vocab_size"],
        hidden_size=hf["hidden_size"],
        intermediate_size=hf["intermediate_size"],
        num_layers=hf["num_hidden_layers"],
        num_heads=hf["num_attention_heads"],
        num_kv_heads=hf["num_key_value_heads"],
        head_dim=hf.get("head_dim")
        or hf["hidden_size"] // hf["num_attention_heads"],
        max_position_embeddings=hf.get("max_position_embeddings", 4096),
        act=hf.get("mlp_hidden_act", "relu2"),
        norm_eps=hf.get("layer_norm_epsilon", hf.get("norm_eps", 1e-5)),
        rope=None,
        tie_word_embeddings=hf.get("tie_word_embeddings", False),
        attention_bias=hf.get("attention_bias", False),
        mlp_gated=False,
        hybrid_pattern=pattern,
        ssm_num_heads=hf["mamba_num_heads"],
        ssm_head_dim=hf["mamba_head_dim"],
        ssm_n_groups=hf["n_groups"],
        ssm_state_size=hf["ssm_state_size"],
        ssm_conv_kernel=hf.get("conv_kernel", 4),
        num_experts=hf.get("n_router_experts", held),
        num_experts_per_tok=hf["num_experts_per_tok"],
        moe_intermediate_size=hf["moe_intermediate_size"],
        moe_shared_intermediate_size=hf.get(
            "moe_shared_expert_intermediate_size", 0),
        num_shared_experts=hf.get("n_shared_experts", 0),
        moe_norm_topk_prob=hf.get("norm_topk_prob", True),
        moe_router_scale=hf.get("routed_scaling_factor", 1.0),
        moe_softmax_before_topk=True,
        moe_score_func="sigmoid",
        moe_score_bias=True,
        moe_first_expert=hf.get("first_routed_expert", 0),
        moe_experts_held=held,
    )


def _falcon_h1(hf: dict) -> ModelConfig:
    """Falcon-H1: EVERY block holds an attention branch and a Mamba-2 mixer
    that read one normed input and are summed, then a dense gated MLP (the
    pattern letter ``P``, models/decoder.py ``run_hybrid``): a KV layer and
    a state slot a block.  Rotary embedding over the whole head; muP
    multipliers on activations (``ModelConfig``); ``mamba_d_ssm`` is the
    mixer's inner width, given beside ``mamba_expand`` and not derived from
    it, and is ``mamba_n_heads`` heads of ``mamba_d_head``.  The gated norm
    is the form ops/ssm.py has (gate, THEN normalise each group).  Weights:
    models/build.py ``build_hybrid_params``."""
    heads, hd = hf["mamba_n_heads"], hf["mamba_d_head"]
    d_ssm = hf.get("mamba_d_ssm") or hf["mamba_expand"] * hf["hidden_size"]
    if heads * hd != d_ssm:
        raise ValueError(
            f"falcon_h1: mamba_d_ssm {d_ssm} is not mamba_n_heads {heads} x "
            f"mamba_d_head {hd}")
    if hf.get("attn_layer_indices") is not None:
        raise ValueError("falcon_h1: attn_layer_indices is not supported "
                         "(every block holds both mixers)")
    if hf.get("mamba_norm_before_gate", False) or not hf.get(
            "mamba_rms_norm", True):
        raise ValueError(
            "falcon_h1: only the gated group RMSNorm is supported "
            "(mamba_rms_norm true, mamba_norm_before_gate false)")
    for key in ("attention_bias", "mlp_bias", "mamba_proj_bias",
                "projectors_bias"):
        if hf.get(key, False):
            raise ValueError(f"falcon_h1: {key} is not supported")
    if not hf.get("mamba_conv_bias", True):
        raise ValueError("falcon_h1: the conv carries a bias")
    mup = tuple(float(m) for m in hf.get("ssm_multipliers") or ())
    mlp = tuple(float(m) for m in hf.get("mlp_multipliers") or ())
    if len(mup) not in (0, 5) or len(mlp) not in (0, 2):
        raise ValueError(
            "falcon_h1: ssm_multipliers names the five column ranges z, x, "
            "B, C, dt and mlp_multipliers the gate and the down projection")
    return ModelConfig(**_base_cfg(
        hf,
        model_type="falcon_h1",
        hybrid_pattern="P" * hf["num_hidden_layers"],
        ssm_num_heads=heads,
        ssm_head_dim=hd,
        ssm_n_groups=hf["mamba_n_groups"],
        ssm_state_size=hf["mamba_d_state"],
        ssm_conv_kernel=hf.get("mamba_d_conv", 4),
        logit_scale=float(hf.get("lm_head_multiplier", 1.0)),
        **{key: float(hf.get(key, 1.0)) for key in (
            "embedding_multiplier", "attention_in_multiplier",
            "attention_out_multiplier", "key_multiplier",
            "ssm_in_multiplier", "ssm_out_multiplier")},
        ssm_multipliers=mup or None,
        mlp_multipliers=mlp or None,
    ))


def _neox_qkv(w, cfg: ModelConfig):
    """GPT-NeoX query_key_value: per-head [q_i;k_i;v_i] interleave ->
    [q_all; k_all; v_all]."""
    h, hd = cfg.num_heads, cfg.head_dim
    return (
        w.reshape(h, 3, hd, -1).transpose(1, 0, 2, 3).reshape(3 * h * hd, -1)
    )


def _internlm2_qkv(w, cfg: ModelConfig):
    """internlm2 wqkv: per-kv-group [q*ratio; k; v] -> [q_all; k_all; v_all]."""
    g, hd = cfg.num_kv_heads, cfg.head_dim
    per = cfg.num_heads // g
    x = w.reshape(g, per + 2, hd, -1)
    q = x[:, :per].reshape(g * per * hd, -1)
    k = x[:, per].reshape(g * hd, -1)
    v = x[:, per + 1].reshape(g * hd, -1)
    return np.concatenate([q, k, v], axis=0)


_PHI_SCHEME = WeightScheme(
    final_norm="model.final_layernorm.weight",
    o="model.layers.{i}.self_attn.dense.{p}",
    gate=None,
    up="model.layers.{i}.mlp.fc1.{p}",
    gate_up=None,
    down="model.layers.{i}.mlp.fc2.{p}",
    # ONE layernorm feeds both parallel branches
    mlp_norm="model.layers.{i}.input_layernorm.weight",
)
_GPTNEOX_SCHEME = WeightScheme(
    embed="gpt_neox.embed_in.weight",
    final_norm="gpt_neox.final_layer_norm.weight",
    lm_head="embed_out.weight",
    attn_norm="gpt_neox.layers.{i}.input_layernorm.weight",
    mlp_norm="gpt_neox.layers.{i}.post_attention_layernorm.weight",
    qkv="gpt_neox.layers.{i}.attention.query_key_value.{p}",
    q=None, k=None, v=None,
    o="gpt_neox.layers.{i}.attention.dense.{p}",
    gate=None, gate_up=None,
    up="gpt_neox.layers.{i}.mlp.dense_h_to_4h.{p}",
    down="gpt_neox.layers.{i}.mlp.dense_4h_to_h.{p}",
)
_STARCODER2_SCHEME = WeightScheme(
    gate=None, gate_up=None,
    up="model.layers.{i}.mlp.c_fc.{p}",
    down="model.layers.{i}.mlp.c_proj.{p}",
)
_BAICHUAN_SCHEME = WeightScheme(
    qkv="model.layers.{i}.self_attn.W_pack.{p}",
    q=None, k=None, v=None,
)
_INTERNLM2_SCHEME = WeightScheme(
    embed="model.tok_embeddings.weight",
    lm_head="output.weight",
    attn_norm="model.layers.{i}.attention_norm.weight",
    mlp_norm="model.layers.{i}.ffn_norm.weight",
    qkv="model.layers.{i}.attention.wqkv.{p}",
    q=None, k=None, v=None,
    o="model.layers.{i}.attention.wo.{p}",
    gate="model.layers.{i}.feed_forward.w1.{p}",
    up="model.layers.{i}.feed_forward.w3.{p}",
    down="model.layers.{i}.feed_forward.w2.{p}",
)

_GLM_SCHEME = WeightScheme(
    gate=None, up=None,
    gate_up="model.layers.{i}.mlp.gate_up_proj.{p}",
)
_GLM4_SCHEME = WeightScheme(
    gate=None, up=None,
    gate_up="model.layers.{i}.mlp.gate_up_proj.{p}",
    post_attn_norm="model.layers.{i}.post_self_attn_layernorm.weight",
    post_mlp_norm="model.layers.{i}.post_mlp_layernorm.weight",
)
_CHATGLM_SCHEME = WeightScheme(
    embed="transformer.embedding.word_embeddings.weight",
    final_norm="transformer.encoder.final_layernorm.weight",
    lm_head="transformer.output_layer.weight",
    attn_norm="transformer.encoder.layers.{i}.input_layernorm.weight",
    mlp_norm="transformer.encoder.layers.{i}.post_attention_layernorm.weight",
    qkv="transformer.encoder.layers.{i}.self_attention.query_key_value.{p}",
    q=None, k=None, v=None,
    o="transformer.encoder.layers.{i}.self_attention.dense.{p}",
    gate=None, up=None,
    gate_up="transformer.encoder.layers.{i}.mlp.dense_h_to_4h.{p}",
    down="transformer.encoder.layers.{i}.mlp.dense_4h_to_h.{p}",
)
_DEEPSEEK_SCHEME = WeightScheme(
    k=None, v=None,  # q template serves the V2-Lite full-rank q_proj
    q_a="model.layers.{i}.self_attn.q_a_proj.{p}",
    q_a_norm="model.layers.{i}.self_attn.q_a_layernorm.weight",
    q_b="model.layers.{i}.self_attn.q_b_proj.{p}",
    kv_a="model.layers.{i}.self_attn.kv_a_proj_with_mqa.{p}",
    kv_a_norm="model.layers.{i}.self_attn.kv_a_layernorm.weight",
    kv_b="model.layers.{i}.self_attn.kv_b_proj.{p}",
)
_DEEPSEEK_MOE = MoEScheme(
    shared_gate="model.layers.{i}.mlp.shared_experts.gate_proj.weight",
    shared_up="model.layers.{i}.mlp.shared_experts.up_proj.weight",
    shared_down="model.layers.{i}.mlp.shared_experts.down_proj.weight",
)
_DEEPSEEK_V3_MOE = MoEScheme(
    shared_gate="model.layers.{i}.mlp.shared_experts.gate_proj.weight",
    shared_up="model.layers.{i}.mlp.shared_experts.up_proj.weight",
    shared_down="model.layers.{i}.mlp.shared_experts.down_proj.weight",
    score_bias="model.layers.{i}.mlp.gate.e_score_correction_bias",
)
def _falcon_qkv(w, cfg: ModelConfig):
    """Falcon fused QKV: old-arch MHA interleaves per head (neox layout),
    old-arch MQA is a straight [q...; k; v] concat, new-arch groups per kv
    head (internlm2 layout)."""
    if cfg.num_kv_heads == 1:
        return w
    if cfg.num_kv_heads == cfg.num_heads:
        return _neox_qkv(w, cfg)
    return _internlm2_qkv(w, cfg)


_BLOOM_SCHEME = WeightScheme(
    embed="transformer.word_embeddings.weight",
    embed_norm="transformer.word_embeddings_layernorm.weight",
    final_norm="transformer.ln_f.weight",
    lm_head="transformer.word_embeddings.weight",
    attn_norm="transformer.h.{i}.input_layernorm.weight",
    mlp_norm="transformer.h.{i}.post_attention_layernorm.weight",
    qkv="transformer.h.{i}.self_attention.query_key_value.{p}",
    q=None, k=None, v=None,
    o="transformer.h.{i}.self_attention.dense.{p}",
    gate=None, gate_up=None,
    up="transformer.h.{i}.mlp.dense_h_to_4h.{p}",
    down="transformer.h.{i}.mlp.dense_4h_to_h.{p}",
)
_MPT_SCHEME = WeightScheme(
    embed="transformer.wte.weight",
    final_norm="transformer.norm_f.weight",
    lm_head="transformer.wte.weight",
    attn_norm="transformer.blocks.{i}.norm_1.weight",
    mlp_norm="transformer.blocks.{i}.norm_2.weight",
    qkv="transformer.blocks.{i}.attn.Wqkv.{p}",
    q=None, k=None, v=None,
    o="transformer.blocks.{i}.attn.out_proj.{p}",
    gate=None, gate_up=None,
    up="transformer.blocks.{i}.ffn.up_proj.{p}",
    down="transformer.blocks.{i}.ffn.down_proj.{p}",
)
_GPT2_SCHEME = WeightScheme(
    embed="transformer.wte.weight",
    pos_embed="transformer.wpe.weight",
    final_norm="transformer.ln_f.weight",
    lm_head="transformer.wte.weight",
    attn_norm="transformer.h.{i}.ln_1.weight",
    mlp_norm="transformer.h.{i}.ln_2.weight",
    qkv="transformer.h.{i}.attn.c_attn.{p}",
    q=None, k=None, v=None,
    o="transformer.h.{i}.attn.c_proj.{p}",
    gate=None, gate_up=None,
    up="transformer.h.{i}.mlp.c_fc.{p}",
    down="transformer.h.{i}.mlp.c_proj.{p}",
)
_OPT_SCHEME = WeightScheme(
    embed="model.decoder.embed_tokens.weight",
    pos_embed="model.decoder.embed_positions.weight",
    final_norm="model.decoder.final_layer_norm.weight",
    lm_head="model.decoder.embed_tokens.weight",
    attn_norm="model.decoder.layers.{i}.self_attn_layer_norm.weight",
    mlp_norm="model.decoder.layers.{i}.final_layer_norm.weight",
    q="model.decoder.layers.{i}.self_attn.q_proj.{p}",
    k="model.decoder.layers.{i}.self_attn.k_proj.{p}",
    v="model.decoder.layers.{i}.self_attn.v_proj.{p}",
    o="model.decoder.layers.{i}.self_attn.out_proj.{p}",
    gate=None, gate_up=None,
    up="model.decoder.layers.{i}.fc1.{p}",
    down="model.decoder.layers.{i}.fc2.{p}",
)
_GPTJ_SCHEME = WeightScheme(
    embed="transformer.wte.weight",
    final_norm="transformer.ln_f.weight",
    lm_head="lm_head.weight",
    attn_norm="transformer.h.{i}.ln_1.weight",
    mlp_norm="transformer.h.{i}.ln_1.weight",  # ONE norm, parallel blocks
    q="transformer.h.{i}.attn.q_proj.{p}",
    k="transformer.h.{i}.attn.k_proj.{p}",
    v="transformer.h.{i}.attn.v_proj.{p}",
    o="transformer.h.{i}.attn.out_proj.{p}",
    gate=None, gate_up=None,
    up="transformer.h.{i}.mlp.fc_in.{p}",
    down="transformer.h.{i}.mlp.fc_out.{p}",
)
_COHERE_SCHEME = WeightScheme(
    lm_head="model.embed_tokens.weight",
    mlp_norm="model.layers.{i}.input_layernorm.weight",  # ONE norm, parallel
)
_OLMO2_SCHEME = WeightScheme(
    attn_norm="model.layers.{i}.post_attention_layernorm.weight",
    mlp_norm="model.layers.{i}.post_feedforward_layernorm.weight",
    q_norm="model.layers.{i}.self_attn.q_norm.weight",
    k_norm="model.layers.{i}.self_attn.k_norm.weight",
)
_FALCON_SCHEME = WeightScheme(
    embed="transformer.word_embeddings.weight",
    final_norm="transformer.ln_f.weight",
    lm_head="transformer.word_embeddings.weight",
    # old arch: one shared input_layernorm; new arch: ln_attn / ln_mlp
    attn_norm="transformer.h.{i}.input_layernorm.weight"
              "|transformer.h.{i}.ln_attn.weight",
    # non-parallel falcon-rw has a real post_attention_layernorm; try it
    # first so it can never be shadowed by the always-present input norm
    mlp_norm="transformer.h.{i}.post_attention_layernorm.weight"
             "|transformer.h.{i}.ln_mlp.weight"
             "|transformer.h.{i}.input_layernorm.weight",
    qkv="transformer.h.{i}.self_attention.query_key_value.{p}",
    q=None, k=None, v=None,
    o="transformer.h.{i}.self_attention.dense.{p}",
    gate=None, gate_up=None,
    up="transformer.h.{i}.mlp.dense_h_to_4h.{p}",
    down="transformer.h.{i}.mlp.dense_4h_to_h.{p}",
)

_QWEN_SCHEME = WeightScheme(
    embed="transformer.wte.weight",
    final_norm="transformer.ln_f.weight",
    lm_head="lm_head.weight",
    attn_norm="transformer.h.{i}.ln_1.weight",
    mlp_norm="transformer.h.{i}.ln_2.weight",
    qkv="transformer.h.{i}.attn.c_attn.{p}",
    q=None, k=None, v=None,
    o="transformer.h.{i}.attn.c_proj.{p}",
    # reference qwen.py:261: c_proj(silu(w2(x)) * w1(x)) → w2 is the gate
    gate="transformer.h.{i}.mlp.w2.{p}",
    up="transformer.h.{i}.mlp.w1.{p}",
    down="transformer.h.{i}.mlp.c_proj.{p}",
)
_GPTBIGCODE_SCHEME = WeightScheme(
    embed="transformer.wte.weight",
    pos_embed="transformer.wpe.weight",
    final_norm="transformer.ln_f.weight",
    lm_head="transformer.wte.weight",
    attn_norm="transformer.h.{i}.ln_1.weight",
    mlp_norm="transformer.h.{i}.ln_2.weight",
    qkv="transformer.h.{i}.attn.c_attn.{p}",
    q=None, k=None, v=None,
    o="transformer.h.{i}.attn.c_proj.{p}",
    gate=None, gate_up=None,
    up="transformer.h.{i}.mlp.c_fc.{p}",
    down="transformer.h.{i}.mlp.c_proj.{p}",
)


def _gptbigcode_qkv(w, cfg: ModelConfig):
    """MQA c_attn is already [q_all; k; v]; the non-MQA variant interleaves
    per head like gpt-neox (reference gptbigcode.py:66-69)."""
    if cfg.num_kv_heads == 1:
        return w
    return _neox_qkv(w, cfg)


_MIXTRAL_MOE = MoEScheme(
    router="model.layers.{i}.block_sparse_moe.gate.weight",
    e_gate="model.layers.{i}.block_sparse_moe.experts.{e}.w1.weight",
    e_up="model.layers.{i}.block_sparse_moe.experts.{e}.w3.weight",
    e_down="model.layers.{i}.block_sparse_moe.experts.{e}.w2.weight",
)
_QWEN2_MOE = MoEScheme(
    shared_gate="model.layers.{i}.mlp.shared_expert.gate_proj.weight",
    shared_up="model.layers.{i}.mlp.shared_expert.up_proj.weight",
    shared_down="model.layers.{i}.mlp.shared_expert.down_proj.weight",
    shared_router="model.layers.{i}.mlp.shared_expert_gate.weight",
)

FAMILIES: dict[str, Family] = {
    "llama": Family("llama", _llama),
    "mistral": Family("mistral", _mistral),
    "qwen2": Family("qwen2", _qwen2),
    "qwen3": Family(
        "qwen3",
        _qwen3,
        WeightScheme(
            q_norm="model.layers.{i}.self_attn.q_norm.weight",
            k_norm="model.layers.{i}.self_attn.k_norm.weight",
        ),
    ),
    "phi3": Family(
        "phi3",
        _phi3,
        WeightScheme(
            qkv="model.layers.{i}.self_attn.qkv_proj.{p}",
            q=None, k=None, v=None, gate=None, up=None,
            gate_up="model.layers.{i}.mlp.gate_up_proj.{p}",
        ),
    ),
    "gemma": Family("gemma", _gemma, _GEMMA_SCHEME),
    "gemma2": Family("gemma2", _gemma2, _GEMMA2_SCHEME),
    "gemma3_text": Family(
        "gemma3_text",
        _gemma3,
        WeightScheme(
            lm_head="model.embed_tokens.weight",
            mlp_norm="model.layers.{i}.pre_feedforward_layernorm.weight",
            post_attn_norm="model.layers.{i}.post_attention_layernorm.weight",
            post_mlp_norm="model.layers.{i}.post_feedforward_layernorm.weight",
            q_norm="model.layers.{i}.self_attn.q_norm.weight",
            k_norm="model.layers.{i}.self_attn.k_norm.weight",
        ),
    ),
    "phi": Family("phi", _phi, _PHI_SCHEME),
    "phi-msft": Family("phi-msft", _phixtral, _PHIXTRAL_SCHEME,
                       _PHIXTRAL_MOE),
    "phixtral": Family("phixtral", _phixtral, _PHIXTRAL_SCHEME,
                       _PHIXTRAL_MOE),
    "gpt_neox": Family("gpt_neox", _gptneox, _GPTNEOX_SCHEME,
                       qkv_transform=_neox_qkv),
    "starcoder2": Family("starcoder2", _starcoder2, _STARCODER2_SCHEME),
    "baichuan": Family("baichuan", _baichuan, _BAICHUAN_SCHEME),
    "internlm2": Family("internlm2", _internlm2, _INTERNLM2_SCHEME,
                        qkv_transform=_internlm2_qkv),
    "bloom": Family("bloom", _bloom, _BLOOM_SCHEME, qkv_transform=_neox_qkv),
    "mpt": Family("mpt", _mpt, _MPT_SCHEME),
    "gpt2": Family("gpt2", _gpt2, _GPT2_SCHEME, transpose_weights=True),
    "opt": Family("opt", _opt, _OPT_SCHEME),
    "gptj": Family("gptj", _gptj, _GPTJ_SCHEME),
    "cohere": Family("cohere", _cohere, _COHERE_SCHEME),
    "stablelm": Family("stablelm", _stablelm),
    "olmo2": Family("olmo2", _olmo2, _OLMO2_SCHEME),
    "falcon": Family("falcon", _falcon, _FALCON_SCHEME,
                     qkv_transform=_falcon_qkv),
    # aquila (BAAI Aquila/Aquila2) is a faithful llama clone — same config
    # keys and weight names (reference models/aquila.py patches llama SDPA)
    "aquila": Family("aquila", _llama),
    "internlm": Family("internlm", _internlm),
    # DeciLM-6B/7B publish model_type "deci" (some forks "deci_lm")
    "deci": Family("deci", _decilm),
    "deci_lm": Family("deci_lm", _decilm),
    "qwen": Family("qwen", _qwen, _QWEN_SCHEME),
    "gpt_bigcode": Family("gpt_bigcode", _gptbigcode, _GPTBIGCODE_SCHEME,
                          qkv_transform=_gptbigcode_qkv),
    "minicpm": Family("minicpm", _minicpm),
    "minicpm3": Family("minicpm3", _minicpm3, _DEEPSEEK_SCHEME),
    "glm": Family("glm", _glm, _GLM_SCHEME),
    "glm4": Family("glm4", _glm4, _GLM4_SCHEME),
    "chatglm": Family("chatglm", _chatglm, _CHATGLM_SCHEME),
    "chatglm1": Family("chatglm1", _chatglm1, _CHATGLM1_SCHEME,
                       qkv_transform=_neox_qkv),
    "deepseek_v2": Family("deepseek_v2", _deepseek_v2, _DEEPSEEK_SCHEME,
                          _DEEPSEEK_MOE),
    "deepseek_v3": Family("deepseek_v3", _deepseek_v3, _DEEPSEEK_SCHEME,
                          _DEEPSEEK_V3_MOE),
    "axk1": Family("axk1", _axk1, _DEEPSEEK_SCHEME, _DEEPSEEK_MOE),
    "glm_moe_dsa": Family("glm_moe_dsa", _glm_moe_dsa, _DEEPSEEK_SCHEME,
                          _DEEPSEEK_V3_MOE),
    "xing4_0": Family("xing4_0", _xing4_0, _DEEPSEEK_SCHEME,
                      _DEEPSEEK_V3_MOE),
    "glm5_next_text": Family("glm5_next_text", _glm5_next_text,
                             _DEEPSEEK_SCHEME, _DEEPSEEK_V3_MOE),
    "nemotron_h": Family("nemotron_h", _nemotron_h),
    "falcon_h1": Family("falcon_h1", _falcon_h1),
    "mixtral": Family("mixtral", _mixtral, WeightScheme(), _MIXTRAL_MOE),
    "qwen2_moe": Family("qwen2_moe", _qwen2_moe, WeightScheme(), _QWEN2_MOE),
    "qwen3_moe": Family(
        "qwen3_moe",
        _qwen3_moe,
        WeightScheme(
            q_norm="model.layers.{i}.self_attn.q_norm.weight",
            k_norm="model.layers.{i}.self_attn.k_norm.weight",
        ),
        MoEScheme(),
    ),
}


def get_family(model_type: str, hf_config: dict | None = None) -> Family:
    """Resolve a family; ``hf_config`` disambiguates model_types that span
    architecture generations (THUDM reused ``chatglm`` for v1's layernorm/
    2D-rope architecture and v2+'s rmsnorm GLM — reference convert.py:1293
    branches on the same config markers)."""
    if (model_type == "chatglm" and hf_config is not None
            and (hf_config.get("position_encoding_2d")
                 or "inner_hidden_size" in hf_config)):
        return FAMILIES["chatglm1"]
    if model_type not in FAMILIES:
        raise ValueError(
            f"model_type {model_type!r} not supported yet; "
            f"available: {sorted(FAMILIES)}"
        )
    return FAMILIES[model_type]
