"""Normalized decoder configuration.

Replaces the reference's strategy of monkey-patching 49 per-architecture HF
modules (transformers/models/*.py, dispatched by convert.py:1275's 79
``model_type`` branches) with ONE shared decoder core driven by a normalized
config.  Each supported HF architecture contributes only a small mapping from
its HF ``config.json`` to this dataclass plus a weight-name table
(ipex_llm_tpu/models/families.py) — the SURVEY.md §7 mitigation for matching
the reference's breadth without 49 forks.
"""

from __future__ import annotations

from dataclasses import dataclass, field

from ipex_llm_tpu.ops.rope import RopeScaling


@dataclass(frozen=True)
class ModelConfig:
    model_type: str
    vocab_size: int
    hidden_size: int
    intermediate_size: int
    num_layers: int
    num_heads: int
    num_kv_heads: int
    head_dim: int
    max_position_embeddings: int = 4096
    act: str = "silu"

    # norms
    norm_eps: float = 1e-5
    norm_kind: str = "rms"        # rms | layer
    norm_offset: float = 0.0      # 1.0 for gemma-style (1+w)
    qk_norm: bool = False         # qwen3/gemma3 per-head q/k rmsnorm
    post_attn_norm: bool = False  # gemma2 extra post-attention norm
    post_mlp_norm: bool = False

    # rope
    rope: RopeScaling | None = None
    rope_layout: str = "half"     # half | two
    # gemma3: sliding-attention layers use a separate (local) rope table
    rope_local: RopeScaling | None = None
    partial_rotary: float = 1.0
    mrope_section: tuple[int, ...] | None = None  # qwen2-vl 3-channel rope

    # projections
    attention_bias: bool = False
    attention_out_bias: bool = False
    mlp_bias: bool = False
    tie_word_embeddings: bool = False

    # block structure
    mlp_gated: bool = True        # False: fc1 -> act -> fc2 (phi/gptneox)
    parallel_blocks: bool = False  # x + attn(ln(x)) + mlp(ln'(x)) (phi/neox)

    # position encodings beyond rope
    alibi: bool = False            # bloom/mpt/baichuan-13b linear biases
    learned_pos: int = 0           # >0: learned absolute embeddings (gpt2/opt)

    # block/embedding variants
    embed_norm: bool = False       # bloom word_embeddings_layernorm
    norm_after: bool = False       # olmo2: x + norm(attn(x)) (no input norm)
    logit_scale: float = 1.0       # cohere final-logit multiplier
    # chatglm v1 (pre-RMSNorm GLM, reference models/chatglm.py): the residual
    # base is the LAYERNORMED input scaled by alpha=(2*num_layers)**0.5
    # (h = ln(x)*alpha + block(ln(x))); 0.0 = standard pre-norm residual
    glm_alpha: float = 0.0
    # chatglm v1 2D rotary: first half of head_dim rotates with sequence
    # positions, second half with generation block positions
    rope_2d: bool = False

    # attention extras
    sliding_window: int | None = None
    layer_types: tuple[str, ...] | None = None  # per-layer 'full'|'sliding'
    attn_softcap: float | None = None           # gemma2 attn logit softcap
    logit_softcap: float | None = None          # gemma2 final logit softcap
    attn_scale: float | None = None             # override 1/sqrt(d)
    embedding_multiplier: float = 1.0           # gemma sqrt(hidden)
    # minicpm "mup"-style depth scaling: each block's residual contribution
    # is multiplied by scale_depth/sqrt(num_layers) (reference minicpm.py:58
    # apply_residual_scale folds it into o_proj/down_proj; here it is a
    # config knob applied in the decoder so quantized weights stay faithful)
    residual_multiplier: float = 1.0
    # decilm variable GQA (reference decilm.py: per-module
    # num_key_value_heads): checkpoint kv-head counts per layer; the loader
    # replicates kv heads up to the uniform num_kv_heads (= max) so the
    # scan decoder keeps one homogeneous stacked cache — replication is
    # mathematically exact for GQA
    kv_heads_per_layer: tuple[int, ...] | None = None

    # MoE (mixtral / qwen-moe / deepseek-style)
    num_experts: int = 0
    num_experts_per_tok: int = 0
    moe_intermediate_size: int = 0
    num_shared_experts: int = 0
    moe_norm_topk_prob: bool = False
    moe_layer_start: int = 0        # deepseek: first k layers dense
    moe_router_scale: float = 1.0
    # router order: True = softmax over ALL experts then top-k (qwen-moe,
    # deepseek); False = top-k logits then softmax over the k (mixtral)
    moe_softmax_before_topk: bool = True
    moe_shared_expert_gate: bool = False  # qwen2-moe sigmoid shared gate
    # deepseek group-limited routing (reference deepseek.py moe_group_topk):
    # experts split into n_group groups; only topk_group groups are eligible
    moe_n_group: int = 0
    moe_topk_group: int = 0
    moe_score_func: str = "softmax"   # softmax (v2) | sigmoid (v3 noaux_tc)
    moe_group_score: str = "max"      # max (v2) | top2sum (v3)
    moe_score_bias: bool = False      # v3 e_score_correction_bias buffer

    # MLA — DeepSeek multi-head latent attention (reference deepseek.py:
    # 274-343; unbalanced-head cache kv.py:155).  head_dim is the FULL qk
    # head dim (nope+rope); the cache stores K at head_dim and V at
    # v_head_dim (k != v dims — the "unbalanced" cache).
    q_lora_rank: int | None = None
    kv_lora_rank: int | None = None
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int | None = None     # defaults to head_dim

    # hybrid stacks: a block is named by a letter of the pattern.  nemotron_h:
    # ONE mixer a block, 'M' Mamba-2 (ops/ssm.py), 'E' experts, '*'
    # attention.  falcon_h1: 'P', a PARALLEL block, where attention and a
    # Mamba-2 mixer read one normed input and are summed, then a dense gated
    # MLP: a KV layer AND a state slot a block.  The weights are stacked per
    # KIND and the decoder walks the pattern
    hybrid_pattern: str | None = None
    ssm_num_heads: int = 0
    ssm_head_dim: int = 0
    ssm_n_groups: int = 0
    ssm_state_size: int = 0
    ssm_conv_kernel: int = 4
    moe_shared_intermediate_size: int = 0   # an always-on, ungated shared expert
    # muP multipliers (falcon_h1), applied to ACTIVATIONS where the published
    # equations put them, never folded into a quantized plane; 1.0 / None
    # elsewhere.  With ``embedding_multiplier`` and ``logit_scale`` (the
    # head's) they are the family's nine.  ``ssm_multipliers`` weighs the
    # z, x, B, C and dt columns of the mixer's ``in_proj`` output;
    # ``mlp_multipliers`` the gate (INSIDE the activation) and the down
    # projection's output
    attention_in_multiplier: float = 1.0
    attention_out_multiplier: float = 1.0
    key_multiplier: float = 1.0
    ssm_in_multiplier: float = 1.0
    ssm_out_multiplier: float = 1.0
    ssm_multipliers: tuple[float, ...] | None = None
    mlp_multipliers: tuple[float, float] | None = None
    # an expert layer that holds PART of its experts (one chip's share of a
    # deployment that divides them): the router scores all ``num_experts``,
    # this layer computes experts [first, first + held) and leaves the
    # others' part out
    moe_first_expert: int = 0
    moe_experts_held: int = 0

    # learned sparse attention over the MLA latent (glm_moe_dsa; DeepSeek-V3.2's
    # indexer): a layer whose ``indexer_types`` entry is "full" scores every
    # cached token with ``index_n_heads`` small heads of ``index_head_dim``
    # against a cached index key and keeps the ``index_topk`` best; a
    # "shared" layer attends over the selection of the nearest full layer
    # below it.  0 / None: every head attends every token
    index_topk: int = 0
    index_n_heads: int = 0
    index_head_dim: int = 0
    indexer_types: tuple[str, ...] | None = None

    # manifold-constrained hyper-connections (xing4_0; Xie et al.,
    # arXiv:2512.24880): the residual is ``hc_mult`` streams a token, and
    # every sublayer reads a learned mix of them and writes back through a
    # (Sinkhorn-normalised, ``hc_sinkhorn_iters`` rounds) doubly stochastic
    # mix (ops/hyper.py).  1: one stream, ``x + F(norm(x))``
    hc_mult: int = 1
    hc_sinkhorn_iters: int = 0
    hc_eps: float = 0.0
    hc_res_clamp: tuple[float, float] = (0.0, 0.0)

    # a per-layer MIXER kind (glm5_next_text): "kda" (Kimi Delta Attention,
    # ops/kda.py: a delta-rule state ``kda_num_heads`` x ``kda_head_dim`` x
    # ``kda_head_dim`` float32 a row a layer, no KV) or "mla" (latent
    # attention, with its indexer where ``index_topk``).  None: every layer
    # attends.  The paged pool holds the "mla" layers alone and the recurrent
    # state the "kda" ones (``kv_layers``, ``kda_layers``)
    mixer_types: tuple[str, ...] | None = None
    kda_num_heads: int = 0
    kda_head_dim: int = 0
    kda_conv_kernel: int = 4
    kda_gate_lower_bound: float = -5.0
    # the indexer's keys POOLED: one index key a complete block of
    # ``index_kpool`` positions (their mean); a query scores blocks, keeps
    # ``index_topk / index_kpool`` of them and always attends the positions
    # after the last complete block.  1: a key a token
    index_kpool: int = 1
    # ``swiglu_limit``: silu(min(gate, l)) * clip(up, -l, l) in every gated
    # MLP and expert.  None: no clamp
    mlp_act_limit: float | None = None

    def layer_is_sliding(self, layer_idx: int) -> bool:
        if self.layer_types is not None:
            return self.layer_types[layer_idx] == "sliding_attention"
        return self.sliding_window is not None

    def layer_is_moe(self, layer_idx: int) -> bool:
        return self.num_experts > 0 and layer_idx >= self.moe_layer_start

    @property
    def kv_layers(self) -> int:
        """Layers the KV cache holds: the attention blocks."""
        if self.hybrid_pattern is not None:
            return sum(self.hybrid_pattern.count(c) for c in "*P")
        if self.mixer_types is not None:
            return sum(t == "mla" for t in self.mixer_types)
        return self.num_layers

    @property
    def index_layers(self) -> int:
        """Layers that hold an index-key cache: the "full" indexers."""
        return sum(t == "full" for t in self.indexer_types or ())

    @property
    def ssm_layers(self) -> int:
        """Blocks that carry recurrent state (0: the cache is KV alone)."""
        return sum((self.hybrid_pattern or "").count(c) for c in "MP")

    @property
    def kda_layers(self) -> int:
        """Layers whose mixer is Kimi Delta Attention (a state slot each)."""
        return sum(t == "kda" for t in self.mixer_types or ())

    @property
    def recurrent_layers(self) -> int:
        """Layers that carry per-row recurrent state, of either kind."""
        return self.ssm_layers + self.kda_layers

    @property
    def chunk_grid(self) -> int:
        """The block a prompt of this model is cut at multiples of (its last
        piece apart): the chunked recurrence's block, so that a token's bits
        do not depend on how its prompt was cut (ops/kda.py).  0: any cut."""
        from ipex_llm_tpu.ops.kda import CHUNK

        return CHUNK if self.kda_layers else 0

    @property
    def ssm_conv_dim(self) -> int:
        return (self.ssm_num_heads * self.ssm_head_dim
                + 2 * self.ssm_n_groups * self.ssm_state_size)

    @property
    def q_dim(self) -> int:
        return self.num_heads * self.head_dim

    @property
    def kv_dim(self) -> int:
        return self.num_kv_heads * self.head_dim

    @property
    def is_mla(self) -> bool:
        return self.kv_lora_rank is not None

    @property
    def v_dim(self) -> int:
        """Per-head V dim (== head_dim except MLA's unbalanced cache)."""
        return self.v_head_dim if self.v_head_dim is not None else self.head_dim
