"""FlashMoE-equivalent: decode an MoE model whose experts live in host RAM.

Reference counterpart: docs/mddocs/Quickstart/flashmoe_quickstart.md
(DeepSeek-671B / Qwen3MoE-235B on 1-2 GPUs via CPU-resident experts).
Synthesizes a tiny mixtral-shaped model and decodes with an HBM expert
cache budget far below the expert footprint, printing the cache hit rate.

    python examples/moe_expert_offload.py
"""

import _tiny_model  # noqa: F401 — puts the repo root on sys.path


def main():
    import numpy as np

    from ipex_llm_tpu.models.random_init import llama_config, random_params
    from ipex_llm_tpu.offload import OffloadedMoE

    cfg = llama_config(
        hidden_size=64, intermediate_size=96, num_layers=2, num_heads=4,
        num_kv_heads=2, vocab_size=256, num_experts=8,
        num_experts_per_tok=2, moe_intermediate_size=96,
        moe_softmax_before_topk=False, moe_norm_topk_prob=True,
    )
    params = random_params(cfg, qtype="sym_int4")
    # a budget of ~2 experts forces real streaming through the LRU cache
    moe = OffloadedMoE(cfg, params, hbm_budget_mb=0.05)

    prompt = np.asarray([1, 5, 9, 13, 21], np.int32)
    out = moe.generate(prompt, max_new_tokens=12)
    print("generated ids:", out[0, len(prompt):].tolist())
    total = moe.store.hits + moe.store.misses
    print(f"expert cache: {moe.store.hits}/{total} hits "
          f"({100 * moe.store.hits / max(total, 1):.0f}%)")


if __name__ == "__main__":
    main()
