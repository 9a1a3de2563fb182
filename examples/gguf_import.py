"""Load a llama.cpp GGUF file directly (from_gguf).

Reference counterpart: example/GPU/HuggingFace/Advanced-Quantizations/GGUF
(``AutoModelForCausalLM.from_gguf``).  K-quant tensors stay in their raw
superblock bytes and dequantize inside the jitted forward.

    python examples/gguf_import.py --gguf /path/to/model.gguf
    python examples/gguf_import.py            # synthesizes a tiny q8_0 file
"""

import argparse
import os
import sys

from _tiny_model import CACHE_DIR


def _synthesize_tiny_gguf(path: str) -> str:
    """Export a tiny random HF llama to GGUF q8_0 (no assets needed)."""
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
    import torch
    from transformers import LlamaConfig, LlamaForCausalLM

    from tests.test_gguf import _export_gguf

    cfg = LlamaConfig(
        vocab_size=160, hidden_size=64, intermediate_size=96,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        tie_word_embeddings=False, max_position_embeddings=256,
    )
    torch.manual_seed(0)
    _export_gguf(LlamaForCausalLM(cfg).eval(), path, wtype="q8_0")
    return path


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--gguf", default=None)
    args = p.parse_args()

    import numpy as np

    from ipex_llm_tpu.transformers import AutoModelForCausalLM

    gguf = args.gguf or _synthesize_tiny_gguf(
        os.path.join(CACHE_DIR, "tiny_example.gguf"))
    model, _tok = AutoModelForCausalLM.from_gguf(gguf)
    out = model.generate(np.array([[2, 4, 6, 8]], np.int32), max_new_tokens=8)
    print("loaded", gguf)
    print("tokens:", np.asarray(out)[0].tolist())


if __name__ == "__main__":
    main()
