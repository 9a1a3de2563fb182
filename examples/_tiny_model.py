"""Shared helper: build a tiny random llama checkpoint + char tokenizer.

The reference's examples download checkpoints from the Hub; this
environment has zero egress, so every example accepts ``--model PATH`` and
falls back to a synthetic checkpoint that exercises the identical code
path (quantize-on-load, tokenizer, generate).  Swap in a real model path
to reproduce the reference's example outputs.
"""

from __future__ import annotations

import os
import sys

# examples run from any cwd without installing the package
sys.path.insert(0, os.path.abspath(os.path.join(os.path.dirname(__file__), "..")))


# Synthetic checkpoints are generated once and reused from a directory of the
# checkout that .gitignore lists.  Examples take the device JAX gives them;
# ``JAX_PLATFORMS=cpu`` is how to ask for the CPU.
CACHE_DIR = os.path.join(os.path.dirname(os.path.abspath(__file__)), ".cache")


def tiny_checkpoint(path: str = os.path.join(CACHE_DIR, "tiny")) -> str:
    if os.path.exists(os.path.join(path, "config.json")):
        return path
    import torch
    from transformers import LlamaConfig, LlamaForCausalLM

    cfg = LlamaConfig(
        vocab_size=256, hidden_size=64, intermediate_size=160,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        tie_word_embeddings=False,
    )
    torch.manual_seed(0)
    LlamaForCausalLM(cfg).eval().save_pretrained(path, safe_serialization=True)

    from tokenizers import Regex, Tokenizer, models, pre_tokenizers
    from transformers import PreTrainedTokenizerFast

    vocab = {chr(i + 32): i for i in range(0, 224)}
    vocab["<unk>"] = 224
    vocab["</s>"] = 225
    tok = Tokenizer(models.WordLevel(vocab, unk_token="<unk>"))
    tok.pre_tokenizer = pre_tokenizers.Split(Regex("."), "isolated")
    PreTrainedTokenizerFast(
        tokenizer_object=tok, unk_token="<unk>", eos_token="</s>"
    ).save_pretrained(path)
    return path


def model_arg(argv=None) -> str:
    import argparse

    p = argparse.ArgumentParser()
    p.add_argument("--model", default=None,
                   help="HF checkpoint dir (default: synthetic tiny model)")
    p.add_argument("--prompt", default="Once upon a time")
    p.add_argument("--n-predict", type=int, default=16)
    args, _ = p.parse_known_args(argv)
    return args, (args.model or tiny_checkpoint())
