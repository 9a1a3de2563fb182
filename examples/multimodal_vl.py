"""Vision-language generation (LLaVA-style CLIP tower + embed replacement).

Reference counterpart: example/GPU/Multimodal (qwen-vl / minicpm-v chat
scripts).  Synthesizes a tiny random LLaVA checkpoint when --model is not
given, so the script runs with zero downloads.

    python examples/multimodal_vl.py [--model LLAVA_PATH]
"""

import os

from _tiny_model import CACHE_DIR, model_arg


def _tiny_llava(path=os.path.join(CACHE_DIR, "tiny_llava")):
    if os.path.exists(os.path.join(path, "config.json")):
        return path
    import torch
    from transformers import LlavaConfig, LlavaForConditionalGeneration

    cfg = LlavaConfig(
        text_config=dict(model_type="llama", vocab_size=160, hidden_size=64,
                         intermediate_size=128, num_hidden_layers=2,
                         num_attention_heads=4, num_key_value_heads=2,
                         max_position_embeddings=256,
                         tie_word_embeddings=False),
        vision_config=dict(hidden_size=32, intermediate_size=64,
                           num_hidden_layers=3, num_attention_heads=2,
                           image_size=16, patch_size=4,
                           hidden_act="quick_gelu"),
        image_token_index=150,
    )
    torch.manual_seed(0)
    LlavaForConditionalGeneration(cfg).eval().save_pretrained(
        path, safe_serialization=True)
    return path


def main():
    import numpy as np

    args, _ = model_arg()
    path = args.model or _tiny_llava()

    from ipex_llm_tpu.transformers import AutoModelForVision2Seq

    model = AutoModelForVision2Seq.from_pretrained(path,
                                                   load_in_low_bit="sym_int4")
    rng = np.random.default_rng(0)
    # a random "image" + a prompt with one image-token slot per patch
    pixels = rng.standard_normal((1, 3, 16, 16)).astype(np.float32)
    ids = np.asarray([5, 9] + [model.image_token_id] * 16 + [7, 11],
                     np.int32)
    out = model.generate(ids, pixel_values=pixels, max_new_tokens=12)
    print("prompt tokens:", ids.tolist())
    print("generated ids:", out[0, len(ids):].tolist())


if __name__ == "__main__":
    main()
