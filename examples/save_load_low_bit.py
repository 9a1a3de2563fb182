"""Quantize once, save the low-bit checkpoint, reload instantly.

Reference counterpart: example/GPU/HuggingFace/More-Data-Types +
``save_low_bit``/``load_low_bit`` (reference model.py).

    python examples/save_load_low_bit.py [--model PATH]
"""

import tempfile

from _tiny_model import model_arg


def main():
    args, model_path = model_arg()
    import numpy as np

    from ipex_llm_tpu.transformers import AutoModelForCausalLM

    model = AutoModelForCausalLM.from_pretrained(
        model_path, load_in_low_bit="sym_int4"
    )
    prompt = np.array([[3, 14, 15, 92, 65]], np.int32)
    want = np.asarray(model.generate(prompt, max_new_tokens=8))

    with tempfile.TemporaryDirectory() as low_bit_dir:
        model.save_low_bit(low_bit_dir)
        reloaded = AutoModelForCausalLM.load_low_bit(low_bit_dir)
        got = np.asarray(reloaded.generate(prompt, max_new_tokens=8))

    assert np.array_equal(want, got), "low-bit reload must be bit-identical"
    print("save_low_bit -> load_low_bit round-trip: outputs identical")
    print("tokens:", got[0].tolist())


if __name__ == "__main__":
    main()
