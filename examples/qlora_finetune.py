"""QLoRA fine-tuning on quantized base weights.

Reference counterpart: example/GPU/LLM-Finetuning/QLoRA (qlora.py's
``get_peft_model`` flow).  The base stays packed INT4 in HBM; LoRA adapters
train in bf16 with a straight-through dequant gradient; ``merge_lora`` does
error-compensated requantization back into the packed format.

    python examples/qlora_finetune.py [--model PATH]
"""

from _tiny_model import model_arg


def main():
    args, model_path = model_arg()
    import jax
    import numpy as np
    import optax

    from ipex_llm_tpu.training.qlora import (
        LoraConfig,
        init_lora,
        make_qlora_train_step,
        merge_lora,
    )
    from ipex_llm_tpu.transformers import AutoModelForCausalLM

    model = AutoModelForCausalLM.from_pretrained(
        model_path, load_in_low_bit="sym_int4"
    )
    cfg, params = model.config, model.params

    lc = LoraConfig(r=8, lora_alpha=16)
    adapters = init_lora(jax.random.PRNGKey(0), cfg, params, lc)
    opt = optax.adam(3e-2)
    step = make_qlora_train_step(cfg, opt, lc)
    opt_state = opt.init(adapters)

    rng = np.random.default_rng(0)
    tokens = rng.integers(1, cfg.vocab_size, (1, 24)).astype(np.int32)
    losses = []
    for it in range(12):
        adapters, opt_state, loss = step(adapters, opt_state, tokens, params)
        losses.append(float(loss))
        print(f"step {it}: loss {losses[-1]:.4f}")
    assert losses[-1] < losses[0], "loss should decrease on the toy batch"

    merged = merge_lora(params, adapters, lc)
    print("merged LoRA into the packed INT4 weights "
          f"(qkv stays {merged['layers']['qkv'].qtype})")


if __name__ == "__main__":
    main()
