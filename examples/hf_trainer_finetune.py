"""Finetune with the transformers.Trainer recipe surface on TPU.

Reference counterpart: the QLoRA finetuning quickstart driven by the HF
Trainer (training_patch.py + axolotl_quickstart): same TrainingArguments,
same dataset-of-dicts shape, the TPU-native step functions underneath.

    python examples/hf_trainer_finetune.py
"""

import os

import numpy as np

from _tiny_model import CACHE_DIR, tiny_checkpoint

OUT_DIR = os.path.join(CACHE_DIR, "tpu-finetune")


def main():
    from ipex_llm_tpu.training import (LoraConfig, TPUTrainer,
                                       get_peft_model)
    from ipex_llm_tpu.transformers import AutoModelForCausalLM

    model = AutoModelForCausalLM.from_pretrained(
        tiny_checkpoint(), load_in_low_bit="sym_int4")
    peft = get_peft_model(model, LoraConfig(r=8, lora_alpha=16))

    rng = np.random.default_rng(0)
    seq = list(rng.integers(0, 200, 24))
    data = [{"input_ids": seq, "labels": [-100] * 8 + seq[8:]}
            for _ in range(16)]

    try:
        from transformers import TrainingArguments

        args = TrainingArguments(
            output_dir=OUT_DIR, per_device_train_batch_size=4,
            num_train_epochs=2, learning_rate=2e-3, logging_steps=2,
            report_to=[],
        )
    except Exception:
        class args:  # noqa: N801 — duck-typed TrainingArguments
            output_dir = OUT_DIR
            per_device_train_batch_size = 4
            num_train_epochs = 2
            learning_rate = 2e-3
            logging_steps = 2

    trainer = TPUTrainer(peft, args=args, train_dataset=data)
    result = trainer.train()
    print("done:", result)


if __name__ == "__main__":
    main()
