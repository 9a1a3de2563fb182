"""INT4 generation with the drop-in transformers API.

Reference counterpart: example scripts under
python/llm/example/GPU/HuggingFace/LLM/*/generate.py — the canonical
"load_in_4bit then model.generate" flow.

    python examples/generate.py [--model PATH] [--prompt TEXT] [--n-predict N]
"""

from _tiny_model import model_arg


def main():
    args, model_path = model_arg()
    from transformers import AutoTokenizer

    from ipex_llm_tpu.transformers import AutoModelForCausalLM

    model = AutoModelForCausalLM.from_pretrained(
        model_path, load_in_4bit=True
    )
    tokenizer = AutoTokenizer.from_pretrained(model_path)

    input_ids = tokenizer(args.prompt, return_tensors="np")["input_ids"]
    output = model.generate(input_ids, max_new_tokens=args.n_predict)
    print(tokenizer.decode(list(output[0]), skip_special_tokens=True))
    print(f"[ttft {model.first_cost * 1e3:.1f} ms, "
          f"decode {1.0 / max(model.rest_cost_mean, 1e-9):.1f} tok/s]")


if __name__ == "__main__":
    main()
