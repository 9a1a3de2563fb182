"""vLLM-style offline batch inference (LLM + SamplingParams).

Reference counterpart: example/GPU/vLLM-Serving/offline_inference.py —
same script shape, served by this framework's own paged TPU engine (no
vLLM install needed).

    python examples/vllm_offline_inference.py [--model PATH]
"""

from _tiny_model import model_arg


def main():
    args, model_path = model_arg()
    from ipex_llm_tpu.vllm import LLM, SamplingParams

    prompts = [
        "Hello, my name is",
        "The capital of France is",
        "The future of AI is",
    ]
    sampling_params = SamplingParams(temperature=0.0, max_tokens=args.n_predict)

    llm = LLM(model=model_path, load_in_low_bit="sym_int4")
    try:
        outputs = llm.generate(prompts, sampling_params)
        for out in outputs:
            print(f"Prompt: {out.prompt!r}")
            print(f"Generated: {out.outputs[0].text!r} "
                  f"({out.outputs[0].finish_reason})")
    finally:
        llm.shutdown()


if __name__ == "__main__":
    main()
