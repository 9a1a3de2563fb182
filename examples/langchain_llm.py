"""LangChain integration (TransformersLLM).

Reference counterpart: example/GPU/LangChain (llm/langchain adapters).
Works with langchain installed or falls back to the duck-typed adapter.

    python examples/langchain_llm.py [--model PATH]
"""

from _tiny_model import model_arg


def main():
    args, model_path = model_arg()
    from ipex_llm_tpu.langchain.llms import TransformersLLM

    llm = TransformersLLM.from_model_id(
        model_id=model_path,
        model_kwargs={"load_in_low_bit": "sym_int4"},
    )
    text = llm.invoke("Q: what is 2+2?\nA:", max_new_tokens=12)
    print(repr(text))


if __name__ == "__main__":
    main()
