"""Schema-constrained JSON generation (the xgrammar-shim equivalent).

Reference counterpart: xgrammar.py's logits-processor intent; here the
schema subset compiles into the pushdown validator so every emitted token
keeps the output a prefix of a conforming document.

    python examples/structured_json.py [--model PATH]
"""

import json

from _tiny_model import model_arg


SCHEMA = {
    "type": "object",
    "properties": {
        "name": {"type": "string"},
        "age": {"type": "integer"},
    },
    "required": ["age"],
    "additionalProperties": False,
}


def main():
    args, model_path = model_arg()
    from transformers import AutoTokenizer

    from ipex_llm_tpu.structured import generate_json
    from ipex_llm_tpu.transformers import AutoModelForCausalLM

    model = AutoModelForCausalLM.from_pretrained(
        model_path, load_in_low_bit="sym_int4"
    )
    tokenizer = AutoTokenizer.from_pretrained(model_path)
    ids = list(tokenizer("Describe a person as JSON: ")["input_ids"])
    text = generate_json(model.config, model.params, tokenizer, ids,
                         max_new_tokens=96, schema=SCHEMA)
    print("raw:", text)
    doc = json.loads(text)
    assert isinstance(doc.get("age"), int)
    print("parsed + schema-conforming:", doc)


if __name__ == "__main__":
    main()
