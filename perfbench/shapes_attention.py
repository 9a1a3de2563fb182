"""What the attention kernel has to read, from the configuration: the bytes
of one page of one layer of the KV pool (K and V: kv heads x page x head
size x the pool's bytes an element) and the number of layers that hold
attention.  The hand-written half of ``attn_roofline``
(readers/attn_roofline.py); nothing here imports the program."""

POOL_ELEMENT_BYTES = {"bf16": 2, "fp8": 1}


def page_bytes(hf: dict, config: dict) -> int:
    """K and V of one page of one layer, as the pool stores them."""
    eng = config["engine"]
    head = hf.get("head_dim") or hf["hidden_size"] // hf["num_attention_heads"]
    return (2 * hf["num_key_value_heads"] * eng["page_size"] * head
            * POOL_ELEMENT_BYTES[eng.get("kv_storage", "bf16")])


def attention_layers(hf: dict) -> int:
    """Layers whose mixer is attention: every layer of a dense model, the
    ``*`` blocks of a ``hybrid_override_pattern``."""
    pattern = hf.get("hybrid_override_pattern")
    return pattern.count("*") if pattern else hf["num_hidden_layers"]
