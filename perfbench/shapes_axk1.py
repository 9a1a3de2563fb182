"""Bytes and operations a tick of ``model_type: axk1`` must move through the
two mechanisms the family adds, from the configuration alone.  Part of the
yardstick, beside shapes.py: a roofline share divides THESE numbers, not the
program's own accounting, by device time.  All at the LOGICAL sizes: what the
program pads (its kernel's tiles, a page's lanes) is its cost and lowers its
share.

- the latent pool holds ONE row a token a layer, shared by every head: the
  compressed KV (``kv_lora_rank``) and the rotated key (``qk_rope_head_dim``),
  bf16.  The absorbed walk reads a live page of it once a layer;
- for each live token the walk multiplies every head's query against the
  row as a key (rank + rope wide) and accumulates the row as a value (rank
  wide): 2 x heads x (rank + rope + rank) operations a token a layer for ONE
  query a head (a decode row; a chunk's queries do that many times more, so
  counting one errs low);
- an expert that is HIT by some token of a layer call has its packed planes
  read once: gate and up ``[hidden, moe_intermediate]`` each and down
  ``[moe_intermediate, hidden]``, 4-bit codes and one fp16 scale a block of
  32: THREE planes, a gated expert's.
"""

from __future__ import annotations

BLOCK = 32
LATENT_ELEMENT_BYTES = 2      # bf16; the program refuses any other


def packed_bytes(k: int, n: int) -> float:
    """A sym_int4 weight [k, n]: codes and fp16 scales."""
    return k * n / 2.0 + (k // BLOCK) * n * 2.0


def expert_bytes(hf: dict) -> float:
    """One routed expert's packed planes: gate, up and down."""
    h, ffn = int(hf["hidden_size"]), int(hf["moe_intermediate_size"])
    return 2 * packed_bytes(h, ffn) + packed_bytes(ffn, h)


def latent_row_bytes(hf: dict) -> int:
    """One token's row in one layer of the latent pool."""
    return (int(hf["kv_lora_rank"]) + int(hf["qk_rope_head_dim"])
            ) * LATENT_ELEMENT_BYTES


def latent_page_bytes(hf: dict, config: dict) -> int:
    """One page of one layer of the latent pool."""
    return int(config["engine"]["page_size"]) * latent_row_bytes(hf)


def attention_layers(hf: dict) -> int:
    return int(hf["num_hidden_layers"])


def walk_flops_per_token(hf: dict) -> int:
    """The absorbed walk's operations for one live token of one layer under
    ONE query a head."""
    rank, rope = int(hf["kv_lora_rank"]), int(hf["qk_rope_head_dim"])
    return 2 * int(hf["num_attention_heads"]) * (rank + rope + rank)
