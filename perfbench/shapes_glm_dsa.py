"""Bytes and operations a tick of ``model_type: glm_moe_dsa`` must move through
the mechanisms the family adds, from the configuration alone.  Part of the
yardstick, beside shapes.py: a roofline share divides THESE numbers, not the
program's own accounting, by device time, and they count the same work
whatever implements it.  All at the LOGICAL sizes: what the program pads (a
pool row's lanes, a page read whole under a mask) is its cost and lowers its
share.

- the latent pool holds ONE row a token a layer, shared by every head: the
  compressed KV (``kv_lora_rank``) and the rotated key (``qk_rope_head_dim``),
  bf16.  A query attends ``min(position + 1, index_topk)`` cached tokens a
  layer, the selection of the nearest full indexer below; a decode row has to
  read each selected token's row once a layer;
- for each selected token the walk multiplies every head's query against the
  row as a key (rank + rope wide) and accumulates the row as a value (rank
  wide): 2 x heads x (rank + rope + rank) operations a (query, token) pair a
  layer;
- a layer whose indexer is full scores every cached token a query sees:
  ``index_n_heads`` dots of ``index_head_dim`` (2 x heads x dim operations a
  pair) against an index key of ``index_head_dim`` bf16 values, read once a
  query row (a decode row) or once a block of queries.
"""

from __future__ import annotations

ELEMENT_BYTES = 2             # bf16 latent and index key; nothing else is taken


def latent_row_bytes(hf: dict) -> int:
    """One token's row in one layer of the latent pool, as published."""
    return (int(hf["kv_lora_rank"]) + int(hf["qk_rope_head_dim"])
            ) * ELEMENT_BYTES


def index_key_bytes(hf: dict) -> int:
    """One token's index key in one full layer."""
    return int(hf["index_head_dim"]) * ELEMENT_BYTES


def attention_layers(hf: dict) -> int:
    return int(hf["num_hidden_layers"])


def full_layers(hf: dict) -> int:
    """Layers whose indexer is full (an index-key cache, a selection)."""
    types = hf.get("indexer_types")
    if types is not None:
        return sum(t == "full" for t in types)
    n = int(hf["num_hidden_layers"])
    freq = int(hf.get("index_topk_freq") or 1)
    skip = int(hf.get("index_skip_topk_offset",
                      hf.get("first_k_dense_replace", 0)))
    return sum(i < skip or (i - skip) % freq == freq - 1 for i in range(n))


def walk_flops_per_pair(hf: dict) -> int:
    """The absorbed walk's operations for one selected token under one
    query, every head, one layer."""
    rank, rope = int(hf["kv_lora_rank"]), int(hf["qk_rope_head_dim"])
    return 2 * int(hf["num_attention_heads"]) * (rank + rope + rank)


def score_flops_per_pair(hf: dict) -> int:
    """The indexer's operations for one cached token under one query, one
    full layer."""
    return 2 * int(hf["index_n_heads"]) * int(hf["index_head_dim"])


def cache_token_bytes(hf: dict) -> int:
    """What one cached token costs across the layers, as published: a latent
    row a layer and an index key a full layer."""
    return (attention_layers(hf) * latent_row_bytes(hf)
            + full_layers(hf) * index_key_bytes(hf))
