"""Work a tick of ``model_type: glm5_next_text`` must do in the mechanisms the
family adds, from the configuration alone (THIS family's keys:
``linear_attn_config``, ``layer_types``, ``index_*``, ``kv_lora_rank``).  Part
of the yardstick, beside shapes.py: a roofline share divides THESE numbers,
not the program's own accounting, by device time, and they count the same work
whatever implements it.  Logical sizes: what the program pads, transposes or
reads again is its cost and lowers its share.  Nothing here imports the
program.

Kimi Delta Attention (a ``linear_attention`` layer; H heads, key and value
dims D, conv kernel K):

- a row's state in one layer is ``S`` [H, D, D] float32 and three conv tails
  (q, k, v: [K-1, H D] bf16 each).  A decode row has it read once and written
  once in EVERY such layer (:func:`kda_step_bytes`); the step's arithmetic
  (~6 operations a state value) is three orders under the chip's operations a
  byte, so the least time is the bytes at the HBM peak.
- a prefill chunk runs the chunkwise form on blocks of ``CHUNK`` = 64 tokens
  (:func:`kda_block_flops`, a head a block: the cumulative decay as a
  triangular product, the state's two products ``[K exp(G); Q exp(G)] S0``,
  the pairwise rows of ``A`` and ``P`` a 16-row sub-block at a time, the
  forward substitution's off-diagonal products, ``P U`` and the state's update
  ``(kb exp(G_C - G))^T U``; the 16 x 16 diagonal inverses are a few percent
  and left out), and moves q, k, v, the log-decay and the output once each in
  float32 and a row's state once each way a call (:func:`kda_chunk_bytes`).
  The least time is the larger of the FLOPs at the bf16 MXU peak (the program
  multiplies float32 at precision "highest", six bf16 passes: its cost) and
  the bytes at the HBM peak.

The pooled indexer and the sparse walk (a ``deepseek_sparse_attention``
layer): a query scores one key a complete block of ``index_kpool`` positions
(:func:`index_score_flops`, a block scored) and attends the chosen blocks'
positions and its tail's, one latent row each (:func:`walk_bytes`, a position
attended).
"""

from __future__ import annotations

CHUNK = 64          # tokens a block of the chunked recurrence
SUB = 16            # rows of a sub-block of pairwise decays
STATE_BYTES = 4     # float32 state
TAIL_BYTES = 2      # bf16 conv tails
LATENT_BYTES = 2    # bf16 latent rows


def kda_layers(hf: dict) -> int:
    return sum(t == "linear_attention" for t in hf["layer_types"])


def sparse_layers(hf: dict) -> int:
    return sum(t == "deepseek_sparse_attention" for t in hf["layer_types"])


def _kda(hf: dict) -> tuple[int, int, int]:
    la = hf["linear_attn_config"]
    return (int(la["num_heads"]), int(la["head_dim"]),
            int(la.get("short_conv_kernel_size", 4)))


def kda_state_row_bytes(hf: dict) -> float:
    """One row's state in ONE KDA layer."""
    h, d, k = _kda(hf)
    return h * d * d * STATE_BYTES + (k - 1) * 3 * h * d * TAIL_BYTES


def kda_step_bytes(hf: dict, row_layers: int) -> float:
    """The least a decode stage moves: ``row_layers`` (rows stepped x KDA
    layers) states, each read and written."""
    return 2.0 * row_layers * kda_state_row_bytes(hf)


def kda_block_flops(d: int, c: int = CHUNK) -> float:
    """One (head, block of ``c`` tokens) of the chunkwise form."""
    return 2.0 * (c * c * d               # G = tril @ g
                  + 2 * c * d * d         # [K exp(G); Q exp(G)] @ S0
                  + (c // SUB) * 2 * SUB * d * c   # A and P rows
                  + c * c * d / 2         # forward substitution
                  + c * c * d             # P @ U
                  + c * d * d)            # the state's update


def kda_chunk_flops(hf: dict, token_layers: int) -> float:
    """``token_layers`` = tokens through the chunked form x KDA layers."""
    h, d, _ = _kda(hf)
    return token_layers / CHUNK * h * kda_block_flops(d)


def kda_chunk_bytes(hf: dict, token_layers: int, row_layers: int) -> float:
    """q, k, v, the log-decay in and the output out, float32, a token a
    layer; a row's state in and out a layer a call."""
    h, d, _ = _kda(hf)
    return (5.0 * token_layers * h * d * STATE_BYTES
            + 2.0 * row_layers * h * d * d * STATE_BYTES)


def index_score_flops(hf: dict, blocks_scored: int) -> float:
    """One pooled key scored by one query: every index head's dot product
    and its weighted relu."""
    return 2.0 * blocks_scored * int(hf["index_n_heads"]) * (
        int(hf["index_head_dim"]) + 1)


def latent_row_bytes(hf: dict) -> int:
    return (int(hf["kv_lora_rank"]) + int(hf.get("qk_rope_head_dim") or 0)
            ) * LATENT_BYTES


def walk_bytes(hf: dict, positions_attended: int) -> float:
    """One attended position: its latent row read once."""
    return float(positions_attended) * latent_row_bytes(hf)
