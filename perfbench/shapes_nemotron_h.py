"""Bytes a tick of ``model_type: nemotron_h`` must move through the two
mechanisms the family adds, from the configuration alone.  Part of the
yardstick, beside shapes.py: a roofline share divides THESE numbers, not the
program's own accounting, by device time.  Both are at the LOGICAL sizes:
what the program pads (its kernel's tiles) is its cost and lowers its share.

- an expert that is HIT by some token of a layer call has its packed planes
  read once: up ``[hidden, moe_intermediate]`` and down ``[moe_intermediate,
  hidden]``, 4-bit codes and one fp16 scale a block of 32.  An ungated
  expert (relu2), so no gate plane.
- a row that is ACTIVE in a decode tick has its recurrent state read and
  written once in every Mamba-2 block: ``h`` [heads, head_dim, state]
  float32 and the conv tail [kernel - 1, conv width] bf16.
"""

from __future__ import annotations

BLOCK = 32


def packed_bytes(k: int, n: int) -> float:
    """A sym_int4 weight [k, n]: codes and fp16 scales."""
    return k * n / 2.0 + (k // BLOCK) * n * 2.0


def expert_bytes(hf: dict) -> float:
    """One routed expert's packed planes."""
    h, ffn = int(hf["hidden_size"]), int(hf["moe_intermediate_size"])
    return packed_bytes(h, ffn) + packed_bytes(ffn, h)


def mamba_blocks(hf: dict) -> int:
    return hf["hybrid_override_pattern"].count("M")


def state_row_bytes(hf: dict) -> float:
    """One row's recurrent state in ONE Mamba-2 block."""
    heads, hd = int(hf["mamba_num_heads"]), int(hf["mamba_head_dim"])
    n, groups = int(hf["ssm_state_size"]), int(hf["n_groups"])
    conv = heads * hd + 2 * groups * n
    return heads * hd * n * 4.0 + (int(hf["conv_kernel"]) - 1) * conv * 2.0


def state_tick_bytes(hf: dict, rows_active: int) -> float:
    """The least a decode tick moves of the state: every active row's,
    read and written, in every Mamba-2 block."""
    return 2.0 * rows_active * mamba_blocks(hf) * state_row_bytes(hf)
