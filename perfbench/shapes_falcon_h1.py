"""Bytes a decode tick of ``model_type: falcon_h1`` must move of its
recurrent state, from the configuration alone (THIS family's keys:
``mamba_n_heads``, ``mamba_d_head``, ``mamba_d_state``, ``mamba_n_groups``,
``mamba_d_conv``, ``mamba_d_ssm``, ``num_hidden_layers``).  Part of the
yardstick, beside shapes.py: ``h1_ssm_state_roofline`` divides THESE numbers,
not the program's own accounting, by device time.  Logical sizes: what the
program pads or copies besides is its cost and lowers its share.

A row that is ACTIVE in a decode tick has its state read and written once in
EVERY block (each holds a Mamba-2 mixer beside its attention): ``h``
[mamba_n_heads, mamba_d_head, mamba_d_state] float32 and the conv tail
[mamba_d_conv - 1, d_ssm + 2 groups x state] bf16 (the configuration's
``assumed`` precisions).  Nothing here imports the program."""

from __future__ import annotations


def state_blocks(hf: dict) -> int:
    """Blocks that carry state: every block."""
    return int(hf["num_hidden_layers"])


def state_row_bytes(hf: dict) -> float:
    """One row's recurrent state in ONE block."""
    heads, hd = int(hf["mamba_n_heads"]), int(hf["mamba_d_head"])
    n, groups = int(hf["mamba_d_state"]), int(hf["mamba_n_groups"])
    d_ssm = int(hf.get("mamba_d_ssm") or heads * hd)
    conv = d_ssm + 2 * groups * n
    return heads * hd * n * 4.0 + (int(hf["mamba_d_conv"]) - 1) * conv * 2.0


def state_tick_bytes(hf: dict, rows_active: int) -> float:
    """The least a decode tick moves of the state: every active row's,
    read and written, in every block."""
    return 2.0 * rows_active * state_blocks(hf) * state_row_bytes(hf)
