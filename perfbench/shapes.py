"""Operations and bytes of a tick's quantized matmuls, from the configuration
and the tick's token counts.  Part of the yardstick: the roofline share
divides THESE numbers, not the program's own accounting, by the device time
of the kernel's events.

A decoder tick pushes M token rows through four stacked linears per layer
(qkv, o, gate_up, down) and ``head_rows`` rows through the head.  One call of
the dequant-matmul kernel on an int4 weight [K, N] with M rows needs

    operations = 2 * M * K * N
    bytes      = K * N / 2            packed 4-bit codes
               + (K / 32) * N * 2     fp16 scales, one per block of 32
               + M * K * 2            bf16 activations in
               + M * N * 2            bf16 result out

whatever the kernel pads or re-reads inside: padding (a K that is no multiple
of the kernel's tile) and re-reading are the kernel's cost and lower its
share.
"""

from __future__ import annotations

BLOCK = 32


def linear_shapes(hf: dict) -> dict[str, tuple[int, int]]:
    """{name: (K, N)} of the per-layer linears and the head."""
    h, ffn = int(hf["hidden_size"]), int(hf["intermediate_size"])
    n_q, n_kv = int(hf["num_attention_heads"]), int(hf["num_key_value_heads"])
    d = int(hf.get("head_dim") or h // n_q)
    return {"qkv": (h, (n_q + 2 * n_kv) * d), "o": (n_q * d, h),
            "gate_up": (h, 2 * ffn), "down": (ffn, h),
            "lm_head": (h, int(hf["vocab_size"]))}


def qmatmul_cost(k: int, n: int, m: int) -> tuple[float, float]:
    """(operations, bytes) of one call."""
    ops = 2.0 * m * k * n
    byts = k * n / 2.0 + (k // BLOCK) * n * 2.0 + m * k * 2.0 + m * n * 2.0
    return ops, byts


def min_seconds(ops: float, byts: float, peak: dict) -> tuple[float, str]:
    """The least time the chip could take, and which bound binds."""
    t_c, t_b = ops / peak["bf16_flops"], byts / peak["hbm_bytes_per_s"]
    return (t_c, "compute") if t_c >= t_b else (t_b, "bandwidth")


def pass_min_seconds(hf: dict, m: int, head_rows: int, peak: dict) -> dict:
    """One pass of the whole decoder at ``m`` token rows (``head_rows`` of
    them through the head): the summed least time of its qmatmul calls, call
    by call, with the count of calls and the seconds under each bound."""
    layers = int(hf["num_hidden_layers"])
    total, calls = 0.0, 0
    by_bound = {"compute": 0.0, "bandwidth": 0.0}
    for name, (k, n) in linear_shapes(hf).items():
        rows, reps = (head_rows, 1) if name == "lm_head" else (m, layers)
        if rows <= 0:
            continue
        t, bound = min_seconds(*qmatmul_cost(k, n, rows), peak)
        total += t * reps
        by_bound[bound] += t * reps
        calls += reps
    return {"seconds": total, "calls": calls, "by_bound": by_bound}


def peak_for(peaks: dict, device_kind: str) -> dict:
    """The peaks of ``device_kind``; a device not in the table is an error,
    not a default."""
    if device_kind not in peaks or not isinstance(peaks[device_kind], dict):
        raise KeyError(f"no peaks for device_kind {device_kind!r} in "
                       f"perfbench/peaks.json")
    return peaks[device_kind]
