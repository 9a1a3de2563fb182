"""Bytes a tick of ``model_type: xing4_0`` must move through the mechanism the
family adds, manifold-constrained hyper-connections (``hc_mult`` residual
streams a token, mixed around every sublayer), from the configuration alone.
Part of the yardstick, beside shapes.py: a roofline share divides THESE
numbers, not the program's own accounting, by device time, and they count the
same work whatever implements it (XLA operations, a fused kernel).  All at the
LOGICAL sizes: what the program pads or reads again (the streams once for the
coefficients and once for each mix, float32 copies) is its cost and lowers
its share.

- a token's residual is ``hc_mult`` streams of ``hidden_size`` bf16 values.
  One wrapped sublayer has to READ them once (the coefficients' RMS and
  projections, the pre-mix and the residual mix all take the same streams)
  and WRITE the re-mixed streams once; the sublayer's own input ``h`` and
  output are its own traffic and not counted;
- a sublayer's three projections ``P_pre`` [n*C, n], ``P_post`` [n*C, n] and
  ``P_res`` [n*C, n*n] are float32 and read once a call, whatever the tokens;
  the scalars and biases (3 + n + n + n*n values) are left out;
- every layer has two wrapped sublayers (attention; the MLP or expert layer).

The work is bandwidth-bound by three orders of magnitude (2 x (2n + n*n) = 48
operations a value read against the chip's ~240 operations a byte), so the
least time is the bytes at the HBM peak alone.
"""

from __future__ import annotations

STREAM_ELEMENT_BYTES = 2      # bf16 streams, the activations' type
COEF_ELEMENT_BYTES = 4        # float32 projections, never quantized


def streams(hf: dict) -> int:
    return int(hf["hc_mult"])


def sublayers(hf: dict) -> int:
    """Wrapped sublayers a token crosses in one pass of the model."""
    return 2 * int(hf["num_hidden_layers"])


def token_bytes(hf: dict) -> int:
    """One token through one wrapped sublayer: its streams read once and
    written once."""
    return 2 * streams(hf) * int(hf["hidden_size"]) * STREAM_ELEMENT_BYTES


def projection_bytes(hf: dict) -> int:
    """One sublayer's ``P_pre``, ``P_post`` and ``P_res``."""
    n = streams(hf)
    return n * int(hf["hidden_size"]) * (n + n + n * n) * COEF_ELEMENT_BYTES
