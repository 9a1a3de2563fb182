"""Quantized checkpoint save/load (``save_low_bit`` / ``load_low_bit``).

Reference counterpart: model.py:59 ``save_low_bit`` which writes the quantized
torch state_dict plus ``bigdl_config.json``, and model.py:532 ``load_low_bit``
with meta-device init.  Here the param pytree (QTensor leaves = packed codes +
scales) is flattened to one safetensors file; reload is mmap-backed and needs
no "meta device" trick because nothing is ever materialized unquantized.
"""

from __future__ import annotations

import json
import os
from typing import Any

import jax.numpy as jnp
import numpy as np

from ipex_llm_tpu.quantize.core import QTensor, f16_bits, f16_view

CONFIG_NAME = "bigdl_config.json"  # reference-compatible filename (model.py:59)
WEIGHTS_NAME = "model_low_bit.safetensors"
FORMAT_VERSION = 1


def _walk(tree: dict, prefix: str = ""):
    for k, v in tree.items():
        p = f"{prefix}{k}"
        if isinstance(v, dict):
            yield from _walk(v, p + ".")
        else:
            yield p, v


def flatten_params(params: dict) -> tuple[dict[str, np.ndarray], dict[str, Any]]:
    """-> (name->array for safetensors, manifest of qtensor/scalar metadata)."""
    tensors: dict[str, np.ndarray] = {}
    manifest: dict[str, Any] = {"qtensors": {}, "scalars": {}, "version": FORMAT_VERSION}
    for path, v in _walk(params):
        if isinstance(v, QTensor):
            tensors[path + ".q.data"] = np.asarray(v.data)
            if v.scale_bits is not None:
                # fp16 on disk, whatever the tree holds on the device
                tensors[path + ".q.scales"] = f16_view(
                    np.asarray(v.scale_bits))
            if v.zeros is not None:
                tensors[path + ".q.zeros"] = np.asarray(v.zeros)
            manifest["qtensors"][path] = {
                "qtype": v.qtype,
                "shape": list(v.shape),
                "block_size": v.block_size,
            }
        elif isinstance(v, (float, int)):
            manifest["scalars"][path] = v
        else:
            arr = np.asarray(v)
            if arr.dtype == jnp.bfloat16:
                # safetensors-np can't write ml_dtypes bf16; store raw bits
                tensors[path] = arr.view(np.uint16)
                manifest.setdefault("bf16", []).append(path)
            else:
                tensors[path] = arr
    return tensors, manifest


def unflatten_params(
    tensors: dict[str, np.ndarray], manifest: dict[str, Any]
) -> dict:
    params: dict[str, Any] = {}

    def put(path: str, v: Any):
        parts = path.split(".")
        d = params
        for p in parts[:-1]:
            d = d.setdefault(p, {})
        d[parts[-1]] = v

    bf16 = set(manifest.get("bf16", []))
    qpaths = manifest["qtensors"]
    done = set()
    for name, arr in tensors.items():
        if name.endswith((".q.data", ".q.scales", ".q.zeros")):
            # rsplit: a param key literally named "q" (e.g. a vision
            # tower's q projection) contains ".q." itself
            base = name.rsplit(".q.", 1)[0]
            if base in done:
                continue
            done.add(base)
            meta = qpaths[base]
            put(
                base,
                QTensor(
                    data=jnp.asarray(tensors[base + ".q.data"]),
                    scale_bits=(
                        jnp.asarray(f16_bits(tensors[base + ".q.scales"]))
                        if base + ".q.scales" in tensors else None
                    ),
                    zeros=(
                        jnp.asarray(tensors[base + ".q.zeros"])
                        if base + ".q.zeros" in tensors else None
                    ),
                    qtype=meta["qtype"],
                    shape=tuple(meta["shape"]),
                    block_size=meta["block_size"],
                ),
            )
        elif name in bf16:
            put(name, jnp.asarray(arr.view(jnp.bfloat16)))
        else:
            put(name, jnp.asarray(arr))
    for path, v in manifest["scalars"].items():
        put(path, v)
    return params


def save_low_bit(path: str, params: dict, hf_config: dict, qtype: str) -> None:
    from safetensors.numpy import save_file

    os.makedirs(path, exist_ok=True)
    tensors, manifest = flatten_params(params)
    save_file(tensors, os.path.join(path, WEIGHTS_NAME))
    with open(os.path.join(path, CONFIG_NAME), "w") as f:
        json.dump(
            {"load_in_low_bit": qtype, "manifest": manifest},
            f,
        )
    with open(os.path.join(path, "config.json"), "w") as f:
        json.dump(hf_config, f)


def load_low_bit(path: str) -> tuple[dict, dict, str]:
    """-> (params, hf_config, qtype)."""
    from safetensors.numpy import load_file

    with open(os.path.join(path, CONFIG_NAME)) as f:
        meta = json.load(f)
    with open(os.path.join(path, "config.json")) as f:
        hf_config = json.load(f)
    tensors = load_file(os.path.join(path, WEIGHTS_NAME))
    params = unflatten_params(tensors, meta["manifest"])
    return params, hf_config, meta["load_in_low_bit"]
