"""Tensor-parallel sharding rules for the decoder param pytree.

Replaces DeepSpeed AutoTP (reference convert.py:217-228: recognize sharded
``LinearAllreduce``, store ``mp_group``, allreduce in LowBitLinear.forward
low_bit_linear.py:715-722).  Megatron-style layout expressed declaratively:

- qkv / gate_up projections: column-parallel (shard ``out`` over ``tp``) —
  attention heads and MLP inner dim split across chips;
- o / down projections: row-parallel (shard ``in`` over ``tp``) — XLA inserts
  the psum over ICI during sharding propagation, the AutoTP
  ``inference_all_reduce`` equivalent, no explicit collective in model code;
- embedding / lm_head: vocab-sharded;
- norms, biases on the sharded dim, rope tables: replicated / follow out.

The rules apply to ``QTensor`` weights as well: packed code planes and block
scales carry the same named sharding (their block axes are sub-divisions of
the logical in-axis), so quantized TP works exactly like bf16 TP.
"""

from __future__ import annotations

from typing import Any

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ipex_llm_tpu.quantize.core import QTensor

# dict key -> parallel style for layer weights
_COL = {"qkv", "gate_up", "moe_gate_up", "q_a", "kv_a"}
_ROW = {"o", "down", "moe_down"}
_COL_BIAS = {"qkv_bias", "gate_up_bias"}


def _divisible(n: int, parts: int) -> bool:
    return parts > 0 and n % parts == 0


def _qtensor_spec(qt: QTensor, kind: str, tp: int, stacked: bool,
                  ep: int = 1, pp: int = 1) -> tuple[P, str | None]:
    """Pick the PartitionSpec for a QTensor's data/scales planes.

    All planes are laid out ``[(L,)? (E,)? in_like, out]``; col-parallel
    shards the last axis, row-parallel the in-like axis; the stacked layer
    axis is sharded over ``pp`` (stage-sequential pipeline — the reference's
    per-rank layer slices, pipeline_parallel.py:166-234, without the
    process groups) and an expert axis (MoE stacks) over ``ep``.  Falls
    back to replication when an axis does not divide evenly.

    Returns (spec, tp_mode): ``tp_mode`` is the mode stamped onto the
    QTensor when the sharded Pallas kernel path can serve it ('col'/'row',
    see ops/pallas/qmatmul.py::qmatmul_pallas_sharded), else None.
    """
    lead: tuple = ()
    if stacked:
        n_l = qt.data.shape[0]
        lead = ("pp" if pp > 1 and _divisible(n_l, pp) else None,)
    if qt.data.ndim == 2 + len(lead) + 1:  # extra expert axis
        n_experts = qt.data.shape[len(lead)]
        lead = lead + ("ep" if _divisible(n_experts, ep) and ep > 1 else None,)
    data_in = qt.data.shape[-2]
    nb = qt.scale_bits.shape[-2] if qt.scale_bits is not None else data_in
    if kind == "col" and _divisible(qt.out_features, tp):
        mode = "col" if tp > 1 else None
        return P(*lead, None, "tp"), mode
    if kind == "row" and _divisible(data_in, tp) and _divisible(nb, tp):
        # the kernel's x-shard/data-shard row alignment additionally needs
        # whole quantization blocks per shard with no padded tail; the
        # 5-bit dual-plane layout (nibble plane ++ bit plane, _pack_5bit)
        # has no contiguous per-shard row slice, so it takes the GSPMD path
        bs = qt.block_size or 1
        mode = (
            "row"
            if tp > 1 and bs and qt.in_features % (bs * tp) == 0
            and qt.qtype not in ("sym_int5", "asym_int5")
            else None
        )
        return P(*lead, "tp", None), mode
    return P(*lead, None, None), None


def param_shardings(params: dict, mesh: Mesh) -> dict:
    """Build a sharding pytree matching ``params`` (QTensor-aware)."""
    tp = mesh.shape.get("tp", 1)
    ep = mesh.shape.get("ep", 1)
    pp = mesh.shape.get("pp", 1)
    n_layers = None
    for v in params["layers"].values():
        leaf = v.data if isinstance(v, QTensor) else v
        n_layers = leaf.shape[0]
        break

    def ns(spec: P) -> NamedSharding:
        return NamedSharding(mesh, spec)

    def lead_pp():
        return "pp" if pp > 1 and _divisible(n_layers or 0, pp) else None

    def qt_sharding(qt: QTensor, kind: str, stacked: bool):
        spec, mode = _qtensor_spec(qt, kind, tp, stacked, ep=ep, pp=pp)
        return QTensor(
            data=ns(spec),
            scale_bits=None if qt.scale_bits is None else ns(spec),
            zeros=None if qt.zeros is None else ns(spec),
            qtype=qt.qtype, shape=qt.shape, block_size=qt.block_size,
            tp_mode=mode,
        )

    def layer_entry(key: str, v: Any):
        stacked = True
        if isinstance(v, QTensor):
            if key in _COL:
                return qt_sharding(v, "col", stacked)
            if key in _ROW:
                return qt_sharding(v, "row", stacked)
            return qt_sharding(v, "rep", stacked)
        if key in _COL_BIAS and _divisible(v.shape[-1], tp):
            return ns(P(lead_pp(), "tp"))
        # stacked per-layer vectors (norms, routers): stage-shard the L axis
        spec = (lead_pp(),) + (None,) * (v.ndim - 1)
        return ns(P(*spec))

    out: dict[str, Any] = {}
    for key, v in params.items():
        if key == "layers":
            out[key] = {k: layer_entry(k, sub) for k, sub in v.items()}
        elif key == "embed":
            if isinstance(v, QTensor):  # quantized table: vocab-block shard
                out[key] = qt_sharding(v, "row", stacked=False)
            elif _divisible(v.shape[0], tp):
                out[key] = ns(P("tp", None))
            else:
                out[key] = ns(P())
        elif key == "lm_head":
            if isinstance(v, QTensor):
                out[key] = qt_sharding(v, "col", stacked=False)
            else:
                out[key] = ns(P())
        elif isinstance(v, (float, int)):
            out[key] = None  # static scalar, not a device array
        else:
            out[key] = ns(P())
    return out


def shard_params(params: dict, mesh: Mesh) -> dict:
    """Place the param pytree onto the mesh under the TP rules.

    QTensor leaves are stamped with their ``tp_mode`` so op dispatch can
    route them through the shard_map-wrapped Pallas kernels.
    """
    from dataclasses import replace as _dc_replace

    sh = param_shardings(params, mesh)

    def place(p, s):
        if s is None or isinstance(p, (float, int)):
            return p
        if isinstance(p, QTensor) and isinstance(s, QTensor):
            if p.tp_mode != s.tp_mode:  # aux must match for device_put
                p = _dc_replace(p, tp_mode=s.tp_mode)
        return jax.device_put(p, s)

    out = {}
    for key, v in params.items():
        if key == "layers":
            out[key] = {k: place(sub, sh[key][k]) for k, sub in v.items()}
        else:
            out[key] = place(v, sh[key])
    return out


def cache_sharding(mesh: Mesh, n_kv_heads: int, batch: int = 0,
                   n_layers: int = 0) -> NamedSharding:
    """KV cache [L, B, Hkv, S, D]: layers over pp, batch over dp, heads over
    tp (when they divide; GQA with fewer kv heads than tp replicates)."""
    tp = mesh.shape.get("tp", 1)
    dp = mesh.shape.get("dp", 1)
    pp = mesh.shape.get("pp", 1)
    head_axis = "tp" if _divisible(n_kv_heads, tp) else None
    batch_axis = "dp" if _divisible(batch, dp) else None
    layer_axis = "pp" if pp > 1 and _divisible(n_layers, pp) else None
    return NamedSharding(mesh, P(layer_axis, batch_axis, head_axis, None, None))


def data_sharding(mesh: Mesh, batch: int = 0) -> NamedSharding:
    """Token batches [B, T]: batch over dp (replicated when non-divisible)."""
    dp = mesh.shape.get("dp", 1)
    axis = "dp" if _divisible(batch, dp) else None
    return NamedSharding(mesh, P(axis, None))


def shard_cache(cache, mesh: Mesh):
    """Place a KVCache pytree onto the mesh (k/v sharded, length replicated)."""
    n_kv_heads = cache.k.shape[2]
    batch = cache.k.shape[1]
    kv_sh = cache_sharding(mesh, n_kv_heads, batch, n_layers=cache.k.shape[0])
    rep = NamedSharding(mesh, P())
    from dataclasses import replace as _replace

    return _replace(
        cache,
        k=jax.device_put(cache.k, kv_sh),
        v=jax.device_put(cache.v, kv_sh),
        length=jax.device_put(cache.length, rep),
    )


def shard_paged_cache(cache, mesh: Mesh):
    """Place a PagedKVCache pool onto the mesh.

    Pool layers ``[L, P, Hkv, page, D]`` shard kv heads over ``tp`` (the
    same head split cache_sharding uses for dense caches; GQA with fewer kv
    heads than tp replicates); block tables and lengths are host-driven
    control state and stay replicated.  This is the serving-side peer of
    the reference's vLLM TP workers each holding their head slice of the
    paged pool (SURVEY §2.1 vllm/).
    """
    from dataclasses import replace as _replace

    tp = mesh.shape.get("tp", 1)
    n_kv_heads = cache.k.shape[2]
    head_axis = "tp" if tp > 1 and _divisible(n_kv_heads, tp) else None
    pool = NamedSharding(mesh, P(None, None, head_axis, None, None))
    rep = NamedSharding(mesh, P())
    return _replace(
        cache,
        k=jax.device_put(cache.k, pool),
        v=jax.device_put(cache.v, pool),
        tables=jax.device_put(cache.tables, rep),
        length=jax.device_put(cache.length, rep),
    )


def shard_batch(mesh: Mesh, batch: int, *arrays):
    """Place per-sequence arrays (leading batch axis) onto the dp axis."""
    dp = mesh.shape.get("dp", 1)
    axis = "dp" if _divisible(batch, dp) else None

    def place(a):
        import jax.numpy as jnp

        a = jnp.asarray(a)
        spec = (axis,) + (None,) * (a.ndim - 1)
        return jax.device_put(a, NamedSharding(mesh, P(*spec)))

    return tuple(place(a) for a in arrays)
