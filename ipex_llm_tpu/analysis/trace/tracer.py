"""Abstract tracing machinery: lower one registered program, no execution.

``trace_entry`` takes a ProgramSpec and one grid point, builds the
abstract inputs (ShapeDtypeStructs — nothing touches a device buffer),
runs ``fn.trace(...).lower()`` on the pinned CPU backend, and distils the
lowered program into the facts the JP rules and the manifest consume:

- per-input-leaf: arg label, aval, whether donation was *requested*
  (``lowered.args_info``) and whether an alias actually *survived*
  lowering (the ``tf.aliasing_output`` arg attributes in the StableHLO
  main signature — jax drops unusable donations with only a warning, so
  the request alone proves nothing);
- output avals, closure-captured constant bytes, callback primitives
  found anywhere in the (recursively walked) jaxpr, and the pre-compile
  ``cost_analysis`` flops / bytes-accessed estimates.
"""

from __future__ import annotations

import re
from dataclasses import dataclass
from typing import Any

import jax
from jax.tree_util import keystr, tree_leaves_with_path

from jax.extend.core import ClosedJaxpr, Jaxpr

# primitives that re-enter the host from inside a lowered program (JP103)
CALLBACK_PRIMITIVES = frozenset({
    "pure_callback", "io_callback", "debug_callback", "callback",
    "debug_print",          # what jax.debug.print traces to since jax 0.9
    "infeed", "outfeed", "host_callback_call",
})


@dataclass(frozen=True)
class LeafInfo:
    """One flattened dynamic input of a lowered program."""
    label: str            # "cache[0]", "params['embed']", "toks"
    arg: str              # top-level dynamic arg name ("cache", "toks")
    shape: tuple[int, ...]
    dtype: str
    nbytes: int
    donated: bool         # donation *requested* (donate_argnums)
    alias: int | None     # output index the lowering actually aliased to


@dataclass(frozen=True)
class TracedEntry:
    """Everything the rules/manifest need about one lowering."""
    point_key: str                    # "horizon=8,kv=fp8,rows=4"
    leaves: tuple[LeafInfo, ...]
    out_avals: tuple[tuple[tuple[int, ...], str], ...]   # (shape, dtype)
    const_bytes: int
    callbacks: tuple[str, ...]
    flops: int
    bytes_accessed: int
    eqn_avals: tuple[tuple[tuple[int, ...], str], ...]   # every eqn output
    # where a stacked buffer can move by value (JP108): ("scan", shapes
    # of its xs and ys), ("dynamic_slice", (operand, result)),
    # ("dynamic_update_slice", (operand, update)), in program order
    slab_eqns: tuple[tuple[str, tuple[tuple[int, ...], ...]], ...] = ()


def point_key(point: dict) -> str:
    return ",".join(f"{k}={point[k]}" for k in sorted(point))


def signature(args: tuple, kwargs: dict) -> tuple:
    """jit-cache-key proxy: dynamic leaf avals + static arg reprs.  Two
    grid points with equal signatures share one compiled program — the
    unit JP104 counts."""
    leaves, treedef = jax.tree_util.tree_flatten((args, kwargs))
    sig = [str(treedef)]
    for leaf in leaves:
        if hasattr(leaf, "shape") and hasattr(leaf, "dtype"):
            sig.append((tuple(leaf.shape), str(leaf.dtype)))
        else:
            sig.append(repr(leaf))
    return tuple(sig)


_MAIN_ARG_RE = re.compile(r"%arg(\d+):")
_ALIAS_RE = re.compile(r"tf\.aliasing_output\s*=\s*(\d+)")
# compiled-HLO alias entry: "{out_idx}: (param_number, {}, may-alias)"
_COMPILED_ALIAS_RE = re.compile(
    r"\{\s*(\d*)\s*\}:\s*\((\d+),\s*\{[\d,\s]*\},\s*(?:may|must)-alias\)")


def parse_output_aliases(mlir_text: str) -> dict[int, int]:
    """MLIR-arg-position -> output index, from the public @main signature.

    jax records surviving donations as ``tf.aliasing_output`` arg
    attributes at lowering (platform-independently, CPU included); parsing
    the signature is the only stable way to see which donations the
    lowering actually kept.  The attr dict is scanned per-arg *segment*
    (from one ``%argN:`` marker to the next) rather than with a brace
    regex: sharded programs carry ``mhlo.sharding = "{...}"`` attrs whose
    quoted nested braces a flat brace match silently truncates — which
    would drop real aliases and fail JP101 on a correct tree."""
    for line in mlir_text.splitlines():
        if "func.func public @main(" in line:
            marks = [(int(m.group(1)), m.start())
                     for m in _MAIN_ARG_RE.finditer(line)]
            out: dict[int, int] = {}
            for (argn, start), (_, end) in zip(
                    marks, marks[1:] + [(-1, len(line))]):
                am = _ALIAS_RE.search(line, start, end)
                if am:
                    out[argn] = int(am.group(1))
            return out
    raise ValueError("no public @main function in lowered module")


def parse_compiled_aliases(hlo_text: str) -> dict[int, int]:
    """MLIR-arg-position -> output index, from the COMPILED module's
    ``input_output_alias`` header — the sharded-program fallback.

    When an input carries a sharding (manual-mesh programs: the tp
    tick's head-sharded KV pools) jax marks the donation in the StableHLO
    as ``jax.buffer_donor = true`` instead of naming an output with
    ``tf.aliasing_output`` (checked under jax 0.9.0: a sharded donated
    input lowers with the former, an unsharded one with the latter), so
    which output it aliases is only decided by XLA at compile time and
    stamped on the entry module as ``{out_idx}: (param, {}, may-alias)``.  Parsing that header
    is the only way to verify a sharded program's donation contract, and
    compiling costs ~1 s on top of the (already-paid) lowering."""
    return {int(m.group(2)): int(m.group(1) or 0)
            for m in _COMPILED_ALIAS_RE.finditer(hlo_text)}


def _shapes(variables) -> tuple[tuple[int, ...], ...]:
    return tuple(tuple(v.aval.shape) for v in variables
                 if hasattr(getattr(v, "aval", None), "shape"))


def _walk_jaxpr(jaxpr: Jaxpr, callbacks: list[str],
                avals: list[tuple[tuple[int, ...], str]],
                slabs: list[tuple[str, tuple[tuple[int, ...], ...]]]):
    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        if name in CALLBACK_PRIMITIVES:
            callbacks.append(name)
        if name == "scan":
            n_fixed = eqn.params["num_consts"] + eqn.params["num_carry"]
            slabs.append((name, _shapes(eqn.invars[n_fixed:])
                          + _shapes(eqn.outvars[eqn.params["num_carry"]:])))
        elif name == "dynamic_slice":
            slabs.append((name, _shapes([eqn.invars[0], eqn.outvars[0]])))
        elif name == "dynamic_update_slice":
            slabs.append((name, _shapes(eqn.invars[:2])))
        for v in eqn.outvars:
            aval = getattr(v, "aval", None)
            if aval is not None and hasattr(aval, "shape"):
                avals.append((tuple(aval.shape), str(aval.dtype)))
        for sub in eqn.params.values():
            for j in _iter_subjaxprs(sub):
                _walk_jaxpr(j, callbacks, avals, slabs)


def _iter_subjaxprs(v: Any):
    if isinstance(v, ClosedJaxpr):
        yield v.jaxpr
    elif isinstance(v, Jaxpr):
        yield v
    elif isinstance(v, (list, tuple)):
        for x in v:
            yield from _iter_subjaxprs(x)


def _leaf_aval(info: Any):
    return getattr(info, "aval", None) or info._aval


def trace_entry(spec, point: dict, prebuilt=None) -> TracedEntry:
    """Lower ``spec.fn`` at ``point`` and distil the audit facts.

    ``prebuilt``: the (args, kwargs) the caller already built for this
    point (the runner builds them once for the dedupe signature — no need
    to pay the builder twice)."""
    args, kwargs = prebuilt if prebuilt is not None \
        else spec.build(dict(point))
    traced = spec.fn.trace(*args, **kwargs)
    lowered = traced.lower()

    ai_args, ai_kwargs = lowered.args_info
    if len(ai_args) != len(spec.arg_names):
        raise ValueError(
            f"{spec.name}: arg_names has {len(spec.arg_names)} entries but "
            f"the lowering reports {len(ai_args)} dynamic args — keep the "
            "registry's arg_names aligned with the jitted signature")
    flat = tree_leaves_with_path((ai_args, dict(ai_kwargs)))

    # flattened dynamic leaves -> MLIR @main args: lowering drops unused
    # inputs; kept_var_idx names the survivors, in flat order
    kept = lowered._lowering.compile_args.get("kept_var_idx")
    kept = sorted(kept) if kept is not None else list(range(len(flat)))
    mlir_pos = {flat_idx: i for i, flat_idx in enumerate(kept)}
    aliases = parse_output_aliases(lowered.as_text())
    if not aliases and any(getattr(i, "donated", False)
                           for _, i in flat):
        # donation requested but the StableHLO shows zero aliases: the
        # sharded-lowering gap (see parse_compiled_aliases) — pay the
        # compile and read the aliases XLA actually established
        aliases = parse_compiled_aliases(lowered.compile().as_text())

    leaves = []
    for flat_idx, (path, info) in enumerate(flat):
        top = path[1]
        if hasattr(top, "idx"):          # positional arg
            name = spec.arg_names[top.idx]
        else:                            # dynamic kwarg
            name = str(getattr(top, "key", top))
        aval = _leaf_aval(info)
        label = name + keystr(tuple(path[2:]))
        leaves.append(LeafInfo(
            label=label, arg=name, shape=tuple(aval.shape),
            dtype=str(aval.dtype),
            nbytes=int(aval.size * aval.dtype.itemsize),
            donated=bool(getattr(info, "donated", False)),
            alias=aliases.get(mlir_pos.get(flat_idx, -1)),
        ))

    callbacks: list[str] = []
    eqn_avals: list[tuple[tuple[int, ...], str]] = []
    slab_eqns: list[tuple[str, tuple[tuple[int, ...], ...]]] = []
    closed = traced.jaxpr
    _walk_jaxpr(closed.jaxpr, callbacks, eqn_avals, slab_eqns)
    const_bytes = sum(int(getattr(c, "nbytes", 0)) for c in closed.consts)

    cost = lowered.cost_analysis() or {}
    if isinstance(cost, (list, tuple)):   # compiled-style shape, just in case
        cost = cost[0] if cost else {}

    return TracedEntry(
        point_key=point_key(point),
        leaves=tuple(leaves),
        out_avals=tuple((tuple(v.aval.shape), str(v.aval.dtype))
                        for v in closed.jaxpr.outvars),
        const_bytes=const_bytes,
        callbacks=tuple(sorted(set(callbacks))),
        flops=int(cost.get("flops", 0) or 0),
        bytes_accessed=int(cost.get("bytes accessed", 0) or 0),
        eqn_avals=tuple(eqn_avals),
        slab_eqns=tuple(slab_eqns),
    )
