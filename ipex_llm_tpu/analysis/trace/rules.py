"""JP101-JP108: rules over lowered programs (tracer.TracedEntry facts).

Each rule yields ``core.Finding`` objects with ``tier="trace"``, anchored
at the jitted function's def site so findings are clickable.  Spec-level
suppressions (``ProgramSpec.suppress``) are applied by the runner, under
the same loud policy as jaxlint: a suppression without a written reason
is itself a JP100 error.
"""

from __future__ import annotations

from collections import Counter

from ipex_llm_tpu.analysis.core import Finding
from ipex_llm_tpu.analysis.trace.catalog import severity_of
from ipex_llm_tpu.analysis.trace.tracer import TracedEntry

# dtypes a pool upcast would land in (JP102)
_WIDE_FLOATS = {"float32", "bfloat16", "float16", "float64"}
_FP8 = ("float8_e5m2", "float8_e4m3")


def finding(spec, code: str, message: str, at: str = "") -> Finding:
    where = f" @ {at}" if at else ""
    return Finding(rule=code, severity=severity_of(code), path=spec.source,
                   line=spec.lineno, col=1, tier="trace",
                   message=f"[{spec.name}{where}] {message}")


def check_donation(spec, entry: TracedEntry):
    """JP101: every large dead-after-call input with a matching output
    aval must hold a lowered alias; a donated-but-held buffer is a
    use-after-donate hazard either way."""
    # outputs not already consumed by a surviving alias are the slots a
    # missing donation wastes (matched by aval: XLA aliases exact
    # shape+dtype pairs only)
    free_outs = Counter(entry.out_avals)
    for leaf in entry.leaves:
        if leaf.alias is not None and leaf.alias < len(entry.out_avals):
            free_outs[entry.out_avals[leaf.alias]] -= 1
    for leaf in entry.leaves:
        if leaf.arg in spec.held and leaf.alias is not None:
            yield finding(
                spec, "JP101",
                f"host-held input {leaf.label} ({leaf.dtype}"
                f"{list(leaf.shape)}) is donated: the host keeps using "
                "this buffer across calls — donation here is a "
                "use-after-donate time bomb", entry.point_key)
            continue
        if leaf.arg not in spec.dead or leaf.alias is not None:
            continue
        if leaf.nbytes < spec.min_donate_bytes:
            continue
        sig = (leaf.shape, leaf.dtype)
        if leaf.donated:
            yield finding(
                spec, "JP101",
                f"donation of {leaf.label} ({leaf.dtype}{list(leaf.shape)}, "
                f"{leaf.nbytes}B) was requested but survived lowering with "
                "no alias — shape/dtype matches no output, so the donated "
                "buffer is silently copied anyway", entry.point_key)
        elif free_outs.get(sig, 0) > 0:
            free_outs[sig] -= 1
            yield finding(
                spec, "JP101",
                f"dead-after-call input {leaf.label} ({leaf.dtype}"
                f"{list(leaf.shape)}, {leaf.nbytes}B) has a matching "
                "output aval but no input_output_alias — the buffer is "
                "re-uploaded/copied every call; add it to donate_argnums",
                entry.point_key)


def check_fp8_integrity(spec, entry: TracedEntry):
    """JP102: pool-resident e5m2 avals stay e5m2 end to end.  Protected
    shapes are the fp8 input avals (the pool and its per-layer slices);
    any value of a protected shape materializing in a wide float dtype is
    a wholesale upcast — the dequant-at-read contract says only *gathered
    tiles* (different shapes) ever widen."""
    protected: set[tuple[int, ...]] = set()
    for leaf in entry.leaves:
        if leaf.dtype.startswith(_FP8) and len(leaf.shape) >= 3:
            protected.add(leaf.shape)
            protected.add(leaf.shape[1:])           # per-layer slice
            protected.add((1,) + leaf.shape[1:])    # dynamic_slice form
    if not protected:
        return
    seen: set[tuple[tuple[int, ...], str]] = set()
    for shape, dtype in entry.eqn_avals + entry.out_avals:
        if shape in protected and dtype in _WIDE_FLOATS \
                and (shape, dtype) not in seen:
            seen.add((shape, dtype))
            yield finding(
                spec, "JP102",
                f"pool-shaped value {dtype}{list(shape)} materializes "
                "inside the lowered program — a wholesale upcast of the "
                "e5m2 pool (2x the bytes the fp8 contract paid for); "
                "widen gathered tiles at the read site instead",
                entry.point_key)


def check_weight_integrity(spec, entry: TracedEntry):
    """JP107: stacked packed-weight planes stay packed end to end — the
    weight twin of JP102's fp8-pool rule.

    Protected inputs are the uint8 code planes of stacked quantized
    weights (``params`` leaves with >= 3 dims: ``[L, in_packed, out]``
    layer stacks, ``[L, E, in_packed, out]`` expert stacks).  The
    dequant-fused contract says a layer's weights widen only INSIDE the
    scan body, per layer, right next to the matmul that consumes them —
    per-layer 2-D wide tiles are the design, on both backends.  What must
    never appear is the FULL-STACK wide form: a wide-float value of the
    dense stack shape a wholesale dequant of the plane would produce —
    ``lead + (in_pad, out)`` for in_pad/data-rows ratios 1 (byte-per-code
    sym_int8/fp8/fp6), 2 (the nibble-packed 4-bit family, the serving
    headline), and 8/5 (the dual-plane 5-bit layout, when the row count
    divides).  That value is a full-width copy of every layer resident
    in HBM: ~4x the bytes the packing paid for, silently, on every tick.
    The two-level iquant/kquant layouts (non-integral row ratios over
    256-row superblocks) are outside this shape protection — they are
    import/offline formats, not the requantize-at-build serving family.

    Known blind zone: a weight with <= 2 quantization blocks per matrix
    whose block count equals the stack depth makes the per-layer
    ``[n_blocks, block, out]`` view ambiguous with the full-stack form —
    toy shapes only (real serving weights carry thousands of contraction
    rows); the audit model keeps every weight at >= 4 blocks by
    construction (registry.audit_model)."""
    protected: set[tuple[int, ...]] = set()
    for leaf in entry.leaves:
        if leaf.arg == "params" and leaf.dtype == "uint8" \
                and len(leaf.shape) >= 3:
            lead, kp, n = leaf.shape[:-2], leaf.shape[-2], leaf.shape[-1]
            for m in (1, 2):
                protected.add(lead + (m * kp, n))
            if kp * 8 % 5 == 0:    # _pack_5bit dual-plane rows = 5*in/8
                protected.add(lead + (kp * 8 // 5, n))
    if not protected:
        return
    seen: set[tuple[tuple[int, ...], str]] = set()
    for shape, dtype in entry.eqn_avals + entry.out_avals:
        if shape in protected and dtype in _WIDE_FLOATS \
                and (shape, dtype) not in seen:
            seen.add((shape, dtype))
            yield finding(
                spec, "JP107",
                f"stacked-weight-shaped value {dtype}{list(shape)} "
                "materializes inside the lowered program — a wholesale "
                "dequant-upcast of a packed weight stack (~4x the HBM "
                "bytes the packing bought); dequantize per layer inside "
                "the scan body, next to the consuming matmul",
                entry.point_key)


def check_pool_in_place(spec, entry: TracedEntry):
    """JP108: the paged KV pool is addressed in place by (layer, page) —
    it never moves through the program by value.

    The pool is the pair of 5-D ``[L, P, Hkv, page, D]`` leaves of a
    ``cache`` input that also carries a 2-D int32 block table (a dense
    cache has none and is not this rule's: it is per-row, and its layer
    scan has nothing to address through).  The layer scan carries the
    stacks whole and hands each layer its index, so two shapes of
    program must not come back: a ``scan`` whose ``xs`` or ``ys`` hold
    the stack (one layer sliced out per step, one stacked back: a copy
    of every layer, every tick), and a ``dynamic_slice`` /
    ``dynamic_update_slice`` that takes or puts ONE WHOLE LAYER of the
    stack.  A value is a layer of the pool when its trailing dims are
    the pool's ``[P, h, page, D]`` with ``h`` dividing ``Hkv`` (a tp
    shard holds its heads of every page), whatever leads them: a pp
    stage's chunk of the stack is still the stack."""
    tables = any(leaf.arg == "cache" and len(leaf.shape) == 2
                 and leaf.dtype == "int32" for leaf in entry.leaves)
    pools = {leaf.shape for leaf in entry.leaves
             if leaf.arg == "cache" and len(leaf.shape) == 5} \
        if tables else set()
    if not pools:
        return

    def layer_like(shape) -> bool:
        return any(len(shape) >= 4 and shape[-4] == p[1]
                   and shape[-2:] == p[3:] and p[2] % shape[-3] == 0
                   for p in pools)

    def stack_like(shape) -> bool:
        return len(shape) == 5 and layer_like(shape)

    def one_layer(shape) -> bool:
        return layer_like(shape) and (len(shape) == 4 or shape[0] == 1)

    seen: set[tuple[str, tuple[int, ...]]] = set()
    for prim, shapes in entry.slab_eqns:
        if prim == "scan":
            hit = next((s for s in shapes if stack_like(s)), None)
            why = ("rides a scan as xs/ys — every step slices a layer out "
                   "of the stack or stacks one back, a copy of the whole "
                   "pool per pass; carry the stacks and index by layer")
        else:
            operand, part = shapes
            hit = part if stack_like(operand) and one_layer(part) else None
            why = (f"is moved by {prim} — a whole layer of the pool copied "
                   "out of or back into the stack; write and read through "
                   "(layer, page) instead")
        if hit is not None and (prim, hit) not in seen:
            seen.add((prim, hit))
            yield finding(
                spec, "JP108",
                f"pool-shaped value {list(hit)} {why}", entry.point_key)


def check_callbacks(spec, entry: TracedEntry):
    """JP103: hot programs must be host-callback-free."""
    if entry.callbacks:
        yield finding(
            spec, "JP103",
            f"host callback primitive(s) {list(entry.callbacks)} in the "
            "lowered program — each one stalls the device on a host round "
            "trip; move the logic out of the jitted hot path",
            entry.point_key)


def check_recompile_surface(spec, n_lowerings: int,
                            manifest_count: int | None):
    """JP104: the grid's distinct-lowering count is bounded and matches
    the locked manifest (the trace-level teeth behind AST rule JL003)."""
    if n_lowerings > spec.max_lowerings:
        yield finding(
            spec, "JP104",
            f"the enumerated grid produces {n_lowerings} distinct "
            f"lowerings, above the spec bound {spec.max_lowerings} — an "
            "axis leaked into the trace key; bucket it or raise the bound "
            "deliberately")
    if manifest_count is not None and n_lowerings != manifest_count:
        yield finding(
            spec, "JP104",
            f"distinct lowerings = {n_lowerings} but the manifest locks "
            f"{manifest_count} — the compiled-program inventory drifted; "
            "review and run scripts/jaxprcheck --update")


def check_constant_bloat(spec, entry: TracedEntry):
    """JP105: closure-captured constants baked into the jaxpr."""
    if entry.const_bytes > spec.const_bytes_limit:
        yield finding(
            spec, "JP105",
            f"{entry.const_bytes}B of closure-captured constants baked "
            f"into the jaxpr (limit {spec.const_bytes_limit}B) — every "
            "retrace re-uploads them; pass them as arguments instead",
            entry.point_key)


def check_tick_dispatches(tick, discovered: set[str]):
    """JP106: the tick's reachable jitted-callee set equals the declared
    program chain and stays within the dispatch gate."""
    effective = set(discovered)
    declared = set(tick.programs)
    if effective != declared:
        extra = sorted(effective - declared)
        gone = sorted(declared - effective)
        parts = []
        if extra:
            parts.append(f"undeclared dispatch(es) {extra}")
        if gone:
            parts.append(f"declared program(s) {gone} no longer reachable")
        yield _tick_finding(
            tick, "JP106",
            f"tick '{tick.name}' program set drifted: {'; '.join(parts)} — "
            "update the TickSpec if this is intentional")
    if len(effective) > tick.max_dispatches:
        yield _tick_finding(
            tick, "JP106",
            f"tick '{tick.name}' can issue {len(effective)} device "
            f"dispatches ({sorted(effective)}), above the gate of "
            f"{tick.max_dispatches} — the mixed tick's dispatch budget is "
            "a locked serving invariant")


def _tick_finding(tick, code: str, message: str) -> Finding:
    path = tick.module.replace(".", "/") + ".py"
    return Finding(rule=code, severity=severity_of(code), path=path,
                   line=1, col=1, tier="trace", message=message)
