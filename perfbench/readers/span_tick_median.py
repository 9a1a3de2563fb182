"""The median device duration, in ms, of the executions of the tick's
compiled program that one kind of tick made: {"module": regex of the
program, "variant": steady | mixed | chunk | spec}.  Which kind an
execution belongs to is read off the ``engine/dispatch`` host span whose
window holds it (xplane_spans.module_variants); the count of executions by
kind, and of those the capture's edge cut from their span, goes to
``ctx["notes"]``."""

import xplane_spans


def read(params: dict, ctx: dict):
    parsed = xplane_spans.load(ctx)
    if parsed is None or not parsed["spans"]:
        return None
    counts: dict = {}
    for _, _, _, variant in xplane_spans.module_variants(
            parsed, params["module"]):
        key = variant or "cut_by_the_capture_edge"
        counts[key] = counts.get(key, 0) + 1
    ctx.setdefault("notes", {})["tick_executions_by_variant"] = counts
    return xplane_spans.variant_median_ms(parsed, params["module"],
                                          params["variant"])
