"""A GATED expert layer's share (%) of its bandwidth roofline over the traced
span: ``readers/moe_expert_roofline.py``'s reckoning (which see; it counts an
ungated expert's two planes and stays as it is) with one expert's bytes from
shapes_axk1.expert_bytes, THREE packed planes (gate, up, down):

    (window's mean experts hit a layer call: moe_experts_hit_total /
     moe_layer_calls_total, /metrics deltas)
    x one expert's packed bytes
    x executions of the scope ``moe/experts`` in the trace
    / the device's HBM bandwidth (peaks.json),

over the device time (self times) of the operations in ``moe/experts``.  An
execution of the scope is ``calls`` events of the kernel named ``kernel`` in
it.  None where the program has no such scope or counter."""

import shapes
import shapes_axk1
import trace_reduce
import xplane_spans
from readers import scope_share


def read(params: dict, ctx: dict):
    parsed = xplane_spans.load(ctx)
    c = ctx["record"]["counters"]
    a, b = c["start"]["metrics"], c["end"]["metrics"]
    if (parsed is None or not parsed["ops"]
            or "moe_layer_calls_total" not in b):
        return None
    calls = b["moe_layer_calls_total"] - a.get("moe_layer_calls_total", 0)
    hits = b["moe_experts_hit_total"] - a.get("moe_experts_hit_total", 0)
    scope = params["scope"]
    spent, _, _ = scope_share.scoped_seconds(parsed, scope)
    executions = sum(
        1 for o in parsed["ops"]
        if scope_share.in_scope(o[3], scope)
        and trace_reduce.kernel_of(o[0]).startswith(params["kernel"])
    ) // int(params["calls"])
    if calls <= 0 or spent <= 0 or executions == 0:
        return None
    peak = shapes.peak_for(ctx["peaks"], ctx["device"]["kind"])
    least = (hits / calls * executions
             * shapes_axk1.expert_bytes(ctx["hf_config"])
             / peak["hbm_bytes_per_s"])
    ctx.setdefault("notes", {})["moe_gated_expert_roofline"] = {
        "experts_hit_a_layer_call": hits / calls, "executions": executions,
        "least_s": least, "scope_s": spent}
    return 100.0 * least / spent
