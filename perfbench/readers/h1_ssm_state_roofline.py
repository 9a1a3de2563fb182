"""Falcon-H1's decode step's share (%) of its bandwidth roofline over the
traced STEADY ticks: the least time to read and write the recurrent state of
the rows ACTIVE (the ``engine/dispatch`` span's ``rows_active``;
shapes_falcon_h1.state_tick_bytes: every block's state of a row, twice) at the
device's HBM bandwidth, over the device time (self times) of the operations in
the scope {"scope": "ssm/step"} that ran inside that tick's window.  Logical
bytes over measured time: a lower bound on the traffic, so the share cannot
pass 100 %.  The same reduction as ``ssm_state_roofline``, which reads
nemotron_h's config keys (shapes_nemotron_h) and stays as it is; this one
reads falcon_h1's.  None where the program has no such scope (the parent's),
the configuration is of another family, or the trace holds no steady tick."""

import shapes
import shapes_falcon_h1
import xplane_spans
from readers import scope_share


def read(params: dict, ctx: dict):
    hf = ctx["hf_config"]
    if "mamba_d_state" not in hf:
        return None
    parsed = xplane_spans.load(ctx)
    if parsed is None or not parsed["ops"] or not parsed["spans"]:
        return None
    steady = [d for d in xplane_spans.dispatches(parsed["spans"])
              if d["variant"] == "steady" and "rows_active" in d["attrs"]]
    ops = parsed["ops"]
    timed = [(o[1], sec) for o, sec in zip(ops, xplane_spans.self_times(ops))
             if scope_share.in_scope(o[3], params["scope"])]
    if not steady or not timed:
        return None
    peak = shapes.peak_for(ctx["peaks"], ctx["device"]["kind"])
    least, spent, ticks = 0.0, 0.0, 0
    for d in steady:
        inside = sum(sec for start, sec in timed if d["a"] <= start < d["end"])
        if inside <= 0:
            continue        # a dispatch whose program the capture cut
        spent += inside
        ticks += 1
        least += (shapes_falcon_h1.state_tick_bytes(
            hf, int(d["attrs"]["rows_active"])) / peak["hbm_bytes_per_s"])
    if spent <= 0:
        return None
    ctx.setdefault("notes", {})["h1_ssm_state_roofline"] = {
        "ticks": ticks, "least_s": least, "scope_s": spent}
    return 100.0 * least / spent
