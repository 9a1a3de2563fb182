"""The ragged attention kernel's share (%) of its bandwidth roofline over
the traced ticks: the least time to read the pages the rows hold (the
``engine/dispatch`` span's ``attn_pages_live``: over both stages, the sum
over the rows that attend of cdiv(kv_len, page size), counted by the host
before the dispatch) in every attention layer (shapes_attention: one page's
K and V bytes, the layers) at the device's HBM bandwidth, over the device
time of the operations named {"ops": regex} that ran inside that tick's
window.  The stat is a lower bound (a row that joins the decode stage
inside the tick is left out), so the share errs low, never high.  A tick
with the stat and no such operation inside (the capture cut it) is left
out on both sides.  None where the program writes no such stat (the
parent's), or the trace holds no such operation."""

import re

import shapes
import shapes_attention
import trace_reduce
import xplane_spans


def read(params: dict, ctx: dict):
    parsed = xplane_spans.load(ctx)
    if parsed is None or not parsed["ops"] or not parsed["spans"]:
        return None
    ticks = [d for d in xplane_spans.dispatches(parsed["spans"])
             if "attn_pages_live" in d["attrs"]]
    pat = re.compile(params["ops"])
    ops = parsed["ops"]
    timed = [(o[1], sec) for o, sec in zip(ops, xplane_spans.self_times(ops))
             if pat.search(trace_reduce.kernel_of(o[0]))]
    if not ticks or not timed:
        return None
    peak = shapes.peak_for(ctx["peaks"], ctx["device"]["kind"])
    tick_bytes = (shapes_attention.page_bytes(ctx["hf_config"], ctx["config"])
                  * shapes_attention.attention_layers(ctx["hf_config"]))
    pages, spent, n = 0, 0.0, 0
    for d in ticks:
        inside = sum(sec for start, sec in timed if d["a"] <= start < d["end"])
        if inside <= 0:
            continue        # a dispatch whose program the capture cut
        spent += inside
        pages += int(d["attrs"]["attn_pages_live"])
        n += 1
    if spent <= 0:
        return None
    least = pages * tick_bytes / peak["hbm_bytes_per_s"]
    ctx.setdefault("notes", {})["attn_roofline"] = {
        "ticks": n, "pages_live": pages, "least_s": least, "kernel_s": spent,
        "pages_table": sum(int(d["attrs"].get("attn_pages_table", 0))
                           for d in ticks)}
    return 100.0 * least / spent
