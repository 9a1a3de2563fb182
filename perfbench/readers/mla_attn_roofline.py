"""The latent attention kernels' share (%) of their roofline over the traced
ticks.  The least time a tick's walk can take is the LARGER of

    live pages (the ``engine/dispatch`` span's ``attn_pages_live``, counted
    by the host before the dispatch) x the attention layers x one latent
    page's bytes (shapes_axk1) / the device's HBM bandwidth, and
    live pages x the page's tokens x the attention layers x the walk's
    operations a live token under one query a head (shapes_axk1) / the
    device's bf16 peak,

over the device time of the operations named {"ops": regex} that ran inside
that tick's window.  The stat is a lower bound (a row that joins the decode
stage inside the tick is left out) and a chunk's queries are counted as one a
head, so the share errs low, never high.  A tick with the stat and no such
operation inside (the capture cut it) is left out on both sides.  None where
the program writes no such stat, the trace holds no such operation, or the
configuration has no latent (the keys shapes_axk1 reads)."""

import re

import shapes
import shapes_axk1
import trace_reduce
import xplane_spans


def read(params: dict, ctx: dict):
    parsed = xplane_spans.load(ctx)
    hf = ctx["hf_config"]
    if (parsed is None or not parsed["ops"] or not parsed["spans"]
            or "kv_lora_rank" not in hf):
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
    layers = shapes_axk1.attention_layers(hf)
    page_s = (shapes_axk1.latent_page_bytes(hf, ctx["config"]) * layers
              / peak["hbm_bytes_per_s"])
    flop_s = (int(ctx["config"]["engine"]["page_size"]) * layers
              * shapes_axk1.walk_flops_per_token(hf) / peak["bf16_flops"])
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
    least = pages * max(page_s, flop_s)
    ctx.setdefault("notes", {})["mla_attn_roofline"] = {
        "ticks": n, "pages_live": pages, "bytes_s": pages * page_s,
        "flops_s": pages * flop_s, "kernel_s": spent}
    return 100.0 * least / spent
