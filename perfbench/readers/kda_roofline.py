"""A Kimi Delta Attention kernel's share (%) of its roofline over the traced
ticks: {"scope": "kda/chunk" | "kda/step", "form": "chunk" | "step"}.  The
least time of a tick's work in that form (shapes_glm5_next, from the
``engine/dispatch`` span's ``kda_chunk_tokens`` / ``kda_chunk_rows`` /
``kda_step_rows``, counted by the host before the dispatch, each already
multiplied by the KDA layers) over the device time (self times) of the
operations in the scope that ran inside that tick's window.  ``step``: the
states of the rows stepped, read and written, at the HBM peak.  ``chunk``: the
larger of the chunkwise form's FLOPs at the bf16 peak and its bytes at the HBM
peak.  The stats are lower bounds (a row that joins the decode stage inside
the tick is left out) and the sizes logical, so the share errs low and cannot
pass 100 %.  A tick with the stat and no such operation inside (the capture
cut it) is left out on both sides.  None where the program writes no such stat
(the parent's), the trace holds no operation in the scope, or the
configuration has no such layer."""

import shapes
import shapes_glm5_next as work
import xplane_spans
from readers.scope_share import in_scope


def read(params: dict, ctx: dict):
    hf = ctx["hf_config"]
    if "linear_attn_config" not in hf:
        return None
    parsed = xplane_spans.load(ctx)
    if parsed is None or not parsed["ops"] or not parsed["spans"]:
        return None
    stat = "kda_chunk_tokens" if params["form"] == "chunk" else "kda_step_rows"
    ticks = [d for d in xplane_spans.dispatches(parsed["spans"])
             if int(d["attrs"].get(stat, 0)) > 0]
    ops = parsed["ops"]
    timed = [(o[1], sec) for o, sec in zip(ops, xplane_spans.self_times(ops))
             if in_scope(o[3], params["scope"])]
    if not ticks or not timed:
        return None
    peak = shapes.peak_for(ctx["peaks"], ctx["device"]["kind"])
    least, spent, n = 0.0, 0.0, 0
    for d in ticks:
        inside = sum(sec for start, sec in timed if d["a"] <= start < d["end"])
        if inside <= 0:
            continue        # a dispatch whose program the capture cut
        spent += inside
        n += 1
        a = d["attrs"]
        if params["form"] == "chunk":
            least += max(
                work.kda_chunk_flops(hf, int(a["kda_chunk_tokens"]))
                / peak["bf16_flops"],
                work.kda_chunk_bytes(hf, int(a["kda_chunk_tokens"]),
                                     int(a.get("kda_chunk_rows", 0)))
                / peak["hbm_bytes_per_s"])
        else:
            least += (work.kda_step_bytes(hf, int(a["kda_step_rows"]))
                      / peak["hbm_bytes_per_s"])
    if spent <= 0:
        return None
    ctx.setdefault("notes", {})["kda_roofline." + params["form"]] = {
        "ticks": n, "least_s": least, "scope_s": spent}
    return 100.0 * least / spent
