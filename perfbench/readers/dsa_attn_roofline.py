"""The sparse walk's share (%) of its roofline over the traced ticks.  The
least time a tick's walk over its selections can take is the LARGER of

    (query, selected token) pairs summed over the layers (the
    ``engine/dispatch`` span's ``dsa_tokens_selected``, counted by the host
    before the dispatch) x the walk's operations a pair (shapes_glm_dsa) /
    the device's bf16 peak, and
    the pairs of the tick's DECODE rows (``dsa_decode_selected``; a decode
    row reads each token it selected once, while a chunk's queries share
    what they read) x one latent row's bytes / the device's HBM bandwidth,

over the device time of the operations in the scope {"scope": path part}
that ran inside that tick's window.  The stats are lower bounds (a row that
joins the decode stage inside the tick is left out), so the share errs low,
never high.  A tick with the stats and no such operation inside (the capture
cut it) is left out on both sides.  None where the program writes no such
stat, the trace holds no operation in the scope, or the configuration has no
indexer (the keys shapes_glm_dsa reads)."""

import shapes
import shapes_glm_dsa
import xplane_spans
from readers.scope_share import in_scope


def read(params: dict, ctx: dict):
    parsed = xplane_spans.load(ctx)
    hf = ctx["hf_config"]
    if (parsed is None or not parsed["ops"] or not parsed["spans"]
            or "index_topk" not in hf or "kv_lora_rank" not in hf):
        return None
    ticks = [d for d in xplane_spans.dispatches(parsed["spans"])
             if "dsa_tokens_selected" in d["attrs"]]
    ops = parsed["ops"]
    timed = [(o[1], sec) for o, sec in zip(ops, xplane_spans.self_times(ops))
             if in_scope(o[3], params["scope"])]
    if not ticks or not timed:
        return None
    peak = shapes.peak_for(ctx["peaks"], ctx["device"]["kind"])
    flop_s = shapes_glm_dsa.walk_flops_per_pair(hf) / peak["bf16_flops"]
    byte_s = shapes_glm_dsa.latent_row_bytes(hf) / peak["hbm_bytes_per_s"]
    pairs, gathered, spent, n = 0, 0, 0.0, 0
    for d in ticks:
        inside = sum(sec for start, sec in timed if d["a"] <= start < d["end"])
        if inside <= 0:
            continue        # a dispatch whose program the capture cut
        spent += inside
        pairs += int(d["attrs"]["dsa_tokens_selected"])
        gathered += int(d["attrs"].get("dsa_decode_selected", 0))
        n += 1
    if spent <= 0:
        return None
    least = max(pairs * flop_s, gathered * byte_s)
    ctx.setdefault("notes", {})["dsa_attn_roofline"] = {
        "ticks": n, "pairs_selected": pairs, "decode_pairs": gathered,
        "flops_s": pairs * flop_s, "bytes_s": gathered * byte_s,
        "kernel_s": spent}
    return 100.0 * least / spent
