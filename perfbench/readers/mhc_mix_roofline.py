"""The hyper-connections' share (%) of their roofline over the traced ticks.
The least time a tick's stream mixing can take is

    (tokens x wrapped sublayers) the tick mixes (the ``engine/dispatch``
    span's ``mhc_tokens``, counted by the host before the dispatch) x one
    token's streams read once and written once (shapes_xing4), plus every
    sublayer's float32 projections read once a tick (shapes_xing4), at the
    device's HBM bandwidth,

over the device time of the operations in the scopes {"scopes": [path parts]}
(``mhc/coef`` and ``mhc/mix``) that ran inside that tick's window.  The stat is
a lower bound (a row that joins the decode stage inside the tick is left out)
and the sizes are logical, so the share errs low, never high, and cannot pass
100 %.  A tick with the stat and no such operation inside (the capture cut it)
is left out on both sides.  None where the program writes no such stat, the
trace holds no operation in the scopes, or the configuration has one residual
stream (the keys shapes_xing4 reads)."""

import shapes
import shapes_xing4
import xplane_spans
from readers.scope_share import in_scope


def read(params: dict, ctx: dict):
    parsed = xplane_spans.load(ctx)
    hf = ctx["hf_config"]
    if (parsed is None or not parsed["ops"] or not parsed["spans"]
            or int(hf.get("hc_mult", 1)) < 2):
        return None
    ticks = [d for d in xplane_spans.dispatches(parsed["spans"])
             if "mhc_tokens" in d["attrs"]]
    ops = parsed["ops"]
    timed = [(o[1], sec) for o, sec in zip(ops, xplane_spans.self_times(ops))
             if any(in_scope(o[3], s) for s in params["scopes"])]
    if not ticks or not timed:
        return None
    peak = shapes.peak_for(ctx["peaks"], ctx["device"]["kind"])
    tick_bytes = shapes_xing4.sublayers(hf) * shapes_xing4.projection_bytes(hf)
    tokens, spent, n = 0, 0.0, 0
    for d in ticks:
        inside = sum(sec for start, sec in timed if d["a"] <= start < d["end"])
        if inside <= 0:
            continue        # a dispatch whose program the capture cut
        spent += inside
        tokens += int(d["attrs"]["mhc_tokens"])
        n += 1
    if spent <= 0:
        return None
    byts = tokens * shapes_xing4.token_bytes(hf) + n * tick_bytes
    least = byts / peak["hbm_bytes_per_s"]
    ctx.setdefault("notes", {})["mhc_mix_roofline"] = {
        "ticks": n, "mhc_tokens": tokens, "bytes": byts, "bytes_s": least,
        "scoped_s": spent}
    return 100.0 * least / spent
