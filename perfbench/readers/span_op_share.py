"""The share (%) of the device's busy time in one class of operations, told
by the program's named scopes and the configuration's pool shape
(xplane_spans.classify): {"class": "kv_pool_move" | "unscoped" | a scope}.
The whole partition, and the unscoped operations by name, go to
``ctx["notes"]``."""

import xplane_spans


def read(params: dict, ctx: dict):
    parsed = xplane_spans.load(ctx)
    if parsed is None:
        return None
    got = xplane_spans.classify(
        parsed, xplane_spans.pool_pattern(ctx["config"], ctx["hf_config"]))
    if got is None or got["total_s"] <= 0:
        return None
    shares = {k: 100.0 * v / got["total_s"]
              for k, v in sorted(got["seconds"].items(), key=lambda kv: -kv[1])}
    ctx.setdefault("notes", {})["busy_share_by_class"] = {
        "shares": shares, "unscoped_ops_s": got["unscoped_ops"]}
    return shares.get(params["class"], 0.0)
