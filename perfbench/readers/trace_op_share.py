"""The share (%) of the device's busy time spent in operations whose name
matches {"ops": regex}; with "complement": true, in all the others."""

import re


def read(params: dict, ctx: dict):
    tr = ctx.get("trace")
    if not tr or not tr["ops"]:
        return None
    pat = re.compile(params["ops"])
    total = sum(sec for _, sec in tr["ops"].values())
    hit = sum(sec for name, (_, sec) in tr["ops"].items() if pat.search(name))
    if total <= 0:
        return None
    if params.get("complement"):
        hit = total - hit
    return 100.0 * hit / total
