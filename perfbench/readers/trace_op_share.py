"""The share (%) of the device's busy time spent in operations whose name
matches {"ops": regex}."""

import re


def read(params: dict, ctx: dict):
    tr = ctx.get("trace")
    if not tr or not tr["ops"]:
        return None
    pat = re.compile(params["ops"])
    total = sum(sec for _, sec in tr["ops"].values())
    if total <= 0:
        return None
    hit = sum(sec for name, (_, sec) in tr["ops"].items() if pat.search(name))
    return 100.0 * hit / total
