"""The median device duration, in ms, of one execution of the compiled
programs whose name matches: {"module": regex}."""

import re
from statistics import median


def read(params: dict, ctx: dict):
    tr = ctx.get("trace")
    if not tr:
        return None
    pat = re.compile(params["module"])
    durs = [m["dur_s"] for m in tr["modules"] if pat.search(m["name"])]
    return median(durs) * 1e3 if durs else None
