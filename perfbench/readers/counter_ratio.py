"""Two of the program's counters (/metrics?format=json), as the ratio of their
advances over the window: {"num": key, "den": key}."""


def read(params: dict, ctx: dict):
    c = ctx["record"]["counters"]
    a, b = c["start"]["metrics"], c["end"]["metrics"]
    den = b.get(params["den"], 0) - a.get(params["den"], 0)
    if den <= 0:
        return None
    return (b.get(params["num"], 0) - a.get(params["num"], 0)) / den
