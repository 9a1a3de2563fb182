"""Two of the program's counters (/metrics?format=json), as the ratio of
their advances over the window times a factor: {"num": key, "den": key,
"scale": 100 for a share in %, 1000 for seconds to ms}.  None where the
denominator did not advance or a counter is not there (a program from
before the counter)."""


def read(params: dict, ctx: dict):
    c = ctx["record"]["counters"]
    a, b = c["start"]["metrics"], c["end"]["metrics"]
    if params["num"] not in b or params["den"] not in b:
        return None
    den = b[params["den"]] - a.get(params["den"], 0)
    if den <= 0:
        return None
    return params["scale"] * (b[params["num"]] - a.get(params["num"], 0)) / den
