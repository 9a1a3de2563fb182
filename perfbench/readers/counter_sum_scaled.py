"""Sums of the program's counters (/metrics?format=json): the advance over
the window of (the `plus` counters together less the `minus` counters
together) over the advance of `den`, times a factor: {"plus": [keys],
"minus": [keys], "den": key, "scale": 1000 for seconds to ms}.  None where
the denominator did not advance or ANY of the counters is not there (a
program from before them)."""


def read(params: dict, ctx: dict):
    c = ctx["record"]["counters"]
    a, b = c["start"]["metrics"], c["end"]["metrics"]
    plus, minus = params["plus"], params.get("minus", [])
    if any(k not in b for k in (*plus, *minus, params["den"])):
        return None

    def advance(key):
        return b[key] - a.get(key, 0)

    den = advance(params["den"])
    if den <= 0:
        return None
    return params["scale"] * (sum(map(advance, plus))
                              - sum(map(advance, minus))) / den
