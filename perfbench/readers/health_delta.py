"""The advance over the window of one number in /health:
{"path": ["perf", "sentinel", "compiles_total"]}."""


def _at(obj, path):
    for k in path:
        if not isinstance(obj, dict) or k not in obj:
            return None
        obj = obj[k]
    return obj


def read(params: dict, ctx: dict):
    c = ctx["record"]["counters"]
    a = _at(c["start"]["health"], params["path"])
    b = _at(c["end"]["health"], params["path"])
    if a is None or b is None:
        return None
    return b - a
