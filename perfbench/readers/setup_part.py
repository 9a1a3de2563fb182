"""One part of the set-up, in seconds on the host's clock: {"key": name}."""


def read(params: dict, ctx: dict):
    v = ctx["setup"].get(params["key"])
    return float(v) if isinstance(v, (int, float)) else None
