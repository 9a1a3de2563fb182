"""One of the values the window's sample carries (``stats.metric_values``),
reported without a bound beside the per-layer metrics: {"name": metric}."""

import stats


def read(params: dict, ctx: dict):
    return stats.metric_values(ctx["e2e"]).get(params["name"])
