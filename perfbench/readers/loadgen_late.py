"""How late the load generator ran, by its own clock: a percentile of its
event loop's wake-up lag sampled through the window, pooled (open loop) with
each request's sent - due: {"percentile": 95}."""

import stats


def read(params: dict, ctx: dict):
    late = ctx["record"].get("late_ms") or []
    try:
        return stats.percentile(late, params["percentile"])
    except ValueError:
        return None
