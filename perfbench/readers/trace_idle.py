"""The device's idle share (%) of the traced window: 1 - union of the
device-operation intervals / window, averaged over the chips used."""


def read(params: dict, ctx: dict):
    tr = ctx.get("trace")
    if not tr or tr["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])
