"""The share (%) of the device's busy time in the operations whose name path
(``tf_op``) holds one of the program's named scopes: {"scope": "<path
part>"}, e.g. "layer/ssm" or "moe/experts".  Self times, so shares of
nested scopes add up as they nest; busy time is the sum of every
operation's self time.  Built on xplane_spans' parser (imported, not
changed): its own SCOPES list fixes the partition ``span_op_share`` reports,
and a scope that is not in it is read here.  None where no operation
carries the scope (a program that has none)."""

import xplane_spans


def in_scope(path: str, scope: str) -> bool:
    return "/" + scope + "/" in "/" + path + "/"


def scoped_seconds(parsed: dict, scope: str):
    """(seconds in the scope, busy seconds, operations in the scope)."""
    ops = parsed["ops"]
    hit, total, n = 0.0, 0.0, 0
    for o, sec in zip(ops, xplane_spans.self_times(ops)):
        total += sec
        if in_scope(o[3], scope):
            hit += sec
            n += 1
    return hit, total, n


def read(params: dict, ctx: dict):
    parsed = xplane_spans.load(ctx)
    if parsed is None or not parsed["ops"]:
        return None
    hit, total, n = scoped_seconds(parsed, params["scope"])
    if n == 0 or total <= 0:
        return None
    return 100.0 * hit / total
