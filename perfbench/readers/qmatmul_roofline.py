"""The qmatmul kernel's share (%) of its roofline over the traced ticks:

    sum over the traced tick executions of the least time the chip could take
    for that tick's qmatmul calls  /  summed device time of the qmatmul events

{"module": regex of the tick program, "ops": regex of the kernel's events,
 "prefill_rows": token rows of a mixed tick's prefill pass}.

A tick runs the decoder once over the engine's ``max_rows`` decode rows (one
token each) and, in a mixed tick, once more over ``prefill_rows`` prompt
tokens; each pass makes 4 * layers + 1 qmatmul calls (qkv, o, gate_up, down a
layer, and the head), so the number of kernel events inside a tick's
execution says how many passes it made.  The first pass is costed at M =
max_rows through every linear and the head; a second at M = prefill_rows
through the layers and ONE row through the head (the least the head can be
asked for, so the share is understated rather than overstated).  Which bound
binds is logged: ``ctx["notes"]`` gets the seconds under each.
"""

import re

import shapes


def read(params: dict, ctx: dict):
    tr = ctx.get("trace")
    if not tr:
        return None
    peak = shapes.peak_for(ctx["peaks"], ctx["device"]["kind"])
    hf = ctx["hf_config"]
    rows = int(ctx["config"]["engine"]["max_rows"])
    per_pass = 4 * int(hf["num_hidden_layers"]) + 1
    decode = shapes.pass_min_seconds(hf, rows, rows, peak)
    prefill = shapes.pass_min_seconds(hf, int(params["prefill_rows"]), 1, peak)
    mod_pat, op_pat = re.compile(params["module"]), re.compile(params["ops"])
    least, spent = 0.0, 0.0
    bound = {"compute": 0.0, "bandwidth": 0.0}
    passes = {}
    for m in tr["modules"]:
        if not mod_pat.search(m["name"]):
            continue
        n = sum(c for k, (c, _) in m["ops"].items() if op_pat.search(k))
        sec = sum(s for k, (_, s) in m["ops"].items() if op_pat.search(k))
        if n == 0 or n % per_pass:
            continue     # cut by the trace's edge, or not a decoder tick
        p = n // per_pass
        passes[p] = passes.get(p, 0) + 1
        for cost in [decode] + [prefill] * (p - 1):
            least += cost["seconds"]
            for b, s in cost["by_bound"].items():
                bound[b] += s
        spent += sec
    ctx.setdefault("notes", {})["qmatmul_roofline"] = {
        "ticks_by_passes": passes, "least_s": least, "kernel_s": spent,
        "least_s_by_bound": bound}
    return 100.0 * least / spent if spent > 0 else None
