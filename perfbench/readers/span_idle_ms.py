"""Device idle time between two programs that falls inside one group of
host phases, in ms per traced working tick: {"group": "plan_admit" |
"prepare_dispatch" | "drain_commit"} (xplane_spans.GROUPS, gap_table).  The
table in full (every phase, the time inside engine/tick but no phase, the
time inside no span, the total) goes to ``ctx["notes"]`` and to stderr."""

import json
import sys

import xplane_spans


def read(params: dict, ctx: dict):
    parsed = xplane_spans.load(ctx)
    if parsed is None:
        return None
    table = xplane_spans.gap_table(parsed)
    if table is None:
        return None
    notes = ctx.setdefault("notes", {})
    if "idle_between_programs_by_span" not in notes:
        notes["idle_between_programs_by_span"] = table
        flat = {**table["by_phase"],
                **{k: table[k] for k in ("tick", "none", "total")}}
        print("[perfbench] device idle between programs, by host span (s): "
              + json.dumps({k: round(v, 6) for k, v in flat.items()})
              + f" over {table['working_ticks']} working ticks",
              file=sys.stderr, flush=True)
    return xplane_spans.group_idle_ms(table, params["group"])
