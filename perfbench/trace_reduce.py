"""From a jax.profiler trace (.xplane.pb) to what the per-layer readers use.
Part of the yardstick: every PR reduces a trace the same way.

What a v5e trace holds (looked at by hand, my chip run PR 24): one plane per
chip, "/device:TPU:<n>", whose line "XLA Modules" has one event per execution
of a compiled program (named "<jit name>(<fingerprint>)") and whose line
"XLA Ops" has one event per operation inside it; a Mosaic kernel's event
carries the kernel's own name ("qmatmul", "ragged_paged_attention") at the
start of its name.  Host threads are lines of "/host:CPU".

The reduced form is plain JSON (kept small enough to store as test data):

  {"window_s", "busy_s", "chips",
   "modules": [{"name", "start_s", "dur_s", "ops": {kernel: [count, self s]},
                "busy_s"}],            # executions on chip 0, in time order
   "ops": {name: [count, self seconds]},  # chip 0, by operation name; an
                                      # operation's self time leaves out the
                                      # operations nested inside it
   "gaps": [[start_s, dur_s, label]], # longest idle gaps on chip 0
   "gap_total_s", "gap_between_s",    # all idle time on chip 0; the part of
                                      # it between two programs
   "idle_by_span": {label: seconds}}  # reduce_dir only: gap_between_s by the
                                      # innermost host span (xplane_spans.py)
"""

from __future__ import annotations

import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
MODULE_LINE, OPS_LINE = "XLA Modules", "XLA Ops"
# operation names differ by a trailing ".<n>"; the breakdown groups without it
_SUFFIX = re.compile(r"[.\-_]?\d+$")


def find_xplane(logdir: str) -> str | None:
    paths = sorted(glob.glob(os.path.join(
        logdir, "plugins", "profile", "*", "*.xplane.pb")))
    return paths[-1] if paths else None


def _union(intervals: list[tuple[float, float]]) -> tuple[float, list]:
    """Total covered length and the gaps between the merged intervals."""
    busy, gaps, end = 0.0, [], None
    for a, b in sorted(intervals):
        if end is None:
            end = b
            busy += b - a
        elif a > end:
            gaps.append((end, a - end))
            busy += b - a
            end = b
        elif b > end:
            busy += b - end
            end = b
    return busy, gaps


def device_events(xplane_path: str) -> dict[int, dict[str, list]]:
    """{chip: {line name: [(name, start_s, dur_s)]}} of every device plane."""
    import jax

    data = jax.profiler.ProfileData.from_file(xplane_path)
    out: dict[int, dict[str, list]] = {}
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if not m:
            continue
        lines = out.setdefault(int(m.group(1)), {})
        for line in plane.lines:
            lines[line.name] = [(e.name, e.start_ns * 1e-9, e.duration_ns * 1e-9)
                                for e in line.events]
    return out


def kernel_of(op_name: str) -> str:
    """The name an operation is grouped under."""
    base = op_name.split(" ")[0].lstrip("%")
    return _SUFFIX.sub("", base) or base


def reduce_events(planes: dict[int, dict[str, list]]) -> dict | None:
    if not planes:
        return None
    busy_per_chip, t_lo, t_hi = [], None, None
    for lines in planes.values():
        ops = lines.get(OPS_LINE, [])
        if not ops:
            continue
        busy, _ = _union([(s, s + d) for _, s, d in ops])
        busy_per_chip.append(busy)
        lo = min(s for _, s, _ in ops)
        hi = max(s + d for _, s, d in ops)
        t_lo = lo if t_lo is None else min(t_lo, lo)
        t_hi = hi if t_hi is None else max(t_hi, hi)
    if not busy_per_chip:
        return None
    first = planes[min(planes)]
    ops = sorted(first.get(OPS_LINE, []), key=lambda e: (e[1], -e[2]))
    mods = sorted(first.get(MODULE_LINE, []), key=lambda e: e[1])
    # operations nest (a `while` spans its body's operations): an operation's
    # SELF time is its duration minus its direct children's, so that shares
    # of the busy time add up to 1
    self_s = [d for _, _, d in ops]
    stack: list[int] = []
    for j, (_, s, d) in enumerate(ops):
        while stack and ops[stack[-1]][1] + ops[stack[-1]][2] <= s:
            stack.pop()
        if stack:
            self_s[stack[-1]] -= d
        stack.append(j)
    by_name: dict[str, list] = {}
    for (name, _, _), d in zip(ops, self_s):
        c = by_name.setdefault(kernel_of(name), [0, 0.0])
        c[0] += 1
        c[1] += max(d, 0.0)
    modules, i = [], 0
    for name, s, d in mods:
        inside: dict[str, list] = {}
        spans = []
        while i < len(ops) and ops[i][1] < s + d:
            o_name, o_s, o_d = ops[i]
            i += 1
            if o_s + o_d <= s:
                continue
            c = inside.setdefault(kernel_of(o_name), [0, 0.0])
            c[0] += 1
            c[1] += max(self_s[i - 1], 0.0)
            spans.append((o_s, o_s + o_d))
        modules.append({"name": name, "start_s": s - t_lo, "dur_s": d,
                        "ops": inside, "busy_s": _union(spans)[0]})
    _, gaps = _union([(s, s + d) for _, s, d in ops])
    gaps = [(g_s, g_d, "inside_a_program"
             if any(s <= g_s and g_s + g_d <= s + d for _, s, d in mods)
             else "host_between_programs") for g_s, g_d in gaps]
    return {"window_s": t_hi - t_lo,
            "busy_s": sum(busy_per_chip) / len(busy_per_chip),
            "chips": len(busy_per_chip), "modules": modules, "ops": by_name,
            "gap_total_s": sum(d for _, d, _ in gaps),
            "gap_between_s": sum(d for _, d, label in gaps
                                 if label == "host_between_programs"),
            "gaps": [[g_s - t_lo, g_d, label] for g_s, g_d, label
                     in sorted(gaps, key=lambda g: -g[1])[:50]]}


def reduce_dir(logdir: str) -> dict | None:
    path = find_xplane(logdir)
    if path is None:
        return None
    reduced = reduce_events(device_events(path))
    if reduced is not None:
        import xplane_spans     # here: it imports this module

        reduced["idle_by_span"] = xplane_spans.idle_by_span(
            xplane_spans.parsed_file(path))
    return reduced


def breakdown(reduced: dict) -> dict:
    """The contract's optional ``breakdown``: the device operations that took
    most time, and ALL the device's idle time by what the host was doing.
    Inside a program the device waits on the program itself; between two
    programs it waits for the host, and that time goes by the innermost of
    the program's host spans that holds it (``engine/sync``,
    ``engine/prepare``, ...).  ``host_between_programs`` is what no span
    holds: all of it in a trace without the program's spans."""
    ops = sorted(reduced["ops"].items(), key=lambda kv: -kv[1][1])[:10]
    by_label = dict(reduced.get("idle_by_span")
                    or {"host_between_programs": reduced["gap_between_s"]})
    by_label["inside_a_program"] = (reduced["gap_total_s"]
                                    - reduced["gap_between_s"])
    gaps = sorted(((n, d) for n, d in by_label.items() if d > 0),
                  key=lambda kv: -kv[1])[:10]
    return {"device_ops": [[n, c[1]] for n, c in ops],
            "idle_gaps": [[n, d] for n, d in gaps]}
