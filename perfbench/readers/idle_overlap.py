"""Of the device's idle time between two programs that falls inside some of
the engine's host phases (booked to the INNERMOST span, as
xplane_spans.gap_table books it), the share in % during which a host span
of another family is open: {"phases": ["prepare", "dispatch", ...],
"prefix": "server/"}.  The other family's spans (``server/stream_write``:
the server's event-loop thread rendering and writing one token event) lie
on the same host plane and the same clock as ``engine/*``, on another
line; xplane_spans keeps ``engine/*`` only, so they are read here with its
own field parser.  The seconds on both sides go to ``ctx["notes"]``.  None
without ``engine/tick`` spans, without a span of the family (a program from
before them) or without such idle time."""

import os

import xplane_spans


def family_spans(path: str, prefix: str) -> list[tuple[float, float]]:
    """[start_s, end_s) of every host-plane event whose name starts with
    ``prefix``, from every line, by start."""
    with open(path, "rb") as f:
        space = memoryview(f.read())
    out = []
    for no, _, buf in xplane_spans._fields(space):      # XSpace: planes=1
        if no != 1:
            continue
        name = next((bytes(v).decode() for f, _, v in xplane_spans._fields(buf)
                     if f == 2), "")
        if name != xplane_spans.HOST_PLANE:
            continue
        plane = xplane_spans._plane(buf)
        for _, events in plane["lines"]:
            out.extend((a, a + d) for mid, a, d, _ in events
                       if plane["meta"].get(mid, ("",))[0].startswith(prefix))
    return sorted(out)


def own_stretches(spans: list, phases) -> list[tuple[float, float]]:
    """Where one of ``phases`` is the innermost engine span: each such
    span's interval less its children's."""
    out = []
    for node in xplane_spans._walk(xplane_spans.span_tree(spans)):
        if node["name"] not in phases:
            continue
        lo = node["a"]
        for kid in node["kids"]:
            if kid["a"] > lo:
                out.append((lo, kid["a"]))
            lo = max(lo, kid["b"])
        if node["b"] > lo:
            out.append((lo, node["b"]))
    return out


def _merged(intervals: list) -> list[list[float]]:
    out: list[list[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return out


def _meet(xs: list, ys: list) -> list[tuple[float, float]]:
    """The intersection of two lists of intervals."""
    xs, ys = _merged(xs), _merged(ys)
    out, i, j = [], 0, 0
    while i < len(xs) and j < len(ys):
        lo, hi = max(xs[i][0], ys[j][0]), min(xs[i][1], ys[j][1])
        if hi > lo:
            out.append((lo, hi))
        if xs[i][1] < ys[j][1]:
            i += 1
        else:
            j += 1
    return out


def overlap(parsed: dict, family: list, phases) -> dict | None:
    """{"idle_s": idle between programs with one of ``phases`` innermost,
    "overlap_s": of it, with a ``family`` span open}; None without
    engine/tick spans."""
    if not any(s[0] == "tick" for s in parsed["spans"]):
        return None
    idle = _meet(xplane_spans.program_gaps(parsed),
                 own_stretches(parsed["spans"], phases))
    both = _meet(idle, family)
    return {"idle_s": sum(b - a for a, b in idle),
            "overlap_s": sum(b - a for a, b in both)}


def run_trace_path() -> str | None:
    """The file xplane_spans.load parsed: this run's, under the
    ``.perfbench_tmp`` of the checkout that holds xplane_spans."""
    checkout = os.path.dirname(os.path.dirname(
        os.path.abspath(xplane_spans.__file__)))
    return xplane_spans.find_run_trace(
        os.path.join(checkout, ".perfbench_tmp"),
        xplane_spans.process_start_s())


def read(params: dict, ctx: dict):
    parsed = xplane_spans.load(ctx)
    path = run_trace_path() if parsed is not None else None
    if path is None:
        return None
    family = family_spans(path, params["prefix"])
    got = overlap(parsed, family, params["phases"]) if family else None
    if got is None or got["idle_s"] <= 0:
        return None
    ctx.setdefault("notes", {})["idle_overlap"] = {
        **got, "phases": list(params["phases"]), "prefix": params["prefix"],
        "family_spans": len(family)}
    return 100.0 * got["overlap_s"] / got["idle_s"]
