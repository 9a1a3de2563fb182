"""The program's own names in the raw trace: host phase spans and scoped
device operations.  Part of the yardstick, beside trace_reduce.py (which
keeps device planes and event names only and is left as it is).

What the engine writes into a jax.profiler capture (ipex_llm_tpu/serving/
perfwatch.py, engine.py, models/decoder.py; looked at by hand, my chip run
PR 25): on "/host:CPU", on the engine thread's line ("python3"), one event
"engine/tick" per tick (stat ``tick``) and nested inside it "engine/plan",
"checkpoint", "admit", "prepare" with "dispatch" inside it (stats
``variant`` = steady | mixed | chunk | spec, ``family``, ``p_b``, ``width``,
``maxp``, ``rows_active``; a string stat is a reference to a stat name),
"drain" with "sync" inside it, "commit", "record".  On "/device:TPU:0" an
"XLA Ops" event's NAME is its HLO instruction with its shapes
("%copy.37 = f32[2,8,256,128]{...} copy(...)"), and its METADATA carries the
stats ``tf_op``: the instruction's name path, which holds the program's
named scopes ("jit(_ragged_tick)/tick/decode/while/body/closed_call/layer/
qkv/mul:"; an instruction the compiler put in itself has none), and
``hlo_category``.  jax.profiler.ProfileData shows an event's own stats but
not its metadata's, so the file's protobuf wire format is read here.  Host
and device events count from the same start of the capture: a tick's
program starts ~0.3 ms after its ``engine/dispatch`` opens.

The parsed form is plain JSON (a cut of one is the tests' fixture):

  {"spans":   [[name, start_s, end_s, {stat: value}]],   # engine/*, by start
   "modules": [[name, start_s, dur_s]],                  # chip 0, by start
   "ops":     [[HLO text, start_s, dur_s, name path, category]]}   # chip 0

Everything below the parser is a pure function of that form.  A trace
without the spans or the scopes (a program from before PR 25) gives None,
never a guess.
"""

from __future__ import annotations

import functools
import glob
import os
import re
import struct
from statistics import median

import trace_reduce

HOST_PLANE = "/host:CPU"
SPAN_PREFIX = "engine/"
# the stat of a device operation's metadata that holds its name path (the
# HLO instruction's op_name: "jit(_ragged_tick)/tick/decode/while/body/
# closed_call/layer/qkv/mul:"); an operation the compiler put in itself
# (most copies) has none
PATH_STAT = "tf_op"
CATEGORY_STAT = "hlo_category"

# the program's named scopes, innermost kind first: an operation belongs to
# the first one its name path holds
SCOPES = ("layer/kv_write", "layer/kv_read", "layer/qkv", "layer/attn",
          "layer/o_proj", "layer/mlp", "head", "sample", "tick/prefill",
          "tick/merge", "tick/decode")
MOSAIC = ("qmatmul", "ragged_paged_attention")
POOL_SCOPES = ("layer/kv_write", "layer/kv_read")
# operations that hold other operations: their text lists every buffer that
# passes through, their self time is the loop's own
CONTROL_FLOW = ("while", "conditional", "call")

# host phases, grouped as the idle metrics report them
GROUPS = {"plan_admit": ("plan", "checkpoint", "admit"),
          "prepare_dispatch": ("prepare", "dispatch"),
          "drain_commit": ("drain", "commit", "record")}


# -- finding and parsing this run's trace ---------------------------------------

def process_start_s() -> float | None:
    """When this process started, on the clock of file times."""
    try:
        return os.stat("/proc/self").st_ctime
    except OSError:
        return None


def find_run_trace(tmp_root: str, not_before: float | None) -> str | None:
    """The .xplane.pb this run wrote under ``<tmp_root>/<workload>/trace``
    (run.py's scratch layout; trace_reduce.find_xplane picks the file of a
    directory), or None.  A file last written before ``not_before`` (the
    start of this process) is another run's and is never read; without a
    ``not_before`` nothing can be told apart and nothing is read."""
    if not_before is None:
        return None
    fresh = []
    for logdir in glob.glob(os.path.join(tmp_root, "*", "trace")):
        path = trace_reduce.find_xplane(logdir)
        if path is not None and os.path.getmtime(path) >= not_before:
            fresh.append((os.path.getmtime(path), path))
    return max(fresh)[1] if fresh else None


def _fields(buf):
    """(field number, wire type, value) of one protobuf message: a varint as
    int, a length-delimited field as a memoryview, fixed 64/32 as bytes."""
    i, n = 0, len(buf)
    while i < n:
        key = shift = 0
        while True:
            b = buf[i]
            i += 1
            key |= (b & 0x7F) << shift
            if b < 0x80:
                break
            shift += 7
        wire = key & 7
        if wire == 0 or wire == 2:
            v = shift = 0
            while True:
                b = buf[i]
                i += 1
                v |= (b & 0x7F) << shift
                if b < 0x80:
                    break
                shift += 7
            if wire == 2:
                v, i = buf[i:i + v], i + v
        elif wire == 1 or wire == 5:
            width = 8 if wire == 1 else 4
            v, i = bytes(buf[i:i + width]), i + width
        else:
            raise ValueError(f"protobuf wire type {wire}")
        yield key >> 3, wire, v


def _map_entry(buf):
    key = value = None
    for no, _, v in _fields(buf):
        if no == 1:
            key = v
        elif no == 2:
            value = v
    return key, value


def _stats(bufs, stat_names: dict) -> dict:
    """{stat name: value} of XStat messages (tsl/profiler/protobuf/
    xplane.proto: metadata_id=1, double=2, uint64=3, int64=4, str=5,
    bytes=6, ref=7: the value is the name of that stat metadata)."""
    out = {}
    for buf in bufs:
        name = value = None
        for no, wire, v in _fields(buf):
            if no == 1:
                name = stat_names.get(v)
            elif no == 2:
                value = struct.unpack("<d", v)[0]
            elif no == 3:
                value = v
            elif no == 4:       # int64 as a varint: two's complement
                value = v - (1 << 64) if v >= 1 << 63 else v
            elif no == 5:
                value = bytes(v).decode("utf-8", "replace")
            elif no == 7:
                value = stat_names.get(v, "")
        if name is not None and value is not None:
            out[name] = value
    return out


def _plane(buf) -> dict:
    """One XPlane (name=2, lines=3, event_metadata=4, stat_metadata=5):
    {"name", "lines": [(line name, [(metadata id, start_s, dur_s, stat
    buffers)])], "meta": {id: (name, stat buffers)}, "stat_names"}."""
    name, lines, meta, stat_names = "", [], {}, {}
    for no, _, v in _fields(buf):
        if no == 2:
            name = bytes(v).decode()
        elif no == 3:
            lines.append(v)
        elif no == 4:
            key, m = _map_entry(v)
            m_name, m_stats = "", []
            for f, _, x in _fields(m):      # XEventMetadata: name=2, stats=5
                if f == 2:
                    m_name = bytes(x).decode("utf-8", "replace")
                elif f == 5:
                    m_stats.append(x)
            meta[key] = (m_name, m_stats)
        elif no == 5:
            key, m = _map_entry(v)
            for f, _, x in _fields(m):      # XStatMetadata: name=2
                if f == 2:
                    stat_names[key] = bytes(x).decode("utf-8", "replace")
    out_lines = []
    for buf_l in lines:                     # XLine: name=2, timestamp_ns=3,
        l_name, t0_ns, events = "", 0, []   # events=4
        for f, _, x in _fields(buf_l):
            if f == 2:
                l_name = bytes(x).decode()
            elif f == 3:
                t0_ns = x
            elif f == 4:
                events.append(x)
        evs = []
        for e in events:                    # XEvent: metadata_id=1,
            mid = off = dur = 0             # offset_ps=2, duration_ps=3,
            e_stats = []                    # stats=4
            for f, _, x in _fields(e):
                if f == 1:
                    mid = x
                elif f == 2:
                    off = x
                elif f == 3:
                    dur = x
                elif f == 4:
                    e_stats.append(x)
            evs.append((mid, t0_ns * 1e-9 + off * 1e-12, dur * 1e-12,
                        e_stats))
        out_lines.append((l_name, evs))
    return {"name": name, "lines": out_lines, "meta": meta,
            "stat_names": stat_names}


def parse_xplane(path: str) -> dict:
    """The parsed form (module docstring) of one .xplane.pb, read off the
    protobuf's wire format: jax.profiler.ProfileData gives an event's name
    and its own stats, but not the stats of its metadata, where a device
    operation's name path lives."""
    with open(path, "rb") as f:
        space = memoryview(f.read())
    spans, modules, ops = [], [], []
    chip0 = None
    for no, _, buf in _fields(space):       # XSpace: planes=1
        if no != 1:
            continue
        name = next((bytes(v).decode() for f, _, v in _fields(buf)
                     if f == 2), "")
        m = trace_reduce.DEVICE_PLANE.match(name)
        if m and (chip0 is None or int(m.group(1)) < chip0[0]):
            chip0 = (int(m.group(1)), buf)      # parsed below, if lowest
        if name != HOST_PLANE:
            continue
        plane = _plane(buf)
        names = plane["stat_names"]
        for _, events in plane["lines"]:
            for mid, a, d, e_stats in events:
                name = plane["meta"].get(mid, ("", ()))[0]
                if name.startswith(SPAN_PREFIX):
                    spans.append([name[len(SPAN_PREFIX):], a, a + d,
                                  _stats(e_stats, names)])
    if chip0 is not None:
        plane = _plane(chip0[1])
        names = plane["stat_names"]
        described = {}                      # metadata id -> (name, path)
        for l_name, events in plane["lines"]:
            if l_name == trace_reduce.MODULE_LINE:
                modules = [[plane["meta"][mid][0], a, d]
                           for mid, a, d, _ in events]
            elif l_name == trace_reduce.OPS_LINE:
                for mid, a, d, _ in events:
                    if mid not in described:
                        name, m_stats = plane["meta"][mid]
                        st = _stats(m_stats, names)
                        described[mid] = (name, st.get(PATH_STAT, ""),
                                          st.get(CATEGORY_STAT, ""))
                    ops.append([described[mid][0], a, d, *described[mid][1:]])
    spans.sort(key=lambda s: (s[1], -s[2]))
    modules.sort(key=lambda m: m[1])
    ops.sort(key=lambda o: (o[1], -o[2]))
    return {"spans": spans, "modules": modules, "ops": ops}


@functools.lru_cache(maxsize=2)
def _parsed(path: str, mtime: float) -> dict:
    return parse_xplane(path)


def parsed_file(path: str) -> dict:
    """:func:`parse_xplane` of ``path``, once per process and writing."""
    return _parsed(path, os.path.getmtime(path))


def load(ctx: dict) -> dict | None:
    """This run's parsed trace, once per process; None where run.py reduced
    no trace, or none written by this process is found."""
    if not ctx.get("trace"):
        return None
    checkout = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    path = find_run_trace(os.path.join(checkout, ".perfbench_tmp"),
                          process_start_s())
    if path is None:
        return None
    return parsed_file(path)


# -- host spans --------------------------------------------------------------------

def span_tree(spans: list) -> list[dict]:
    """The spans as nodes {"name", "a", "b", "attrs", "kids"}, nested by
    containment; the top level in time order."""
    top, stack = [], []
    for name, a, b, attrs in sorted(spans, key=lambda s: (s[1], -s[2])):
        node = {"name": name, "a": a, "b": b, "attrs": attrs, "kids": []}
        while stack and stack[-1]["b"] <= a:
            stack.pop()
        (stack[-1]["kids"] if stack else top).append(node)
        stack.append(node)
    return top


def _walk(nodes):
    for n in nodes:
        yield n
        yield from _walk(n["kids"])


def dispatches(spans: list) -> list[dict]:
    """Every engine/dispatch span with a variant, in time order, each with
    the end of its window: the end of its tick's engine/sync that follows
    it, else (a pure-chunk tick has no sync) the start of the next
    dispatch, else the end of the trace."""
    out = []
    for tick in span_tree(spans):
        inside = sorted(_walk(tick["kids"]) if tick["name"] == "tick"
                        else _walk([tick]), key=lambda n: n["a"])
        for i, n in enumerate(inside):
            if n["name"] != "dispatch" or "variant" not in n["attrs"]:
                continue
            sync = next((s for s in inside[i + 1:] if s["name"] == "sync"),
                        None)
            out.append({"a": n["a"], "end": sync["b"] if sync else None,
                        "variant": n["attrs"]["variant"],
                        "attrs": n["attrs"]})
    out.sort(key=lambda d: d["a"])
    for d, nxt in zip(out, out[1:] + [None]):
        if d["end"] is None:
            d["end"] = nxt["a"] if nxt else float("inf")
    return out


def module_variants(parsed: dict, module: str) -> list[tuple]:
    """[(name, start_s, dur_s, variant or None)] of the executions of the
    compiled programs whose name matches ``module``: an execution belongs
    to the dispatch whose window [dispatch start, its sync's end) holds
    most of it, and more than half (the host's and the device's clocks
    agree to a few tenths of a ms, so an execution may seem to start just
    before its dispatch opens).  None: no such window (the capture began
    inside that tick)."""
    pat = re.compile(module)
    disp = dispatches(parsed["spans"])
    out, i = [], 0
    for name, s, d in parsed["modules"]:
        if not pat.search(name):
            continue
        while i + 1 < len(disp) and disp[i + 1]["a"] <= s:
            i += 1
        best, held = None, 0.5 * d
        for cand in disp[i:i + 2]:
            got = _overlap(cand["a"], cand["end"], s, s + d)
            if got > held:
                best, held = cand, got
        out.append((name, s, d, best["variant"] if best else None))
    return out


def variant_median_ms(parsed: dict, module: str, variant: str):
    durs = [d for _, _, d, v in module_variants(parsed, module)
            if v == variant]
    return median(durs) * 1e3 if durs else None


# -- device operations by scope --------------------------------------------------

def self_times(ops: list) -> list[float]:
    """Each operation's duration less its direct children's (a ``while``
    spans its body), as trace_reduce.reduce_events computes it; ``ops`` by
    (start, -duration)."""
    self_s = [o[2] for o in ops]
    stack: list[int] = []
    for j, o in enumerate(ops):
        while stack and ops[stack[-1]][1] + ops[stack[-1]][2] <= o[1]:
            stack.pop()
        if stack:
            self_s[stack[-1]] -= o[2]
        stack.append(j)
    return [max(s, 0.0) for s in self_s]


def scope_of(path: str) -> str | None:
    """The program's scope an operation's name path holds (SCOPES order)."""
    parts = "/" + path + "/"
    for s in SCOPES:
        if "/" + s + "/" in parts:
            return s
    return None


def pool_pattern(config: dict, hf: dict) -> re.Pattern:
    """Shapes of the KV pool and of one layer of it, as HLO text writes
    them: [layers,pages,kv heads,page,head] and [pages,kv heads,page,head]
    (the engine's sizes: EngineConfig.n_pages, models/config.py head_dim)."""
    eng = config["engine"]
    pages = eng.get("pool_pages") or max(
        eng["max_rows"] * eng["max_seq_len"] // eng["page_size"] // 2,
        eng["max_rows"] + 2)
    head = hf.get("head_dim") or hf["hidden_size"] // hf["num_attention_heads"]
    dims = f"{pages},{hf['num_key_value_heads']},{eng['page_size']},{head}"
    return re.compile(r"\[(?:\d+,)?" + dims + r"\]")


def classify(parsed: dict, pool: re.Pattern) -> dict | None:
    """Self seconds of chip 0's operations by class; the classes partition
    the busy time: a Mosaic kernel by its name; ``kv_pool_move``: an
    operation in a pool scope (layer/kv_read, layer/kv_write), or a copy,
    slice, update or other operation that is no control flow and whose
    HLO text holds a pool-shaped buffer (result or operand); then the
    program's scope it carries; ``unscoped`` for one that carries none.
    None where no operation carries a scope."""
    ops = parsed["ops"]
    if not ops:
        return None
    by_class: dict[str, float] = {}
    unscoped_ops: dict[str, float] = {}
    any_scope = False
    for o, sec in zip(ops, self_times(ops)):
        name, _, _, path, _ = o
        kernel = trace_reduce.kernel_of(name)
        scope = scope_of(path)
        any_scope = any_scope or scope is not None
        if kernel.startswith(MOSAIC):
            cls = next(k for k in MOSAIC if kernel.startswith(k))
        elif scope in POOL_SCOPES or (kernel not in CONTROL_FLOW
                                      and pool.search(name)):
            cls = "kv_pool_move"
        elif scope is not None:
            cls = scope
        else:
            cls = "unscoped"
            unscoped_ops[kernel] = unscoped_ops.get(kernel, 0.0) + sec
        by_class[cls] = by_class.get(cls, 0.0) + sec
    if not any_scope:
        return None
    return {"seconds": by_class, "total_s": sum(by_class.values()),
            "unscoped_ops": dict(sorted(unscoped_ops.items(),
                                        key=lambda kv: -kv[1])[:12])}


# -- device idle time by host phase ---------------------------------------------

def program_gaps(parsed: dict) -> list[tuple[float, float]]:
    """[start, end) of every stretch in which chip 0 runs no operation and
    that lies between two programs (not wholly inside one execution):
    trace_reduce's ``host_between_programs``, all of them."""
    _, gaps = trace_reduce._union([(o[1], o[1] + o[2]) for o in parsed["ops"]])
    mods = parsed["modules"]
    return [(g, g + d) for g, d in gaps
            if not any(s <= g and g + d <= s + m for _, s, m in mods)]


def _overlap(a, b, lo, hi) -> float:
    return max(0.0, min(b, hi) - max(a, lo))


def gap_table(parsed: dict) -> dict | None:
    """Seconds of device idle time between programs, by the innermost host
    span they fall in: {"by_phase": {phase: s}, "tick": s inside an
    engine/tick but no phase of it, "none": s inside no span, "total": s,
    "working_ticks": engine/tick spans with a dispatch}.  None without
    engine/tick spans."""
    top = span_tree(parsed["spans"])
    ticks = [n for n in top if n["name"] == "tick"]
    if not ticks:
        return None
    gaps = program_gaps(parsed)
    by_phase: dict[str, float] = {}

    def attribute(node, lo, hi) -> float:
        """Seconds of [lo, hi) inside ``node``; booked to the innermost."""
        got = _overlap(node["a"], node["b"], lo, hi)
        if got <= 0:
            return 0.0
        kids = sum(attribute(k, lo, hi) for k in node["kids"])
        by_phase[node["name"]] = by_phase.get(node["name"], 0.0) + got - kids
        return got

    total = covered = 0.0
    for lo, hi in gaps:
        total += hi - lo
        covered += sum(attribute(n, lo, hi) for n in top)
    own = by_phase.pop("tick", 0.0)
    working = sum(1 for t in ticks
                  if any(n["name"] == "dispatch" for n in _walk(t["kids"])))
    return {"by_phase": by_phase, "tick": own, "none": total - covered,
            "total": total, "working_ticks": working}


def idle_by_span(parsed: dict) -> dict | None:
    """:func:`gap_table` as the labels of the result line's
    ``breakdown.idle_gaps``: seconds by ``engine/<innermost span>``, and
    ``host_between_programs`` for what lies inside no span.  None without
    engine/tick spans."""
    table = gap_table(parsed)
    if table is None:
        return None
    out = {SPAN_PREFIX + phase: s for phase, s in table["by_phase"].items()}
    out[SPAN_PREFIX + "tick"] = table["tick"]
    out["host_between_programs"] = table["none"]
    return out


def group_idle_ms(table: dict, group: str):
    """A group's idle milliseconds per traced working tick."""
    if not table["working_ticks"]:
        return None
    sec = sum(table["by_phase"].get(p, 0.0) for p in GROUPS[group])
    return 1e3 * sec / table["working_ticks"]
