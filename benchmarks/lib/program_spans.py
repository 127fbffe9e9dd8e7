"""The program's own spans and op roles in a JAX profiler trace.

When the program's span layer is armed, every ``observability.tracing.span``
is also a ``jax.profiler.TraceAnnotation`` named ``"pt:" + name``, so a traced
run holds the spans of ``Executor.run`` (``pt:executor/stage`` ...) in the host
plane, on the clock of the device operations. Every op of a compiled step is
traced inside ``jax.named_scope("<role>/<op_type>")``, so each device operation's
``op_name`` starts, after its ``jit(...)`` prefixes, with its role.

``load`` reads a trace once: the benchmark's step spans, the ``pt:`` spans of
the thread that ran the steps, and chip 0's operations with the role of each. What the readers ``layer_metrics/exe_run.py``
and ``layer_metrics/phases.py`` compute from it is arithmetic on intervals
(``benchmarks.lib.trace``), tested on the recorded trace in
``benchmarks/fixtures``. A trace from a program without such spans or scopes
(an older commit) loads as well: it has no ``pt:`` span and no role.

All times are nanoseconds on the profiler's one clock.
"""
from __future__ import annotations

import bisect
import functools
import glob
import os
import statistics

from . import trace as T

PROGRAM_PREFIX = "pt:"
STEP_EVENT = T.SPAN_PREFIX + T.STEP_SPAN
ROLES = ("forward", "backward", "optimizer", "collective")
# the parts of Executor.run under which the device's idle time is split; they
# are siblings, so no instant lies under two of them
IDLE_PARTS = ("stage", "launch", "fetch")
REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


class ProgramTrace:
    """steps: [(start, end)] of the benchmark's step spans, by start;
    spans: [(name, start, end)] of the program's spans on the steps' thread,
    without the ``pt:`` prefix; ops: chip 0's [(scope, start, end)] by start,
    scope being ``<role>/<op_type>`` or "" where the operation has none."""

    def __init__(self, steps, spans, ops):
        self.steps = sorted(tuple(s) for s in steps)
        self.spans = sorted((tuple(s) for s in spans), key=lambda s: s[1])
        self.ops = sorted((tuple(o) for o in ops), key=lambda o: o[1])

    @classmethod
    def from_json(cls, obj):
        return cls(obj["steps"], obj["spans"], obj["ops"])

    def to_json(self):
        return {"steps": self.steps, "spans": self.spans, "ops": self.ops}


def newest_xplane(repo=REPO):
    """The newest ``*.xplane.pb`` under ``<repo>/.bench_trace/*/``: the
    harness empties a cell's directory before it traces, so it is this run's.
    None where there is none."""
    files = glob.glob(os.path.join(repo, ".bench_trace", "*", "plugins",
                                   "profile", "*", "*.xplane.pb"))
    return max(files, key=os.path.getmtime) if files else None


def scope_of(op_name):
    """``jit(step)/jit(main)/backward/mul_grad/dot_general`` ->
    ``backward/mul_grad``: the first two components after the ``jit(...)``
    prefixes, if the first is a role; else ""."""
    parts = op_name.split("/")
    for i, part in enumerate(parts):
        if part.startswith(("jit(", "pjit(")):
            continue
        return "/".join(parts[i:i + 2]) if part in ROLES else ""
    return ""


def role_of(scope):
    return scope.partition("/")[0]


def op_names(path):
    """{event name of a TPU plane: ``op_name`` of its HLO instruction}.

    ``jax.profiler.ProfileData`` gives an event its own stats only. The
    ``op_name`` (with ``hlo_category``, ``flops``, ``bytes_accessed``) is a
    stat of the event's *metadata*, called ``tf_op``, which ``ProfileData``
    does not show; so the interned tables of the device planes are read from
    the file with the program's wire reader
    (``observability/device_trace.py``). Only those tables are decoded, not
    the planes' events."""
    from paddle_tpu.observability import device_trace as wire

    with open(path, "rb") as f:
        data = f.read()
    names = {}
    for field, _, plane in wire._iter_fields(data):
        if field != 1:
            continue
        plane_name, events, stat_names = "", [], {}
        for field, _, value in wire._iter_fields(plane):
            if field == 2:
                plane_name = wire._utf8(value)
            elif field == 4:
                events.append(value)
            elif field == 5:
                key, meta = wire._parse_map_entry(value)
                for field, _, v in wire._iter_fields(meta):
                    if field == 2:
                        stat_names[key] = wire._utf8(v)
        if not plane_name.startswith("/device:TPU:"):
            continue
        for entry in events:
            meta = wire._parse_event_metadata(wire._parse_map_entry(entry)[1])
            for raw in meta["stats_raw"]:
                stat, value = wire._decode_stat(raw, stat_names)
                if stat == "tf_op":
                    names[meta["name"]] = str(value)
    return names


@functools.lru_cache(maxsize=1)   # both readers read the one trace of a run
def load(path):
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    steps, spans, ops = [], [], []
    chip0 = None
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            chip = int(plane.name.rsplit(":", 1)[1].split()[0])
            if chip0 is None or chip < chip0[0]:
                chip0 = (chip, plane)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                mine, found_step = [], False
                for ev in line.events:
                    if ev.name == STEP_EVENT:
                        found_step = True
                        steps.append((int(ev.start_ns),
                                      int(ev.start_ns + ev.duration_ns)))
                    elif ev.name.startswith(PROGRAM_PREFIX):
                        start = int(ev.start_ns)
                        mine.append((ev.name[len(PROGRAM_PREFIX):], start,
                                     start + int(ev.duration_ns)))
                if found_step:
                    spans.extend(mine)
    if chip0 is not None:
        scopes = {name: scope_of(op_name)
                  for name, op_name in op_names(path).items()}
        for line in chip0[1].lines:
            if line.name != T.OPS_LINE:
                continue
            for ev in line.events:
                start = int(ev.start_ns)
                ops.append((scopes.get(ev.name, ""), start,
                            start + int(ev.duration_ns)))
    return ProgramTrace(steps, spans, ops)


# -- what the readers compute ------------------------------------------------

def step_tiles(steps):
    """The steps as tiles of the window: each runs from its span's start to
    the next one's, the last to its own end."""
    return [(s, steps[i + 1][0] if i + 1 < len(steps) else e)
            for i, (s, e) in enumerate(steps)]


def median_ms(ns_per_step):
    return statistics.median(ns_per_step) / 1e6


def span_ms(trace, name):
    """Median duration (ms) of the program's spans called ``name`` that lie
    inside the traced window; None where there is none."""
    if not trace.steps:
        return None
    lo, hi = trace.steps[0][0], trace.steps[-1][1]
    durs = [(e - s) / 1e6 for n, s, e in trace.spans
            if n == name and s >= lo and e <= hi]
    return statistics.median(durs) if durs else None


def intersect(a, b):
    """The common part of two sorted lists of disjoint intervals."""
    out, i, j = [], 0, 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            out.append((lo, hi))
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


def per_tile(intervals, tiles):
    """Nanoseconds of ``intervals`` inside each tile; both are sorted and
    disjoint, so one pass over both does it."""
    out, i = [], 0
    for lo, hi in tiles:
        ns = 0
        while i < len(intervals) and intervals[i][1] <= lo:
            i += 1
        j = i
        while j < len(intervals) and intervals[j][0] < hi:
            ns += min(intervals[j][1], hi) - max(intervals[j][0], lo)
            j += 1
        i = max(i, j - 1)   # the last one may reach into the next tile
        out.append(ns)
    return out


def idle_parts(trace):
    """Chip 0's idle time of each step tile, split by what the host was in:
    {"stage" | "launch" | "fetch" | "other": [ns per step]}, plus
    "interior": the idle time between a step's first and last operation
    inside its span, and "dispatch": the rest of the idle time inside the
    span (what ``executor.dispatch_ms`` reads). None without steps, ops or
    program spans."""
    if not trace.steps or not trace.ops or not trace.spans:
        return None
    lo, hi = trace.steps[0][0], trace.steps[-1][1]
    busy = T.clip(T.merge((s, e) for _, s, e in trace.ops), lo, hi)
    idle = T.gaps(busy, lo, hi)
    tiles = step_tiles(trace.steps)
    out = {"other": per_tile(idle, tiles)}
    for part in IDLE_PARTS:
        under = T.merge((s, e) for n, s, e in trace.spans
                        if n == "executor/" + part)
        out[part] = per_tile(intersect(idle, under), tiles)
        out["other"] = [o - p for o, p in zip(out["other"], out[part])]
    # from a step's first operation to its last, inside its span
    starts, ends = [b[0] for b in busy], [b[1] for b in busy]
    cores = []
    for s, e in trace.steps:
        i = bisect.bisect_right(ends, s)        # the first that ends after s
        j = bisect.bisect_left(starts, e) - 1   # the last that starts before e
        cores.append((max(starts[i], s), min(ends[j], e)) if i <= j
                     else (s, s))
    out["interior"] = per_tile(idle, cores)
    out["dispatch"] = [d - i for d, i in zip(per_tile(idle, trace.steps),
                                             out["interior"])]
    return out


def phase_times(trace):
    """Per role, the union of chip 0's operations of that role in each step
    tile (ns per step); and over the window the operations' summed time, the
    part of it that carries a role, and the summed time of each scope. None
    without steps or ops."""
    if not trace.steps or not trace.ops:
        return None
    lo, hi = trace.steps[0][0], trace.steps[-1][1]
    by_role, by_scope = {}, {}
    sum_ops = sum_attributed = 0
    for scope, s, e in trace.ops:
        s, e = max(s, lo), min(e, hi)
        if e <= s:
            continue
        sum_ops += e - s
        if scope:
            sum_attributed += e - s
            by_scope[scope] = by_scope.get(scope, 0) + (e - s)
            by_role.setdefault(role_of(scope), []).append((s, e))
    tiles = step_tiles(trace.steps)
    busy = T.total(T.clip(T.merge((s, e) for _, s, e in trace.ops), lo, hi))
    return {"per_step": {role: per_tile(T.merge(intervals), tiles)
                         for role, intervals in by_role.items()},
            "by_scope": by_scope, "sum_ops": sum_ops,
            "sum_attributed": sum_attributed, "busy": busy}
