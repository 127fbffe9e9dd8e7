"""Reduction of a JAX profiler trace to the numbers the per-layer metrics read.

``load`` reads an ``.xplane.pb`` with ``jax.profiler.ProfileData`` (nothing but
JAX) into a small ``Trace``: for each chip the device operations of its
"XLA Ops" line, and the benchmark's own host spans (``TraceAnnotation`` names
that start with ``bench.``). Everything after that is plain arithmetic on
intervals, tested on the recorded trace in ``benchmarks/fixtures``.

All times are nanoseconds on the profiler's one clock; host spans and device
operations share it.
"""
from __future__ import annotations

import glob
import os
import statistics

SPAN_PREFIX = "bench."
STEP_SPAN = "step"
COLLECTIVE_WORDS = ("all-reduce", "all-gather", "reduce-scatter",
                    "all-to-all", "collective-permute", "collective")
# the other lines of a device plane (modules, steps) repeat the operations at
# another level: counting them would count time twice
OPS_LINE = "XLA Ops"


class Trace:
    """chips: {chip index: [(name, start_ns, end_ns)]} sorted by start;
    spans: [(name, start_ns, end_ns)] without the ``bench.`` prefix."""

    def __init__(self, chips, spans):
        self.chips = {int(k): [tuple(o) for o in v] for k, v in chips.items()}
        self.spans = [tuple(s) for s in spans]

    @classmethod
    def from_json(cls, obj):
        return cls(obj["chips"], obj["spans"])


def find_xplane(trace_dir):
    files = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile",
                                          "*", "*.xplane.pb")))
    if not files:
        raise FileNotFoundError("no .xplane.pb under %s" % trace_dir)
    return files[-1]


def short_name(hlo_text):
    """``%convert_reduce_fusion.54 = (f32[...]) fusion(...), kind=kOutput``
    becomes ``convert_reduce_fusion fusion:kOutput``: an event of the TPU's
    "XLA Ops" line is named by the whole text of its HLO instruction, and the
    numbered copies of one fusion are one row of the breakdown."""
    head, _, rest = hlo_text.partition(" = ")
    base = head.lstrip("%").rstrip("0123456789").rstrip(".") or head
    if not rest:
        return base
    opcode = ""
    depth = 0
    for i, ch in enumerate(rest):   # the opcode follows the result's shape
        if ch in "([{":
            depth += 1
        elif ch in ")]}":
            depth -= 1
        elif ch == " " and depth == 0:
            opcode = rest[i + 1:].split("(", 1)[0]
            break
    kind = ""
    if ", kind=" in rest:
        kind = ":" + rest.split(", kind=", 1)[1].split(",", 1)[0]
    return "%s %s%s" % (base, opcode, kind)


def load(path):
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    chips, spans = {}, []
    names = {}   # a step's few thousand instructions recur in every step
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            chip = int(plane.name.rsplit(":", 1)[1].split()[0])
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                ops = chips.setdefault(chip, [])
                for ev in line.events:
                    start = int(ev.start_ns)
                    name = names.get(ev.name)
                    if name is None:
                        name = names[ev.name] = short_name(ev.name)
                    ops.append((name, start, start + int(ev.duration_ns)))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(SPAN_PREFIX):
                        start = int(ev.start_ns)
                        spans.append((ev.name[len(SPAN_PREFIX):], start,
                                      start + int(ev.duration_ns)))
    for ops in chips.values():
        ops.sort(key=lambda o: o[1])
    spans.sort(key=lambda s: s[1])
    return Trace(chips, spans)


# -- interval arithmetic ------------------------------------------------------

def merge(intervals):
    """Union of (start, end) intervals as a sorted list of disjoint ones."""
    out = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def total(intervals):
    return sum(e - s for s, e in intervals)


def clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def gaps(merged, lo, hi):
    """The parts of [lo, hi] that no interval of ``merged`` covers."""
    out, at = [], lo
    for s, e in clip(merged, lo, hi):
        if s > at:
            out.append((at, s))
        at = max(at, e)
    if hi > at:
        out.append((at, hi))
    return out


def uncovered(intervals, cover):
    """Nanoseconds of ``intervals`` (merged first) that ``cover`` leaves
    bare: the exposed part of collectives against compute."""
    cover = merge(cover)
    bare = 0
    for s, e in merge(intervals):
        bare += total(gaps(cover, s, e))
    return bare


def span_at(spans, t):
    """The innermost (latest-starting) span that holds the instant t."""
    best = None
    for name, s, e in spans:
        if s <= t < e and (best is None or s >= best[1]):
            best = (name, s)
    return best[0] if best else "between_steps"


def is_collective(name):
    return any(w in name.lower() for w in COLLECTIVE_WORDS)


# -- the reduction -------------------------------------------------------------

def reduce(trace):
    """Numbers of the traced window, which runs from the first step span's
    start to the last one's end. Chip 0 (the lowest index) stands for the
    per-chip figures; ``busy_s`` is the mean over chips."""
    steps = [(s, e) for name, s, e in trace.spans if name == STEP_SPAN]
    if not steps or not trace.chips:
        return None
    lo, hi = steps[0][0], steps[-1][1]
    window_s = (hi - lo) / 1e9
    busy = {}
    for chip, ops in trace.chips.items():
        busy[chip] = total(clip(merge(
            (s, e) for _, s, e in ops), lo, hi)) / 1e9
    chip0 = min(trace.chips)
    ops = [o for o in trace.chips[chip0] if o[2] > lo and o[1] < hi]
    merged = merge((s, e) for _, s, e in ops)

    by_name = {}
    coll, compute = [], []
    for name, s, e in ops:
        s, e = max(s, lo), min(e, hi)
        by_name[name] = by_name.get(name, 0) + (e - s)
        (coll if is_collective(name) else compute).append((s, e))
    coll_ns = total(merge(coll))
    exposed_ns = uncovered(coll, compute)

    dispatch, step_busy, step_wall = [], [], []
    for s, e in steps:
        inside = clip(merged, s, e)
        step_wall.append((e - s) / 1e9)
        step_busy.append(total(inside) / 1e9)
        if inside:
            dispatch.append(((inside[0][0] - s) + (e - inside[-1][1])) / 1e9)

    idle = sorted(gaps(merged, lo, hi), key=lambda g: g[0] - g[1])[:10]
    sum_ops = sum(by_name.values())
    return {
        "window_s": window_s,
        "n_steps": len(steps),
        "busy_s": statistics.mean(busy.values()),
        "busy_s_chip0": busy[chip0],
        "sum_ops_s": sum_ops / 1e9,
        "collective_s": coll_ns / 1e9,
        "exposed_collective_s": exposed_ns / 1e9,
        "dispatch_s": dispatch,
        "step_busy_s": step_busy,
        "step_wall_s": step_wall,
        "device_ops": [[n, d / 1e9] for n, d in sorted(
            by_name.items(), key=lambda kv: -kv[1])[:10]],
        "idle_gaps": [[span_at(trace.spans, (s + e) // 2), (e - s) / 1e9]
                      for s, e in idle],
    }
