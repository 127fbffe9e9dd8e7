"""Layer "mesh engines", inside ``run_data_parallel``: the host's part of a
mesh step by the program's own spans (``pt:parallel/*`` in the trace's host
plane, siblings under the ``pt:executor/run`` that ``Executor.run`` opens),
and chip 0's idle time of a step split by the span the host was in. Medians
over the steps of the traced window; a step runs from one ``bench.step``'s
start to the next one's, so the steps tile the window and the six idle parts
add up to the window's idle time. ``parallel/step`` is the jitted call, this
path's launch; ``parallel/release`` is the death of the donated arguments'
array objects, after the fetch. ``mesh_run.trace_s`` is the program's counter of the seconds
its mesh steps were traced in Python (set-up only: nothing traces in the
window).

The arithmetic is ``exe_run.py``'s (``lib/program_spans.py``), on the mesh
engine's names. A program without these spans or this counter (an older
commit, which has ``parallel/step`` alone) reads nothing.
"""
import bisect
import statistics

from benchmarks.lib import program_spans as P
from benchmarks.lib import trace as T

# metric's part -> the program's span; siblings, so no instant lies under two
SPANS = {"prepare": "parallel/prepare", "stage": "parallel/stage",
         "launch": "parallel/step", "writeback": "parallel/writeback",
         "fetch": "parallel/fetch", "release": "parallel/release"}
DURATIONS = ("prepare", "stage", "launch", "writeback")
IDLE_PARTS = ("prepare", "stage", "launch", "fetch", "release")


def idle_parts(trace):
    """Chip 0's idle time of each step tile, split by what the host was in:
    {"prepare" | "stage" | "launch" | "fetch" | "release" | "other": [ns per
    step]}, plus
    "interior": the idle time between a step's first and last operation
    inside its span, and "dispatch": the rest of the idle time inside the
    span (what ``executor.dispatch_ms`` reads). None without steps or ops."""
    if not trace.steps or not trace.ops:
        return None
    lo, hi = trace.steps[0][0], trace.steps[-1][1]
    busy = T.clip(T.merge((s, e) for _, s, e in trace.ops), lo, hi)
    idle = T.gaps(busy, lo, hi)
    tiles = P.step_tiles(trace.steps)
    out = {"other": P.per_tile(idle, tiles)}
    for part in IDLE_PARTS:
        under = T.merge((s, e) for n, s, e in trace.spans
                        if n == SPANS[part])
        out[part] = P.per_tile(P.intersect(idle, under), tiles)
        out["other"] = [o - p for o, p in zip(out["other"], out[part])]
    # from a step's first operation to its last, inside its span
    starts, ends = [b[0] for b in busy], [b[1] for b in busy]
    cores = []
    for s, e in trace.steps:
        i = bisect.bisect_right(ends, s)        # the first that ends after s
        j = bisect.bisect_left(starts, e) - 1   # the last that starts before e
        cores.append((max(starts[i], s), min(ends[j], e)) if i <= j
                     else (s, s))
    out["interior"] = P.per_tile(idle, cores)
    out["dispatch"] = [d - i for d, i in zip(P.per_tile(idle, trace.steps),
                                             out["interior"])]
    return out


def read(ctx):
    from paddle_tpu import observability as obs

    out = {}
    trace_s = obs.dump()["counters"].get("parallel.trace_s")
    if trace_s is not None:
        out["mesh_run.trace_s"] = trace_s

    path = P.newest_xplane()
    if path is None:
        return out
    trace = P.load(path)
    # the parent's mesh step has parallel/step and nothing beside it
    if P.span_ms(trace, SPANS["prepare"]) is None:
        return out
    print("# mesh_run: read %s: %d steps, %d program spans, %d operations"
          % (path, len(trace.steps), len(trace.spans), len(trace.ops)),
          flush=True)
    s = ctx["suffix"]
    for part in DURATIONS:
        ms = P.span_ms(trace, SPANS[part])
        if ms is not None:
            out["mesh_run.%s_ms.%s" % (part, s)] = ms
    idle = idle_parts(trace)
    if idle is None:
        return out
    for part in IDLE_PARTS + ("other",):
        out["mesh_run.idle_%s_ms.%s" % (part, s)] = P.median_ms(idle[part])

    # the new numbers against the old ones they subdivide
    parts_s = sum(sum(idle[p]) for p in IDLE_PARTS + ("other",)) / 1e9
    line = "# mesh_run: idle parts summed over the window %.6f s" % parts_s
    tr = ctx.get("trace")
    if tr:
        window_idle_s = tr["window_s"] - tr["busy_s_chip0"]
        line += ("; the window's idle time by device.idle_share_pct %.6f s "
                 "(%.3f %% of %.3f s): ratio %.4f"
                 % (window_idle_s, 100.0 * window_idle_s / tr["window_s"],
                    tr["window_s"], parts_s / window_idle_s))
    print(line, flush=True)
    line = ("# mesh_run: idle parts less the gaps between a step's "
            "operations, median %.4f ms" % P.median_ms(idle["dispatch"]))
    if tr and tr["dispatch_s"]:
        line += "; executor.dispatch_ms %.4f" % (
            1e3 * statistics.median(tr["dispatch_s"]))
    print(line, flush=True)
    return out
