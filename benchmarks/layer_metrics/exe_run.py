"""Layer "executor", inside ``Executor.run``: the host's part of a step by the
program's own spans (``pt:executor/*`` in the trace's host plane), and chip 0's
idle time of a step split by the span the host was in. Medians over the steps
of the traced window; a step runs from one ``bench.step``'s start to the
next one's, so the steps tile the window and the four idle parts add up to the
window's idle time. ``exe_run.trace_s`` is the program's counter of the seconds
its steps were traced in Python (set-up only: nothing traces in the window).

A program without these spans or this counter (an older commit) reads nothing.
"""
import statistics

from benchmarks.lib import program_spans as P


def read(ctx):
    from paddle_tpu import observability as obs

    out = {}
    trace_s = obs.dump()["counters"].get("executor.trace_s")
    if trace_s is not None:
        out["exe_run.trace_s"] = trace_s

    path = P.newest_xplane()
    if path is None:
        return out
    trace = P.load(path)
    print("# exe_run: read %s: %d steps, %d program spans, %d operations"
          % (path, len(trace.steps), len(trace.spans), len(trace.ops)),
          flush=True)
    s = ctx["suffix"]
    for part in ("stage", "launch", "writeback"):
        ms = P.span_ms(trace, "executor/" + part)
        if ms is not None:
            out["exe_run.%s_ms.%s" % (part, s)] = ms
    idle = P.idle_parts(trace)
    if idle is None:
        return out
    for part in P.IDLE_PARTS + ("other",):
        out["exe_run.idle_%s_ms.%s" % (part, s)] = P.median_ms(idle[part])

    # the new numbers against the old ones they subdivide
    parts_s = sum(sum(idle[p]) for p in P.IDLE_PARTS + ("other",)) / 1e9
    line = "# exe_run: idle parts summed over the window %.6f s" % parts_s
    tr = ctx.get("trace")
    if tr:
        window_idle_s = tr["window_s"] - tr["busy_s_chip0"]
        line += ("; the window's idle time by device.idle_share_pct %.6f s "
                 "(%.3f %% of %.3f s): ratio %.4f"
                 % (window_idle_s, 100.0 * window_idle_s / tr["window_s"],
                    tr["window_s"], parts_s / window_idle_s))
    print(line, flush=True)
    line = ("# exe_run: idle parts less the gaps between a step's operations, "
            "median %.4f ms" % P.median_ms(idle["dispatch"]))
    if tr and tr["dispatch_s"]:
        line += "; executor.dispatch_ms %.4f" % (
            1e3 * statistics.median(tr["dispatch_s"]))
    print(line, flush=True)
    return out
