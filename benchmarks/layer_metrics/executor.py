"""Layer "executor": XLA backend-compile seconds of set-up (``jax.monitoring``);
the 95th percentile of the traced window's single-step wall times (host
clock); and, from the trace, the host time of a step that the device waits
for: from the start of the benchmark's span around the step to the first
device operation, plus from the last device operation to the end of the span."""
import statistics

from benchmarks.lib.timing import step_ms_p95


def read(ctx):
    s = ctx["suffix"]
    out = {"executor.compile_s": ctx["compile_s"],
           "executor.step_ms_p95." + s: step_ms_p95(ctx["step_times"])}
    tr = ctx["trace"]
    if tr and tr["dispatch_s"]:
        out["executor.dispatch_ms." + s] = \
            1e3 * statistics.median(tr["dispatch_s"])
    return out
