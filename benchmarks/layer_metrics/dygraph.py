"""Layer "dygraph": the host's share of an imperative step (median step wall
time less the device's busy time inside that step's span), and how often a
lazy flush found its compiled graph in the cache (counters)."""
import statistics


def read(ctx):
    out = {}
    s = ctx["suffix"]
    tr = ctx["trace"]
    if tr:
        out["dygraph.host_ms_per_step." + s] = 1e3 * statistics.median(
            w - b for w, b in zip(tr["step_wall_s"], tr["step_busy_s"]))
    flushes = ctx["counters"].get("lazy.flushes")
    if flushes:
        out["dygraph.lazy_cache_hit_pct." + s] = \
            100.0 * ctx["counters"]["lazy.cache_hits"] / flushes
    return out
