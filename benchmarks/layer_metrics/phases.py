"""Layer "kernels", by op role: every op of a compiled step is traced inside
``jax.named_scope("<role>/<op_type>")``, so a device operation's ``op_name``
says whether it belongs to the forward pass, the backward pass or the optimizer.
Per step (median over the steps of the traced window) the union of chip 0's
operations of each role, and the share of the operations' time that carries a
role at all. A fusion has the role of its own ``op_name``: one that XLA merged
across a role boundary cannot be split.

An executable without roles (an older commit, or a compile cache that an older
commit warmed) reads ``attributed_pct`` 0 and no phase time.
"""
from benchmarks.lib import program_spans as P

PHASES = ("forward", "backward", "optimizer")


def read(ctx):
    path = P.newest_xplane()
    if path is None:
        return {}
    trace = P.load(path)
    times = P.phase_times(trace)
    if times is None or not times["sum_ops"]:
        return {}
    s = ctx["suffix"]
    share = times["sum_attributed"] / times["sum_ops"]
    out = {"phases.attributed_pct." + s: 100.0 * share}
    for role in PHASES:
        if role in times["per_step"]:
            out["phases.%s_ms.%s" % (role, s)] = \
                P.median_ms(times["per_step"][role])
    unions_s = sum(sum(v) for v in times["per_step"].values()) / 1e9
    print("# phases: read %s: roles %s; their unions summed over the window "
          "%.6f s; attributed %.2f %% of the operations' %.6f s = %.6f s "
          "(busy %.6f s; the difference is operations of one role that "
          "overlap)"
          % (path, sorted(times["per_step"]) or "none", unions_s,
             100.0 * share, times["sum_ops"] / 1e9,
             times["sum_attributed"] / 1e9, times["busy"] / 1e9), flush=True)
    top = sorted(times["by_scope"].items(), key=lambda kv: -kv[1])[:12]
    print("# phases: share of the operations' time by scope: %s"
          % ", ".join("%s %.1f %%" % (scope, 100.0 * ns / times["sum_ops"])
                      for scope, ns in top), flush=True)
    return out
