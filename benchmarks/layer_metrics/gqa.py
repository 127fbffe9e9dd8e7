"""Layer "kernels", grouped-query attention's core against its roofline
(``attend_roofline_pct``): the least time of scores and context at the true
head dim over the causal pairs
(``configs/<family>/flops.py:attend_ops_and_bytes``, forward once and
backward twice that for each ``*`` layer; the products are counted at the
head's own width, so a kernel that holds a narrow head in wider tiles reads
a lower share) over what ``attention.kernels_ms`` reads: the union of chip
0's attention operations a traced step.

A program without attention operations, or a configuration without the
function, has nothing to read: the reader returns nothing.
"""
from benchmarks.layer_metrics import _scoped as S
from benchmarks.layer_metrics import attention
from benchmarks.lib import program_spans as P


def read(ctx):
    loaded = S.load()
    if loaded is None:
        return {}
    path, steps, events, op_names = loaded
    attend_ms = P.median_ms(attention.per_step_ns(events, op_names, steps))
    roof = attend_ms and S.step_roofline(ctx, path, "*",
                                         "attend_ops_and_bytes", attend_ms)
    if not roof:
        return {}
    print("# gqa: attention's core of a step, forward and backward: %.3f "
          "GFLOP, %.3f GB, bound by %s, over %.4f ms of attention's kernels"
          % (roof[2], roof[3], roof[1], attend_ms), flush=True)
    return {"gqa.attend_roofline_pct." + ctx["suffix"]: roof[0]}
