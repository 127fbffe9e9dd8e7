"""Layer "kernels", the selective scan alone: per traced step (median over
the steps of the window) the union of chip 0's operations whose scope's op
type is ``ssd_chunk_scan`` or ``ssd_chunk_scan_grad``, and that time's share
of the scan's roofline: a step's scans are the configuration's ``M`` layers,
forward once and backward twice the forward (the gradient op's recomputation
of the forward is time, not counted work), by
``configs/<family>/flops.py:scan_ops_and_bytes``.

A program without the op (an older commit, another model) has no such
operation: the reader returns nothing.
"""
from benchmarks.layer_metrics import _scoped as S
from benchmarks.lib import program_spans as P

OP_TYPES = frozenset(("ssd_chunk_scan", "ssd_chunk_scan_grad"))


def is_scan(event_name, op_name):
    return S.op_type_of(op_name) in OP_TYPES


def read(ctx):
    loaded = S.load()
    if loaded is None:
        return {}
    path, steps, events, op_names = loaded
    ns = S.per_step_ns(events, op_names, steps, is_scan)
    if not any(ns):
        return {}
    ms = P.median_ms(ns)
    out = {"ssm.scan_ms." + ctx["suffix"]: ms}
    roof = S.step_roofline(ctx, path, "M", "scan_ops_and_bytes", ms)
    if roof:
        out["ssm.scan_roofline_pct." + ctx["suffix"]] = roof[0]
        print("# ssm: the scans of a step, forward and backward: %.3f GFLOP, "
              "%.3f GB, bound by %s" % (roof[2], roof[3], roof[1]),
              flush=True)
    print("# ssm: read %s: %.4f ms a step (median of %d steps) in operations "
          "of %s" % (path, ms, len(ns), "/".join(sorted(OP_TYPES))),
          flush=True)
    return out
