"""Layer "kernels", Kimi Delta Attention: per traced step (median over the
steps of the window) the union of chip 0's operations whose scope's op type
is ``kda_chunk`` or ``kda_chunk_grad`` (``scan_ms``: the gated delta rule by
chunks, the forward op and the gradient op, which makes what it needs of the
forward again from the op's inputs; the op's output is a checkpoint, so the
op is not run again with its recomputed sublayer), that time's share of
the op's roofline (``scan_roofline_pct``: a step's delta rules are the configuration's ``K``
layers, forward once and backward twice the forward, recomputation is time
and not counted work, by ``configs/<family>/flops.py:kda_ops_and_bytes``,
which counts the operations at chunks of 64 and the operands and the result
once whatever implements the op), and the union of the mixer's other
operations (``project_ms``: the q, k, v projections and their convolutions,
the decay's and the output gate's low-rank pairs, the write strength's
projection, the gated head norm, the output projection, and their
gradients). The mixer builds its ops inside ``name_scope("kda")``, which a
compiled step carries as the third component of an operation's scope,
``<role>/<op_type>/kda/...``: that tells its ``mul`` and ``rms_norm`` from
the other layers'.

A program without the op (an older commit, another model) has no such
operation: the reader returns nothing.
"""
from benchmarks.layer_metrics import _scoped as S
from benchmarks.lib import program_spans as P

NAME_SCOPE = "kda"
CORE = frozenset(("kda_chunk", "kda_chunk_grad"))


def is_scan(event_name, op_name):
    return S.op_type_of(op_name) in CORE


def is_projection(event_name, op_name):
    """An operation of the delta-rule mixer other than its core op."""
    scope = P.scope_of(op_name)
    if not scope or scope.partition("/")[2] in CORE:
        return False
    inner = op_name.partition(scope + "/")[2]
    return inner == NAME_SCOPE or inner.startswith(NAME_SCOPE + "/")


def read(ctx):
    loaded = S.load()
    if loaded is None:
        return {}
    path, steps, events, op_names = loaded
    ns = S.per_step_ns(events, op_names, steps, is_scan)
    if not any(ns):
        return {}
    s = ctx["suffix"]
    scan_ms = P.median_ms(ns)
    out = {"kda.scan_ms." + s: scan_ms,
           "kda.project_ms." + s: P.median_ms(
               S.per_step_ns(events, op_names, steps, is_projection))}
    roof = S.step_roofline(ctx, path, "K", "kda_ops_and_bytes", scan_ms)
    if roof:
        out["kda.scan_roofline_pct." + s] = roof[0]
        print("# kda: the delta rules of a step, forward and backward: %.3f "
              "GFLOP, %.3f GB, bound by %s" % (roof[2], roof[3], roof[1]),
              flush=True)
    print("# kda: read %s: the op %.4f ms, the mixer outside it %.4f ms a "
          "step (median of %d steps)"
          % (path, scan_ms, out["kda.project_ms." + s], len(ns)), flush=True)
    return out
