"""Layer "kernels", the doubly gated short convolution: per traced step
(median over the steps of the window) the union of chip 0's operations whose
scope's op type is ``short_conv_gate`` or ``short_conv_gate_grad``
(``gate_ms``: the two gates and the taps, the forward op, its second run
with the recomputed sublayer, and the gradient op, which makes the convolved
stream again from the op's inputs), that time's share of the op's roofline
(``gate_roofline_pct``: a step's gated convolutions are the configuration's
``C`` layers, forward once and backward twice the forward, recomputation is
time and not counted work, by
``configs/<family>/flops.py:gate_ops_and_bytes``, which counts the three
streams and the result once whatever implements the op), and the union of
the mixer's other operations (``project_ms``: the input and the output
projection and their gradients). The mixer builds its ops inside
``name_scope("shortconv")``, which a compiled step carries as the third
component of an operation's scope, ``<role>/<op_type>/shortconv/...``: that
tells its two ``mul``s from the other layers'.

A program without the op (an older commit, another model) has no such
operation: the reader returns nothing.
"""
from benchmarks.layer_metrics import _scoped as S
from benchmarks.lib import program_spans as P

NAME_SCOPE = "shortconv"
CORE = frozenset(("short_conv_gate", "short_conv_gate_grad"))


def is_gate(event_name, op_name):
    return S.op_type_of(op_name) in CORE


def is_projection(event_name, op_name):
    """An operation of the short-convolution mixer other than its core op."""
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
    ns = S.per_step_ns(events, op_names, steps, is_gate)
    if not any(ns):
        return {}
    s = ctx["suffix"]
    gate_ms = P.median_ms(ns)
    out = {"shortconv.gate_ms." + s: gate_ms,
           "shortconv.project_ms." + s: P.median_ms(
               S.per_step_ns(events, op_names, steps, is_projection))}
    roof = S.step_roofline(ctx, path, "C", "gate_ops_and_bytes", gate_ms)
    if roof:
        out["shortconv.gate_roofline_pct." + s] = roof[0]
        print("# shortconv: the gated convolutions of a step, forward and "
              "backward: %.3f GFLOP, %.3f GB, bound by %s"
              % (roof[2], roof[3], roof[1]), flush=True)
    print("# shortconv: read %s: the op %.4f ms, the mixer outside it %.4f "
          "ms a step (median of %d steps)"
          % (path, gate_ms, out["shortconv.project_ms." + s], len(ns)),
          flush=True)
    return out
