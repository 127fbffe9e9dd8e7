"""Layer "kernels", the causal depthwise convolution: per traced step (median
over the steps of the window) the union of chip 0's operations whose scope's
op type is ``causal_conv1d`` or ``causal_conv1d_grad`` (``taps_ms``: the K
taps and the activation, the forward op, its second run with the recomputed
sublayer, and the gradient op, whether the program registers one or the
registry made an automatic one: both go by that name). Whatever mixer holds
the convolution: the delta rule's q, k, v and the selective scan's x, B, C
streams alike. The log line splits it by scope, which tells the first
forward from the one emitted again inside the backward and from the
gradient.

A program without the op (another model) has no such operation: the reader
returns nothing.
"""
from benchmarks.layer_metrics import _scoped as S
from benchmarks.lib import program_spans as P

CORE = frozenset(("causal_conv1d", "causal_conv1d_grad"))


def is_taps(event_name, op_name):
    return S.op_type_of(op_name) in CORE


def read(ctx):
    loaded = S.load()
    if loaded is None:
        return {}
    path, steps, events, op_names = loaded
    ns = S.per_step_ns(events, op_names, steps, is_taps)
    if not any(ns):
        return {}
    taps_ms = P.median_ms(ns)
    by_scope = {
        scope: P.median_ms(S.per_step_ns(
            events, op_names, steps,
            lambda _, op_name, scope=scope: P.scope_of(op_name) == scope))
        for scope in sorted({P.scope_of(n) for n in op_names.values()
                             if is_taps("", n)})}
    print("# conv1d: read %s: the convolutions %.4f ms a step (median of %d "
          "steps): %s" % (path, taps_ms, len(ns), ", ".join(
              "%s %.4f" % kv for kv in by_scope.items())), flush=True)
    return {"conv1d.taps_ms." + ctx["suffix"]: taps_ms}
