"""Layer "kernels", the routed experts: per traced step (median over the
steps of the window) the union of chip 0's operations of the ``moe_topk`` op
and its gradient op, split by the op's inner scopes: ``route`` (the router's
product, sigmoid, top-k, weights) and the rest, ``experts`` (sorting the
slots, gather, the two grouped products, scatter, and the exact branch if it
ran). On the TPU the grouped products are megablox kernel calls, which
carry the op's scope; where they are the TPU compiler's own ``ragged-dot``
instructions they count as experts whatever name their metadata carries. The
experts' share of their roofline is by
``configs/<family>/flops.py:experts_ops_and_bytes`` at the EXPECTED load,
forward once and backward twice the forward for each ``E`` layer (the
auto-VJP gradient op's second forward is time, not counted work).

A program without the op has no such operation: the reader returns nothing.
"""
import re

from benchmarks.layer_metrics import _scoped as S
from benchmarks.lib import program_spans as P

OP_TYPES = frozenset(("moe_topk", "moe_topk_grad"))
ROUTE = re.compile(r"/moe_topk(_grad)?/(.*[(/])?route[)/]")
RAGGED = "ragged-dot"


def part_of(event_name, op_name):
    """"route", "experts" or None for an operation."""
    if S.op_type_of(op_name) in OP_TYPES:
        return "route" if ROUTE.search(op_name) else "experts"
    if RAGGED in event_name.split("=", 1)[0]:
        return "experts"
    return None


def read(ctx):
    loaded = S.load()
    if loaded is None:
        return {}
    path, steps, events, op_names = loaded
    ns = {part: S.per_step_ns(events, op_names, steps,
                              lambda e, o, part=part: part_of(e, o) == part)
          for part in ("route", "experts")}
    if not any(ns["experts"]):
        return {}
    s = ctx["suffix"]
    experts_ms = P.median_ms(ns["experts"])
    out = {"moe.experts_ms." + s: experts_ms,
           "moe.route_ms." + s: P.median_ms(ns["route"])}
    roof = S.step_roofline(ctx, path, "E", "experts_ops_and_bytes",
                           experts_ms)
    if roof:
        out["moe.experts_roofline_pct." + s] = roof[0]
        print("# moe: the expert layers of a step, forward and backward, at "
              "the expected load: %.3f GFLOP, %.3f GB, bound by %s"
              % (roof[2], roof[3], roof[1]), flush=True)
    ragged = sorted({op_names.get(e, "") for e, _, _ in events
                     if RAGGED in e.split("=", 1)[0]})
    print("# moe: read %s: experts %.4f ms, route %.4f ms a step (median of "
          "%d steps); op_name of the ragged-dot instructions: %s"
          % (path, experts_ms, out["moe.route_ms." + s], len(ns["experts"]),
             ragged or "none in the trace"), flush=True)
    return out
