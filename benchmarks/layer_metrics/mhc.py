"""Layer "kernels", the hyper-connected residual path: per traced step
(median over the steps of the window) the union of chip 0's operations of
the ``mhc_pre`` and ``mhc_post`` ops and their gradient ops, split by the
ops' inner scopes: ``maps`` (the flat norm, the product with Phi, the
sigmoids and the Sinkhorn rounds, and all of it again in the gradient) and
``mix`` (``h = H_pre . X`` and ``X' = H_res X + H_post (x) y``, the passes
over the streams); and their share of a roofline: the least time of what
the mathematics must move and multiply
(``configs/<family>/flops.py:mhc_ops_and_bytes``: the streams read once for
the maps and ``h``, read and written once for the mix, ``y`` read once),
forward once and backward twice that for every sublayer of the pattern, over
``maps_ms + mix_ms``. Recomputation is time, not counted work.

A program without the ops (an older commit, another model) has no such
operation: the reader returns nothing.
"""
import re

from benchmarks.layer_metrics import _scoped as S
from benchmarks.lib import program_spans as P

OP_TYPES = frozenset(("mhc_pre", "mhc_pre_grad", "mhc_post", "mhc_post_grad"))
PARTS = ("maps", "mix")
SCOPE = re.compile(r"/mhc_(?:pre|post)(?:_grad)?/(?:.*?[(/])?(maps|mix)[)/]")


def part_of(event_name, op_name):
    """"maps", "mix" or None for an operation: the inner scope its
    ``op_name`` carries, or ``mix`` where an operation of these ops carries
    none (a cotangent cast or summed outside the scopes)."""
    if S.op_type_of(op_name) not in OP_TYPES:
        return None
    found = SCOPE.search(op_name)
    return found.group(1) if found else "mix"


def read(ctx):
    loaded = S.load()
    if loaded is None:
        return {}
    path, steps, events, op_names = loaded
    ns = {part: S.per_step_ns(events, op_names, steps,
                              lambda e, o, part=part: part_of(e, o) == part)
          for part in PARTS}
    if not any(ns["mix"]):
        return {}
    s = ctx["suffix"]
    maps_ms, mix_ms = (P.median_ms(ns[part]) for part in PARTS)
    out = {"mhc.maps_ms." + s: maps_ms, "mhc.mix_ms." + s: mix_ms}
    cfg, traffic, flops = S.cell_of(path) if ctx.get("peaks") else (None,) * 3
    if hasattr(flops, "mhc_ops_and_bytes"):
        sublayers = len(cfg["hybrid_override_pattern"])
        ops, moved = flops.mhc_ops_and_bytes(
            cfg, traffic["batch"] * traffic["seq_len"])
        ops, moved = 3 * sublayers * ops, 3 * sublayers * moved
        share, bound = S.roofline_pct(ops, moved, ctx["peaks"],
                                      (maps_ms + mix_ms) / 1e3)
        out["mhc.roofline_pct." + s] = share
        print("# mhc: the residual path of a step, %d sublayers forward and "
              "backward: %.3f GFLOP, %.3f GB, bound by %s"
              % (sublayers, ops / 1e9, moved / 1e9, bound), flush=True)
    print("# mhc: read %s: maps %.4f ms, mix %.4f ms a step (median of %d "
          "steps)" % (path, maps_ms, mix_ms, len(ns["mix"])), flush=True)
    return out
