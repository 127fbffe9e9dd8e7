"""Layer "kernels", learned sparse attention: per traced step (median over
the steps of the window) the union of chip 0's operations of the indexer's
ops (``attn_index_project``, ``attn_index_select``, ``attn_index_loss`` and
their gradient ops), split by the ops' inner scopes: ``score`` (the indexer's
projections and the index scores, wherever they are made again), ``select``
(the threshold search and the selection written) and ``loss`` (the
attention's probabilities made again, the KL and its gradient to the
scores); and two shares of a roofline:

- ``index_roofline_pct``: the least time of the index scores, ONCE over the
  causal pairs for each ``S`` layer
  (``configs/<family>/flops.py:index_ops_and_bytes``), over ``index_ms``. The
  scores are made several times a step (selection, loss, gradient, and again
  under recomputation): that is time, not counted work.
- ``attend_roofline_pct``: the least time of the selected attention
  (``attend_ops_and_bytes``: scores and context over the SELECTED pairs,
  forward once and backward twice that, for each ``S`` layer) over what
  ``attention.kernels_ms`` reads. It counts what the mathematics needs, so a
  kernel that masks every causal block reads low and one that skips
  unselected blocks reads higher, with no change to the count.

A program without the ops (an older commit, another model) has no such
operation: the reader returns nothing.
"""
import re

from benchmarks.layer_metrics import _scoped as S
from benchmarks.layer_metrics import attention
from benchmarks.lib import program_spans as P

OPS = {"attn_index_project": "score", "attn_index_select": "select",
       "attn_index_loss": "loss"}
PARTS = ("score", "select", "loss")
SCOPE = re.compile(r"/attn_index_\w+?/(?:.*?[(/])?(score|select|loss)[)/]")
METRIC = {"score": "index_ms", "select": "select_ms", "loss": "index_loss_ms"}


def part_of(event_name, op_name):
    """"score", "select", "loss" or None for an operation: the inner scope
    its ``op_name`` carries (the scopes are siblings, never nested), or its
    op's own part where it carries none (an operand stacked or padded
    outside the scopes)."""
    op_type = S.op_type_of(op_name)
    op = op_type[:-len("_grad")] if op_type.endswith("_grad") else op_type
    if op not in OPS:
        return None
    found = SCOPE.search(op_name)
    return found.group(1) if found else OPS[op]


def read(ctx):
    loaded = S.load()
    if loaded is None:
        return {}
    path, steps, events, op_names = loaded
    ns = {part: S.per_step_ns(events, op_names, steps,
                              lambda e, o, part=part: part_of(e, o) == part)
          for part in PARTS}
    if not any(ns["score"]):
        return {}
    s = ctx["suffix"]
    out = {"sparse_attn.%s.%s" % (METRIC[part], s): P.median_ms(ns[part])
           for part in PARTS}
    index_ms = out["sparse_attn.index_ms." + s]
    roof = S.step_roofline(ctx, path, "S", "index_ops_and_bytes", index_ms)
    if roof:
        # the scores are counted once a layer, not three times: they take no
        # gradient of their own in the model's count (flops.py)
        out["sparse_attn.index_roofline_pct." + s] = roof[0] / 3.0
        print("# sparse_attn: the index scores of a step, once a layer: "
              "%.3f GFLOP, %.3f GB, bound by %s"
              % (roof[2] / 3.0, roof[3] / 3.0, roof[1]), flush=True)
    attend_ms = P.median_ms(attention.per_step_ns(events, op_names, steps))
    roof = attend_ms and S.step_roofline(ctx, path, "S",
                                         "attend_ops_and_bytes", attend_ms)
    if roof:
        out["sparse_attn.attend_roofline_pct." + s] = roof[0]
        print("# sparse_attn: the selected attention of a step, forward and "
              "backward: %.3f GFLOP, %.3f GB, bound by %s, over %.4f ms of "
              "attention's kernels" % (roof[2], roof[3], roof[1], attend_ms),
              flush=True)
    print("# sparse_attn: read %s: index scores %.4f ms, selection %.4f ms, "
          "indexer loss %.4f ms a step (median of %d steps)"
          % (path, index_ms, out["sparse_attn.select_ms." + s],
             out["sparse_attn.index_loss_ms." + s], len(ns["score"])),
          flush=True)
    return out
