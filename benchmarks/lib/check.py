"""The comparison that decides ``correct``.

The program's first steps are held to the plain reference's on these
numbers, each with a limit of its own; a cell compares those that its traffic
file gives a limit (set from readings on the chip, see PERF.md):

- ``loss``: the widest relative gap of a step's loss;
- ``grad_norm``: the first gradient as the optimizer gets it, by the worst
  leaf: the gap between the program's norm and the reference's, over the
  reference's norm of that leaf or of the median leaf, whichever is larger;
- ``delta_norm``: the same for the parameters' change over the steps. Leaves
  whose reference gradient is under ``NOISE_SHARE`` of the median leaf's are
  left out of this one: their gradient is zero in exact arithmetic (a key bias
  under softmax), and Adam scales the rounding noise in its place to a full
  step in either direction;
- ``grad_norm_median``, ``delta_norm_median``: the same gaps by the median
  leaf, for a model whose worst leaf swings from seed to seed (batch-norm
  scales whose gradients are sums that cancel): a learning rate or a batch
  that is wrong moves every leaf, the median one too.
"""
from __future__ import annotations

import math
import statistics

NOISE_SHARE = 1e-4


def leaf_gaps(program, reference):
    """{leaf: gap}; a leaf the program lacks, or a non-finite norm, is an
    infinite gap."""
    floor = statistics.median(reference.values())
    out = {}
    for leaf, ref in reference.items():
        got = program.get(leaf)
        if got is None or not math.isfinite(got):
            out[leaf] = math.inf
        else:
            out[leaf] = abs(got - ref) / max(ref, floor, 1e-30)
    return out


def worst_leaf_gap(program, reference):
    """(gap, leaf) of the worst leaf."""
    gaps = leaf_gaps(program, reference)
    leaf = max(gaps, key=gaps.get)
    return gaps[leaf], leaf


def loss_gap(program, reference):
    gaps = [abs(a - b) / max(abs(b), 1e-30) if math.isfinite(a) else math.inf
            for a, b in zip(program, reference)]
    return max(gaps)


def compare(program, reference, limits):
    """``program`` and ``reference`` hold ``losses``, ``grad_norms`` and
    ``delta_norms``. Returns [(name, value, limit, ok, note)], one row for
    each name in ``limits``."""
    n = min(len(program["losses"]), len(reference["losses"]))
    noise = NOISE_SHARE * statistics.median(reference["grad_norms"].values())
    moved = {k: v for k, v in reference["delta_norms"].items()
             if reference["grad_norms"][k] > noise}
    g = leaf_gaps(program["grad_norms"], reference["grad_norms"])
    d = leaf_gaps(program["delta_norms"], moved)
    numbers = {
        "loss": (loss_gap(program["losses"][:n], reference["losses"][:n]),
                 "%d steps" % n),
        "grad_norm": (max(g.values()), "worst leaf %s" % max(g, key=g.get)),
        "delta_norm": (max(d.values()), "worst leaf %s" % max(d, key=d.get)),
        "grad_norm_median": (statistics.median(g.values()), "median leaf"),
        "delta_norm_median": (statistics.median(d.values()), "median leaf"),
    }
    return [(name, numbers[name][0], limit, numbers[name][0] <= limit,
             numbers[name][1]) for name, limit in limits.items()]
