"""What the readers of one op type's kernels share: the cell of the newest
trace, its configuration's ``flops`` module, and the union per traced step of
chip 0's operations that a predicate picks.

The harness hands a reader no configuration (``ctx`` has none), so a reader
finds its cell from the trace directory's name
(``.bench_trace/<cell>/plugins/...``) and reads the manifest itself.
"""
import importlib
import os

from benchmarks.lib import harness
from benchmarks.lib import program_spans as P
from benchmarks.lib import trace as T
from benchmarks.lib.manifest import Manifest

from .attention import chip0_events


def cell_of(path):
    """(cfg, traffic, flops module) of the cell whose trace ``path`` is."""
    parts = os.path.normpath(path).split(os.sep)
    name = parts[parts.index(".bench_trace") + 1]
    manifest = Manifest(harness.MANIFEST, harness.REPO)
    cell = manifest.cell(name)
    cfg, family = manifest.config(cell)
    flops = importlib.import_module("benchmarks.configs.%s.flops" % family)
    return cfg, manifest.traffic(cell), flops


def op_type_of(op_name):
    return P.scope_of(op_name).partition("/")[2]


def per_step_ns(events, op_names, steps, mine):
    """Nanoseconds a step tile of the union of the operations for which
    ``mine(event name, op_name)`` holds."""
    picked = T.merge((s, e) for name, s, e in events
                     if mine(name, op_names.get(name, "")))
    return P.per_tile(picked, P.step_tiles(steps))


def load():
    """(path, steps, chip 0's events, {event name: op_name}) of the newest
    trace, or None where there is nothing to read."""
    path = P.newest_xplane()
    if path is None:
        return None
    steps, events = P.load(path).steps, chip0_events(path)
    if not steps or not events:
        return None
    return path, steps, events, P.op_names(path)


def roofline_pct(ops, moved, peaks, seconds):
    """The least time the chip could take (the larger of operations over the
    peak and bytes over the bandwidth) as a share of ``seconds``, and which
    of the two bounds it."""
    by_flops = ops / peaks["bf16_flops_per_s"]
    by_bytes = moved / peaks["hbm_bytes_per_s"]
    return (100.0 * max(by_flops, by_bytes) / seconds,
            "compute" if by_flops >= by_bytes else "memory")


def step_roofline(ctx, path, letter, counter, ms):
    """A training step's roofline share for the layers of kind ``letter`` of
    the traced cell: ``flops.<counter>(cfg, tokens)`` gives one forward's
    operations and bytes, a step is that three times (the backward is twice
    the forward; recomputation is time, not counted work) for each such
    layer. Returns (share in %, "compute" | "memory", GFLOP, GB), or None
    without peaks or where the configuration has no such function."""
    cfg, traffic, flops = cell_of(path)
    if not ctx.get("peaks") or not hasattr(flops, counter):
        return None
    layers = cfg["hybrid_override_pattern"].count(letter)
    ops, moved = getattr(flops, counter)(
        cfg, traffic["batch"] * traffic["seq_len"])
    ops, moved = 3 * layers * ops, 3 * layers * moved
    share, bound = roofline_pct(ops, moved, ctx["peaks"], ms / 1e3)
    return share, bound, ops / 1e9, moved / 1e9
