"""Layer "kernels", attention's core alone: per traced step (median over the
steps of the window) the union of chip 0's operations that compute the scores,
the softmax, the context and their gradients. An operation belongs to it when
the op type of its scope (``<role>/<op_type>``, see ``lib/program_spans.py``)
is one of ``OP_TYPES``, or when it is a Mosaic call (``tpu_custom_call``) that
carries no ``op_name`` at all: XLA's own rewrite of a dense attention forward
is such a call. In BERT ``matmul`` is attention only (the FC layers are
``mul``), so a program with dense attention and one with the
``flash_attention`` op read their own attention under the one name.

The program counts which kernels each traced ``flash_attention`` op took
(``kernels.flash_attention{path=short|stream|dense}``); a traced run prints
the three on a ``# attention:`` line. A program without the counter (an older
commit) prints no such line and still reads the time.
"""
from benchmarks.lib import program_spans as P
from benchmarks.lib import trace as T

OP_TYPES = frozenset(("matmul", "matmul_grad", "softmax", "softmax_grad",
                      "flash_attention", "flash_attention_grad"))
COUNTER = "kernels.flash_attention{path=%s}"
PATHS = ("short", "stream", "dense")


def is_attention(event_name, op_name):
    """``event_name`` is the text of the HLO instruction, ``op_name`` what
    its metadata carries ("" where it carries none)."""
    if not op_name:
        return "tpu_custom_call" in event_name
    return P.scope_of(op_name).partition("/")[2] in OP_TYPES


def chip0_events(path):
    """[(event name, start, end)] of chip 0's operations."""
    from jax.profiler import ProfileData

    chip0 = None
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/device:TPU:"):
            chip = int(plane.name.rsplit(":", 1)[1].split()[0])
            if chip0 is None or chip < chip0[0]:
                chip0 = (chip, plane)
    if chip0 is None:
        return []
    return [(ev.name, int(ev.start_ns), int(ev.start_ns + ev.duration_ns))
            for line in chip0[1].lines if line.name == T.OPS_LINE
            for ev in line.events]


def per_step_ns(events, op_names, steps):
    """Nanoseconds of the union of the attention operations in each step
    tile; ``events`` as ``chip0_events`` gives them, ``op_names`` {event
    name: op_name}, ``steps`` the sorted step spans."""
    mine = T.merge((s, e) for name, s, e in events
                   if is_attention(name, op_names.get(name, "")))
    return P.per_tile(mine, P.step_tiles(steps))


def read(ctx):
    from paddle_tpu import observability as obs

    counters = obs.dump()["counters"]
    taken = {p: counters[COUNTER % p] for p in PATHS
             if COUNTER % p in counters}
    if taken:
        print("# attention: flash_attention ops traced, by the kernels they "
              "took: %s" % ", ".join("%s %d" % (p, taken.get(p, 0))
                                     for p in PATHS), flush=True)
    path = P.newest_xplane()
    if path is None:
        return {}
    steps = P.load(path).steps
    events = chip0_events(path)
    if not steps or not events:
        return {}
    ns = per_step_ns(events, P.op_names(path), steps)
    value = P.median_ms(ns)
    print("# attention: read %s: %.4f ms a step (median of %d steps) in "
          "operations of %s or Mosaic calls without op_name"
          % (path, value, len(ns), "/".join(sorted(OP_TYPES))), flush=True)
    return {"attention.kernels_ms." + ctx["suffix"]: value}
