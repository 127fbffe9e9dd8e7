"""Layer "executor", set-up: the seconds the program's own steps took to lower
(jaxpr -> MLIR module, every Pallas body's lowering to Mosaic inside it), by
the program's counters ``executor.lower_s`` (one chip) and
``parallel.lower_s`` (the mesh engine), whichever the cell's path counts. The
program counts a lowering only on a thread that is inside one of its spans, so
the plain reference's programs, which lower after the window and before this
is read, are not in it. Set-up only: nothing lowers in the window.

A program without these counters (an older commit) reads nothing.
"""
COUNTERS = ("executor.lower_s", "parallel.lower_s")


def read(ctx):
    from paddle_tpu import observability as obs

    counters = obs.dump()["counters"]
    found = [counters[name] for name in COUNTERS if name in counters]
    return {"startup.lower_s": sum(found)} if found else {}
