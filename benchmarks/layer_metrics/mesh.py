"""Layer "mesh engines": device time of collective operations per step on
chip 0, and the part of it during which no compute operation runs there."""


def read(ctx):
    tr = ctx["trace"]
    if not tr or ctx["chips"] < 2:
        return {}
    s = ctx["suffix"]
    return {
        "mesh.collective_ms_per_step." + s:
            1e3 * tr["collective_s"] / tr["n_steps"],
        "mesh.exposed_collective_ms_per_step." + s:
            1e3 * tr["exposed_collective_s"] / tr["n_steps"],
    }
