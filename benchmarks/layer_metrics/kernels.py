"""Layer "kernels" (XLA fusions today): utilization while busy, which is the
configuration's model FLOPs of the traced steps over the device-busy seconds
and the chips' bf16 peak, so that host gaps do not dilute it."""


def read(ctx):
    tr = ctx["trace"]
    if not tr:
        return {}
    peak = ctx["chips"] * ctx["peaks"]["bf16_flops_per_s"]
    flops = ctx["flops_per_step"] * tr["n_steps"]
    return {"kernels.busy_mfu_pct." + ctx["suffix"]:
            100.0 * flops / tr["busy_s"] / peak}
