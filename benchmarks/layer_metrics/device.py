"""Layer "device": the share of the traced window in which no operation ran
on chip 0, and the peak of device memory on the fullest chip."""


def read(ctx):
    s = ctx["suffix"]
    out = {}
    if ctx["peaks"] is not None:
        out["device.peak_hbm_gib." + s] = ctx["memory_peak_bytes"] / 2 ** 30
    tr = ctx["trace"]
    if tr:
        out["device.idle_share_pct." + s] = \
            100.0 * (1.0 - tr["busy_s_chip0"] / tr["window_s"])
    return out
