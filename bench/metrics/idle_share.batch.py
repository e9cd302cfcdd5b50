"""Share of the traced window (whole jobs) in which no operation ran on
the device, in percent: 100 · (1 − busy / window)."""


def read(ctx):
    if not ctx.get("window_s") or not ctx.get("jobs"):
        return None
    return 100.0 * (1.0 - ctx["busy_s"] / ctx["window_s"])
