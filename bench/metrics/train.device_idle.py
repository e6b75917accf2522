"""Device: share of the traced window in which no operation ran, in %."""


def read(ctx):
    d = ctx.device
    if d is None or d["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - d["busy_s"] / d["window_s"])
