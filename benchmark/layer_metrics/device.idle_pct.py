"""Device: the share of the traced slice in which no operation ran on the
chip."""


def read(ctx):
    t = ctx.trace
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"]) if t["window_s"] else None
