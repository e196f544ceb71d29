"""Share of the traced window, in percent, in which no operation ran on the
device: 1 - (union of device op intervals) / window, averaged over the
chips (layer: device)."""


def read(ctx):
    w = ctx.trace.window_s()
    if w <= 0:
        return None
    return 100.0 * (1.0 - ctx.trace.busy_s() / w)
