"""Device busy seconds per refit: the union of device operations inside the
harness's ``bench.refit`` spans, over the fits of the window (layer:
protocols)."""


def read(ctx):
    fits = ctx.counters.get("fits")
    t = ctx.trace.device_time_in_spans("refit")
    return t / fits if fits and t > 0 else None
