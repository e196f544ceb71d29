"""Roofline share of the broadcast factor build (layer: kernels): the least
time of the fits' build work, from the shapes (``factor_build.work``, never
from HLO), over the device time of the build program's operations, less its
``qgram_packed`` kernel operations (``bench/factor_build.py``).  No build
program in the trace: no value, never 0."""
from bench import factor_build


def read(ctx):
    fits = ctx.counters.get("fits")
    t = factor_build.device_s(ctx.trace)
    if not fits or t <= 0:
        return None
    flops, nbytes = factor_build.work(ctx.cfg)
    return 100.0 * ctx.least_time(fits * flops, fits * nbytes)[0] / t
