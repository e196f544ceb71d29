"""Device seconds per fit of the broadcast factor build: the operations of
its program, found by its jitted name, less the ``qgram_packed`` kernel's
inside it (``bench/factor_build.py``; layer: protocols).  No build program
in the trace: no value."""
from bench import factor_build


def read(ctx):
    fits = ctx.counters.get("fits")
    t = factor_build.device_s(ctx.trace)
    return t / fits if fits and t > 0 else None
