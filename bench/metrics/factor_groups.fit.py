"""Receiver groups per fit of the broadcast factor build: the mean
``groups`` stat of the ``repro.fit.factors`` spans of the window's counted
fits (layer: protocols).  A trace whose factor spans carry no such stat (a
program that builds every receiver at once) gives no value."""
from bench import program_trace

SPAN = program_trace.ROOT_SPAN + ".factors"


def read(ctx):
    pt = program_trace.of(ctx)
    if pt is None:
        return None
    roots = pt.roots()
    groups = [st["groups"] for n, s, _, st in pt.program_spans
              if n == SPAN and "groups" in st
              and any(a <= s < b for a, b in roots)]
    if not groups:
        return None
    return pt.per_fit(float(sum(groups)), ctx.counters.get("fits"))
