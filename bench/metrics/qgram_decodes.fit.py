"""Row-tile decodes of the ``qgram_packed`` kernel per fit (layer: kernels):
the sum of the ``qgram_decodes`` stats of the program's spans inside the
window's counted fits (``repro.fit.wire`` for machine 0's column of the wire
products, ``repro.fit.factors.group`` for each receiver group's).  A trace
whose spans carry no such stat (a program that does not count its decodes,
or runs no kernel) gives no value."""
from bench import program_trace

STAT = "qgram_decodes"


def read(ctx):
    pt = program_trace.of(ctx)
    if pt is None:
        return None
    roots = pt.roots()
    decodes = [st[STAT] for _, s, _, st in pt.program_spans
               if STAT in st and any(a <= s < b for a, b in roots)]
    if not decodes:
        return None
    return pt.per_fit(float(sum(decodes)), ctx.counters.get("fits"))
