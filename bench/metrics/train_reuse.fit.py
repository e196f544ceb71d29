"""Share of the window's fits, in percent, that reused a compiled training
program: the fits whose ``repro.fit.train.program`` markers, opened after
each call of the training program, all carry ``built=0`` (layer:
protocols).  A trace with no such marker in the counted fits (a program
without it) gives no value."""
from bench import program_trace

MARKER = program_trace.ROOT_SPAN + ".train.program"


def read(ctx):
    pt = program_trace.of(ctx)
    if pt is None:
        return None
    roots = pt.roots()
    built = [[st.get("built") for n, s, _, st in pt.program_spans
              if n == MARKER and a <= s < b] for a, b in roots]
    if not any(built):
        return None
    reused = sum(1 for b in built if b and all(x == 0 for x in b))
    return pt.per_fit(100.0 * reused, ctx.counters.get("fits"))
