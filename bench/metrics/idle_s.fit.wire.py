"""Device idle seconds per fit inside the program's ``repro.fit.wire`` spans:
padding, the scheme's fit, encode and decode, the squared norms and the
inner products built from the wire (layer: protocols)."""
from bench import program_trace


def read(ctx):
    return program_trace.per_fit(ctx, lambda t: t.idle_in_spans("fit.wire"))
