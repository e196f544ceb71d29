"""Device idle seconds per fit inside the program's ``repro.fit.factors``
spans: the factor builds and the building of the fitted artifact (layer:
protocols)."""
from bench import program_trace


def read(ctx):
    return program_trace.per_fit(ctx, lambda t: t.idle_in_spans("fit.factors"))
