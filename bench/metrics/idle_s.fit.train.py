"""Device idle seconds per fit inside the program's ``repro.fit.train``
spans: the assembly of machine 0's training inputs and the training scan,
its trace and its program load included (layer: protocols)."""
from bench import program_trace


def read(ctx):
    return program_trace.per_fit(ctx, lambda t: t.idle_in_spans("fit.train"))
