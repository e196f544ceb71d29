"""Device idle seconds per fit inside the program's ``repro.fit`` roots and
outside the phase spans nested in them: the split across machines and the
glue between phases (layer: protocols)."""
from bench import program_trace


def read(ctx):
    return program_trace.per_fit(ctx, lambda t: t.root_self_idle())
