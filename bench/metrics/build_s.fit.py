"""Host seconds per fit in which JAX built programs inside the ``repro.fit``
roots: the calls of jitted functions that traced and lowered a program and
compiled it or loaded it from the persistent compilation cache, with
JAX's other building events (``ProgramTrace.build_intervals``) (layer:
protocols)."""
from bench import program_trace


def read(ctx):
    return program_trace.per_fit(
        ctx, lambda t: t.host_time_in_roots(t.build_intervals()))
