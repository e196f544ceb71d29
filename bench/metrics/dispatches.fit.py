"""Calls of jitted functions per fit inside the ``repro.fit`` roots, eager
operations included: JAX's outermost ``PjitFunction(<name>)`` host events,
each a dispatch to the device or a build and a dispatch (layer:
protocols)."""
from bench import program_trace


def read(ctx):
    return program_trace.per_fit(
        ctx, lambda t: t.host_count_in_roots(t.calls()))
