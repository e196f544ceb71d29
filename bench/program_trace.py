"""The program's own spans and JAX's host events in a traced run, on the clock
of :mod:`bench.trace`.

The program opens a root span ``repro.fit`` around each fit (its stat
``fit`` numbers the fits of the process) and, inside it, one span per phase:
``repro.fit.wire``, ``repro.fit.train``, ``repro.fit.factors``
(``src/repro/spans.py``).  JAX writes its own events to the same host plane
``/host:CPU``:

* ``PjitFunction(<name>)``: one call of a jitted function (eager
  operations too); a call made while another is traced, and the second
  event the profiler writes for every call, lie inside the calling one;
* program building: ``trace_to_jaxpr_dynamic`` (tracing),
  ``lower_sharding_computation`` (lowering), ``backend_compile_and_load``
  (a compile) and, on a TPU, ``LoadProgramMaybeFromCache`` (the runtime
  putting a program on the chip).

With jax 0.9 on a TPU v5e, neither the tracing of a jitted function
(``pe.trace_to_jaxpr``) nor a load from the persistent compilation cache
writes an event of its own: in a traced refit they are unlabelled time
inside the ``PjitFunction`` call, before and after its
``lower_sharding_computation``.  So a call that lowered a program counts
whole as building (:meth:`ProgramTrace.build_intervals`).

:func:`load` reduces a trace file to a :class:`ProgramTrace`; :func:`of`
gives the one of a metric reader's run, read from the trace ``run.py``
wrote.  Only the fits whose root lies wholly inside the window count; every
per-fit number divides by their number, which must equal the window's
``fits`` counter.  A trace with no root span (a program without spans)
gives no value.  Everything is in nanoseconds internally and returned in
seconds.
"""
from __future__ import annotations

import bisect
import dataclasses
import os
import re

from bench import trace

PREFIX = "repro."
ROOT_SPAN = "repro.fit"
DISPATCH = re.compile(r"PjitFunction\(")
LOWER = "lower_sharding_computation"
BUILD = re.compile(r"^(trace_to_jaxpr_dynamic|lower_sharding_computation|"
                   r"backend_compile_and_load|LoadProgramMaybeFromCache)$")


def _kept(name: str) -> bool:
    return (name.startswith(PREFIX) or DISPATCH.match(name) is not None
            or BUILD.match(name) is not None)


@dataclasses.dataclass
class ProgramTrace(trace.Trace):
    """A :class:`bench.trace.Trace` with the program's spans and JAX's host
    events of the same run."""

    program_spans: list = dataclasses.field(default_factory=list)
    # [(name, start, end, stats)]: every ``repro.*`` span
    host_events: list = dataclasses.field(default_factory=list)
    # [(name, start, end)]: JAX's dispatch and program-building events

    def roots(self):
        """The root spans lying wholly inside the window, sorted."""
        return sorted((s, e) for n, s, e, _ in self.program_spans
                      if n == ROOT_SPAN and s >= self.t0 and e <= self.t1)

    def _inside_roots(self, intervals):
        """Merged intervals: the parts of ``intervals`` inside the roots."""
        roots = self.roots()
        return trace._union([(max(s, a), min(e, b)) for s, e in intervals
                             for a, b in roots if s < b and e > a])

    def _idle(self, merged) -> float:
        """Seconds of ``merged`` in which no operation ran, averaged over
        the chips."""
        n = len(self.ops)
        return sum(trace._length(merged) - trace._intersect(self._busy(c), merged)
                   for c in range(n)) * 1e-9 / n

    def span_union(self, name: str):
        """Merged intervals of the program spans ``repro.<name>`` inside the
        counted roots."""
        return self._inside_roots(
            [(s, e) for n, s, e, _ in self.program_spans if n == PREFIX + name])

    def idle_in_spans(self, name: str) -> float:
        """Device idle seconds inside the spans ``repro.<name>``."""
        return self._idle(self.span_union(name))

    def root_self_idle(self) -> float:
        """Device idle seconds inside the roots and outside every span
        nested in them: the split and the glue between phases."""
        children = self._inside_roots(
            [(s, e) for n, s, e, _ in self.program_spans
             if n.startswith(ROOT_SPAN + ".")])
        return self._idle(trace._subtract(trace._union(self.roots()), children))

    def calls(self):
        """(start, end) of the outermost ``PjitFunction`` events: the calls
        the program's Python made, each dispatched or built and
        dispatched."""
        out = []
        for n, s, e in sorted(self.host_events, key=lambda h: (h[1], -h[2])):
            if DISPATCH.match(n) and not (out and s < out[-1][1]):
                out.append((s, e))
        return out

    def build_intervals(self):
        """Merged intervals in which JAX built programs: every call that
        lowered a program (its tracing, lowering, compile or cache load),
        and every building event outside such calls."""
        lowers = sorted(s for n, s, _ in self.host_events if n == LOWER)
        built = [(s, e) for s, e in self.calls()
                 if bisect.bisect_left(lowers, s) < bisect.bisect_left(lowers, e)]
        return trace._union(built + [(s, e) for n, s, e in self.host_events
                                     if BUILD.match(n)])

    def host_time_in_roots(self, intervals) -> float:
        """Seconds of the union of host ``intervals`` inside the roots."""
        return trace._length(self._inside_roots(intervals)) * 1e-9

    def host_count_in_roots(self, intervals) -> int:
        """Number of host ``intervals`` that start inside a root."""
        roots = self.roots()
        return sum(1 for s, _ in intervals if any(a <= s < b for a, b in roots))

    def per_fit(self, value: float, fits):
        """``value`` over the number of roots; None when there is no root
        or their number is not the window's ``fits``."""
        n = len(self.roots())
        if n == 0 or (fits is not None and n != fits):
            return None
        return value / n


def read_host(path: str) -> tuple:
    """(program spans, JAX host events) of the ``/host:CPU`` plane of the
    trace at ``path`` (a ``.xplane.pb`` or the directory the profiler
    wrote)."""
    from jax.profiler import ProfileData

    if os.path.isdir(path):
        path = trace.find_xplane(path)
    spans, events = [], []
    for plane in ProfileData.from_file(path).planes:
        if plane.name != "/host:CPU":
            continue
        for ln in plane.lines:
            for e in ln.events:
                if not _kept(e.name):
                    continue
                end = e.start_ns + e.duration_ns
                if e.name.startswith(PREFIX):
                    spans.append((e.name, e.start_ns, end, dict(e.stats)))
                else:
                    events.append((e.name, e.start_ns, end))
    return spans, events


def extend(tr: trace.Trace, path: str) -> ProgramTrace:
    """``tr`` with the program spans and host events of the trace it was
    reduced from."""
    spans, events = read_host(path)
    base = {f.name: getattr(tr, f.name) for f in dataclasses.fields(trace.Trace)}
    return ProgramTrace(**base, program_spans=spans, host_events=events)


def load(path: str, chips: int = 1) -> ProgramTrace:
    return extend(trace.load(path, chips), path)


# (the Trace the readers of one run are given, its ProgramTrace): a traced
# window's trace is read once for the six readers, not once each
_LAST = [None, None]


def of(ctx):
    """The :class:`ProgramTrace` of a metric reader's run (read once per
    run), or None when the run's trace cannot be found."""
    from bench.run import TRACE_DIR

    if isinstance(ctx.trace, ProgramTrace):
        return ctx.trace
    if _LAST[0] is not ctx.trace:
        try:
            _LAST[:] = [ctx.trace, extend(ctx.trace, TRACE_DIR)]
        except FileNotFoundError:
            return None
    return _LAST[1]


def per_fit(ctx, value):
    """A reader's per-fit value: ``value(program_trace)`` over the fits, or
    None when the run has no program spans to read."""
    pt = of(ctx)
    return None if pt is None else pt.per_fit(value(pt), ctx.counters.get("fits"))
