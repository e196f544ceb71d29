"""Named host spans in the JAX profiler's trace, on the device's clock.

``span(name)`` is a ``jax.profiler.TraceAnnotation`` named ``repro.<name>``;
``fit_span()`` opens the root ``repro.fit`` around one fit and gives it the
next value of a process-wide ordinal.  Every span opened inside a root
carries the root's ``fit=<n>`` stat, so the spans of one fit share an id; a
span's parent is the span that encloses it on the same thread.

Spans are always on.  With no profiler running one costs about a
microsecond; with one running, the profiler's own buffer holds them until
``jax.profiler.stop_trace`` writes them out.  Nothing is kept in Python.
"""
from __future__ import annotations

import contextlib
import contextvars
import itertools

import jax

__all__ = ["PREFIX", "span", "fit_span"]

PREFIX = "repro."

_FIT = contextvars.ContextVar("repro_fit", default=None)
_ORDINAL = itertools.count(1)


def span(name: str, **meta):
    """The span ``repro.<name>``, with ``meta`` and the enclosing fit's id
    as its stats."""
    fit = _FIT.get()
    if fit is not None:
        meta.setdefault("fit", fit)
    return jax.profiler.TraceAnnotation(PREFIX + name, **meta)


@contextlib.contextmanager
def fit_span():
    """The root span ``repro.fit`` of one fit, with the next fit id."""
    token = _FIT.set(next(_ORDINAL))
    try:
        with span("fit"):
            yield
    finally:
        _FIT.reset(token)
