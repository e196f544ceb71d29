"""Recursive jaxpr traversal — the primitive-level plane of the contract
checker.

A compiled program's jaxpr is the ground truth of what the hot path actually
does: every factorization is a ``cholesky``/``eigh`` equation, every host
round-trip is a callback primitive, every inter-machine byte is a collective
equation.  This module walks a (closed) jaxpr INCLUDING every sub-jaxpr a
primitive carries in its params — ``pjit`` bodies, ``shard_map`` bodies,
``scan``/``while``/``cond`` carries, ``custom_jvp``/``custom_vjp`` rules — so
counts cover the whole program, not just its top level.  It is deliberately
free of any ``repro`` import: :mod:`repro.analysis.contracts` builds the
declarative rule layer on top, and :func:`repro.core.protocols.base.
predict_op_counts` is a thin wrapper over :func:`primitive_counts`.
"""
from __future__ import annotations

import collections

import jax

try:  # jax >= 0.4.16 re-exports the core IR types under jax.extend
    from jax.extend.core import ClosedJaxpr, Jaxpr
except ImportError:  # pragma: no cover - older jax spells them jax.core
    from jax.core import ClosedJaxpr, Jaxpr

__all__ = [
    "HOST_CALLBACK_PRIMITIVES",
    "COLLECTIVE_PRIMITIVES",
    "FACTORIZATION_PRIMITIVES",
    "walk_jaxpr",
    "primitive_counts",
    "collective_stats",
    "aval_bytes",
    "jaxpr_of",
]

# primitives that punch through the device boundary at run time: any of these
# inside a hot-path program is a host round-trip per dispatch (the PR-7 bug
# class: update() pulling factors to host between jitted segments)
HOST_CALLBACK_PRIMITIVES = frozenset({
    "pure_callback",
    "io_callback",
    "debug_callback",
    "callback",
    "outside_call",  # legacy host_callback spelling
    "host_local_array_to_global_array",
    "global_array_to_host_local_array",
})

# cross-device communication primitives — the §4 wire is made of exactly
# these, so counting them per program IS the collective accounting plane
COLLECTIVE_PRIMITIVES = frozenset({
    "psum",
    "psum2",  # shard_map's replication-rewrite spelling (check_rep=True)
    "psum_invariant",  # shard_map's spelling under check_vma=True
    "all_gather",
    "all_gather_invariant",
    "all_to_all",
    "ppermute",
    "pmax",
    "pmin",
    "psum_scatter",
    "reduce_scatter",
    "pbroadcast",
})

# one-shot O(n^3) decompositions — zero of these may appear in a warm serve
# program (triangular solves against cached factors are the only linalg)
FACTORIZATION_PRIMITIVES = frozenset({"cholesky", "eigh", "eig", "svd", "qr", "lu"})


def _as_jaxpr(jaxpr):
    return jaxpr.jaxpr if isinstance(jaxpr, ClosedJaxpr) else jaxpr


def _sub_jaxprs(param_value):
    """Every Jaxpr hiding in one eqn param value (covers the list-of-branches
    layout of ``cond``, the (jaxpr, consts) tuples of custom derivatives, and
    the plain ClosedJaxpr params of ``pjit``/``shard_map``/``scan``)."""
    if isinstance(param_value, ClosedJaxpr):
        yield param_value.jaxpr
    elif isinstance(param_value, Jaxpr):
        yield param_value
    elif isinstance(param_value, (list, tuple)):
        for item in param_value:
            yield from _sub_jaxprs(item)


def walk_jaxpr(jaxpr):
    """Yield every equation of ``jaxpr`` (Jaxpr or ClosedJaxpr) and of every
    sub-jaxpr reachable through equation params, depth-first."""
    jaxpr = _as_jaxpr(jaxpr)
    for eqn in jaxpr.eqns:
        yield eqn
        for pv in eqn.params.values():
            for sub in _sub_jaxprs(pv):
                yield from walk_jaxpr(sub)


def primitive_counts(jaxpr, names=None) -> collections.Counter:
    """Count primitive names over the whole (recursive) program.  ``names``:
    restrict to these (the returned counter then has an entry — possibly 0 —
    for each requested name, so budget checks never KeyError)."""
    counts = collections.Counter()
    if names is not None:
        counts.update({name: 0 for name in names})
    for eqn in walk_jaxpr(jaxpr):
        name = eqn.primitive.name
        if names is None or name in names:
            counts[name] += 1
    return counts


def aval_bytes(aval) -> int:
    """Bytes of one abstract value (0 for abstract tokens/opaque avals)."""
    size = getattr(aval, "size", None)
    dtype = getattr(aval, "dtype", None)
    if size is None or dtype is None:
        return 0
    return int(size) * int(dtype.itemsize)


def collective_stats(jaxpr) -> dict:
    """Per-collective accounting over the whole program: for each collective
    primitive present, its equation count and the summed OUTPUT payload bytes
    (what the collective materializes on every participant — the quantity the
    §4 ledger budgets).  Returns ``{name: {"count": int, "bytes": int}}``."""
    stats: dict = {}
    for eqn in walk_jaxpr(jaxpr):
        name = eqn.primitive.name
        if name not in COLLECTIVE_PRIMITIVES:
            continue
        entry = stats.setdefault(name, {"count": 0, "bytes": 0})
        entry["count"] += 1
        entry["bytes"] += sum(aval_bytes(v.aval) for v in eqn.outvars)
    return stats


def jaxpr_of(fn, *args, **kwargs) -> ClosedJaxpr:
    """``jax.make_jaxpr`` as an expression (the contract checker's program
    builder); kwargs are passed through as static."""
    return jax.make_jaxpr(fn)(*args, **kwargs)
