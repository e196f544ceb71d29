"""Pieces every part of the benchmark shares: finding the program, refusing to
run without a TPU, counting compilations, the program census and the host
spans around each call.

``require_tpu``, ``CacheCounter`` and ``program_census`` are copies of the
sound checks of the repository's ``chip_smoke.py``; the benchmark keeps its
own so that a change to the program cannot change them.
"""
from __future__ import annotations

import contextlib
import os
import sys

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)


class Refused(SystemExit):
    """A run that must print no result: exit code 2 with a message."""

    def __init__(self, msg: str):
        print(f"bench: {msg}", file=sys.stderr)
        super().__init__(2)


def import_repro():
    """The ``repro`` package of THIS checkout (``src/`` beside ``bench/``),
    never an installed copy, so the benchmark alone in a directory fails."""
    src = os.path.join(ROOT, "src")
    if not os.path.isfile(os.path.join(src, "repro", "__init__.py")):
        raise Refused(f"no repro package at {src}: run from a checkout of the "
                      "repository")
    sys.path.insert(0, src)
    import repro

    if not os.path.abspath(repro.__file__).startswith(src + os.sep):
        raise Refused(f"imported repro from {repro.__file__}, not from {src}")
    return repro


def require_tpu(count: int):
    import jax

    try:
        devs = jax.devices()
    except RuntimeError as e:
        raise Refused(f"no TPU found: JAX could not initialize a backend ({e})")
    if devs[0].platform != "tpu":
        raise Refused(f"no TPU found: JAX sees {len(devs)} {devs[0].platform} "
                      "device(s); the benchmark does not fall back to the CPU")
    if len(devs) < count:
        raise Refused(f"the cell needs {count} TPU chips, JAX sees {len(devs)}")
    return devs


class CacheCounter:
    """Counts JAX persistent-compilation-cache hits and misses, and backend
    compilations, from JAX's monitoring events."""

    def __init__(self):
        import jax

        self.hits = self.misses = self.compiles = 0
        jax.monitoring.register_event_listener(self._on_event)
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)

    def _on_event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def _on_duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1

    def snapshot(self) -> tuple:
        return self.hits, self.misses, self.compiles


def program_census(fn, *args) -> dict:
    """What a program really runs, from its jaxpr: the matmul precisions of
    every dot_general (Pallas kernel bodies included) and the interpret flag
    of every Pallas call."""
    import jax

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            yield eqn
            for v in eqn.params.values():
                for sub in v if isinstance(v, (list, tuple)) else (v,):
                    inner = getattr(sub, "jaxpr", sub)
                    if hasattr(inner, "eqns"):
                        yield from walk(inner)

    prec, pallas = {}, []
    for eqn in walk(jax.make_jaxpr(fn)(*args).jaxpr):
        name = eqn.primitive.name
        if name == "dot_general":
            p = eqn.params.get("precision")
            key = "DEFAULT" if p is None else "/".join(
                sorted({str(x).split(".")[-1] for x in p}))
            prec[key] = prec.get(key, 0) + 1
        elif name == "pallas_call":
            pallas.append(bool(eqn.params.get("interpret")))
    return {"dot_precision": prec, "pallas_calls": len(pallas),
            "pallas_interpret": sorted(set(pallas))}


def block(tree):
    import jax

    jax.block_until_ready(jax.tree_util.tree_leaves(tree))
    return tree


class Spans:
    """Host spans around each call into the program.  With tracing on they
    are ``jax.profiler.TraceAnnotation`` ranges named ``bench.<kind>`` in the
    profiler's own trace; off, they cost nothing."""

    PREFIX = "bench."

    def __init__(self, on: bool):
        self.on = on

    def __call__(self, kind: str):
        if not self.on:
            return contextlib.nullcontext()
        import jax

        return jax.profiler.TraceAnnotation(self.PREFIX + kind)
