"""One kernel runtime for every Pallas family: dispatch policy, registry,
persistent autotune cache, and the shape-sweep bench harness.

Before this module existed each family (``gram``, ``quant``, ``qgram``)
re-parsed ``REPRO_FORCE_PALLAS`` and treated ``interpret=None`` slightly
differently, and ``decode_attn`` had no XLA fallback at all.  The policy now
lives in exactly one place — :func:`choose` — and is identical for all
families:

* ``interpret`` given explicitly -> the Pallas kernel path with that
  interpret flag (the caller is debugging the kernel; policy stays out of
  the way).
* ``interpret=None`` on TPU -> compiled Pallas.
* ``interpret=None`` off-TPU with ``REPRO_FORCE_PALLAS=1`` -> interpret-mode
  Pallas (kernel checking only — on CPU the interpreter LOSES to XLA, see
  benchmarks/hotpath_bench.py).
* ``interpret=None`` otherwise (CPU, and GPU until a Triton lowering is
  registered) -> the family's single-jit XLA fallback.

Families register a :class:`KernelImpl` (pallas + xla entry points over the
SAME public signature, plus the ``ref.py`` oracle) so dispatch tables,
parity tests, and the bench sweep can enumerate every backend of every
family without knowing family internals.  docs/kernel_runtime.md documents
the policy, the cache file format, and how to add a backend.
"""
from __future__ import annotations

import dataclasses
import functools
import json
import os
import sys
import tempfile
import threading
import time
from typing import Any, Callable, Iterable, Sequence

import jax

from ..compat import CHECKOUT_ROOT
from ..core.registry import Registry

# --------------------------------------------------------------------------
# the one fallback-policy code path
# --------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class Decision:
    """Outcome of the dispatch policy: which backend kind runs this call.

    ``kind`` is ``"pallas"`` or ``"xla"``; ``interpret`` is only meaningful
    for the Pallas kind."""

    kind: str
    interpret: bool = False


def force_pallas() -> bool:
    """True when ``REPRO_FORCE_PALLAS=1`` — the kernel path is forced even
    off-TPU (interpret mode; for checking kernels, never for speed)."""
    return os.environ.get("REPRO_FORCE_PALLAS", "") == "1"


def choose(interpret: bool | None = None) -> Decision:
    """THE fallback policy.  Every kernel family routes through this single
    function; see the module docstring for the table."""
    if interpret is not None:
        return Decision("pallas", bool(interpret))
    if jax.default_backend() == "tpu":
        return Decision("pallas", False)
    if force_pallas():
        return Decision("pallas", True)
    return Decision("xla")


# --------------------------------------------------------------------------
# kernel registry (mirrors core.registry: named specs, menu-on-typo)
# --------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class KernelImpl:
    """Per-backend implementations of one kernel op, all over the SAME
    public (unpadded) signature so they are interchangeable in dispatch
    tables, parity tests, and the bench sweep.

    ``pallas`` takes the public args plus a required ``interpret`` keyword
    and owns its padding; ``xla`` is the single-jit fallback program; ``ref``
    is the pure-jnp oracle from the family's ``ref.py`` (parity target, may
    coincide with ``xla``)."""

    name: str
    pallas: Callable  # (*args, interpret: bool, **kw)
    xla: Callable  # (*args, **kw)
    ref: Callable | None = None


KERNEL_OPS = Registry("kernel op")


def register_kernel_op(spec: KernelImpl) -> KernelImpl:
    return KERNEL_OPS.register(spec.name, spec)


def kernel_op(name: str) -> KernelImpl:
    return KERNEL_OPS.get(name)


def dispatch(name: str, interpret: bool | None = None):
    """Resolve (policy, callable) for one op under the unified policy.

    Returns ``(decision, fn)`` where ``fn`` already has the backend choice
    (and interpret flag, for Pallas) bound."""
    spec = KERNEL_OPS.get(name)
    d = choose(interpret)
    if d.kind == "xla":
        return d, spec.xla
    return d, functools.partial(spec.pallas, interpret=d.interpret)


# --------------------------------------------------------------------------
# autotune candidate registry (one menu per op family)
# --------------------------------------------------------------------------
#
# Families used to keep their candidate tables as private module constants,
# which meant a new shape family (the tenant-batched fleet epilogue) had no
# sanctioned place to declare what is worth sweeping.  Candidates now
# register next to the KernelImpl, at module top level, and every sweep
# (qgram's block autotune, the fleet epilogue's t-tile resolve) reads the
# same table.

_TUNE_CANDIDATES: dict[str, tuple] = {}


def register_tune_candidates(op: str, candidates: Iterable[tuple]) -> tuple:
    """Declare the autotune candidate set for one kernel op (module top
    level, like :func:`register_kernel_op`).  Re-registration replaces the
    menu — the persistent cache keys are shape-scoped, so stale winners that
    fall off the menu are ignored by :func:`autotune`'s membership check."""
    cands = tuple(tuple(c) for c in candidates)
    _TUNE_CANDIDATES[op] = cands
    return cands


def tune_candidates(op: str) -> tuple:
    """The registered candidate menu for ``op`` (KeyError names the menu on
    a typo, mirroring the registry convention)."""
    try:
        return _TUNE_CANDIDATES[op]
    except KeyError:
        raise KeyError(
            f"no autotune candidates registered for {op!r}: known are "
            f"{sorted(_TUNE_CANDIDATES)}"
        ) from None


def interpret_autotune() -> bool:
    """Normally sweeps only run on the compiled (TPU) path — timing the
    interpreter is meaningless.  REPRO_AUTOTUNE_INTERPRET=1 lets tests drive
    the full autotune round-trip (sweep -> persist -> warm hit) on CPU."""
    return os.environ.get("REPRO_AUTOTUNE_INTERPRET", "") == "1"


# --------------------------------------------------------------------------
# persistent autotune cache
# --------------------------------------------------------------------------
#
# File format (JSON, atomic-rename writes):
#   {"version": 1, "entries": {"<key>": [bn, bp], ...}}
# Key format (one string so the file stays greppable):
#   <op>|<device_kind>|<shape>x<shape>...|<dtype>|bits=<b>|<extra...>
# A corrupt, stale, or unreadable file is IGNORED (defaults / re-sweep), never
# an error: the cache is an accelerant, not a dependency.  The default file
# sits at a fixed path in the checkout, beside the compilation cache
# (repro.compat.setup_compilation_cache), so a later process in the same
# checkout finds the winners again.

CACHE_VERSION = 1

_SWEEPS = 0  # process-local count of sweeps actually run (tests assert on it)
_CACHE_MEM: dict[str, tuple] | None = None
_CACHE_LOCK = threading.Lock()


def cache_path() -> str:
    return os.environ.get(
        "REPRO_TUNE_CACHE", os.path.join(CHECKOUT_ROOT, ".autotune.json")
    )


def cache_key(
    op: str,
    shapes: Sequence[Sequence[int]],
    dtype: Any,
    bits: int | None = None,
    extra: Sequence[Any] = (),
) -> str:
    """Build the (shape, dtype, bits, device kind) cache key for one op call
    — a winner tuned on one chip generation is never served to another."""
    shape_sig = "x".join("-".join(str(int(s)) for s in shp) for shp in shapes)
    parts = [op, jax.devices()[0].device_kind, shape_sig, str(dtype)]
    if bits is not None:
        parts.append(f"bits={int(bits)}")
    parts.extend(str(e) for e in extra)
    return "|".join(parts)


def _load_cache() -> dict[str, tuple]:
    global _CACHE_MEM
    if _CACHE_MEM is not None:
        return _CACHE_MEM
    entries: dict[str, tuple] = {}
    try:
        with open(cache_path()) as f:
            blob = json.load(f)
        if (
            isinstance(blob, dict)
            and blob.get("version") == CACHE_VERSION
            and isinstance(blob.get("entries"), dict)
        ):
            for k, v in blob["entries"].items():
                if isinstance(k, str) and isinstance(v, (list, tuple)):
                    entries[k] = tuple(v)
    except (OSError, ValueError, TypeError):
        pass  # corrupt/stale/missing -> defaults; a later sweep rewrites it
    _CACHE_MEM = entries
    return entries


def _store_cache(key: str, value: tuple) -> None:
    entries = _load_cache()
    entries[key] = tuple(value)
    path = cache_path()
    try:
        os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
        fd, tmp = tempfile.mkstemp(
            dir=os.path.dirname(path) or ".", prefix=".autotune-"
        )
        with os.fdopen(fd, "w") as f:
            json.dump(
                {
                    "version": CACHE_VERSION,
                    "entries": {k: list(v) for k, v in entries.items()},
                },
                f,
                indent=1,
                sort_keys=True,
            )
        os.replace(tmp, path)
    except OSError:
        pass  # read-only FS etc: stay in-process-only


def clear_cache_memory() -> None:
    """Drop the in-process cache image (tests poke the file between calls)."""
    global _CACHE_MEM
    with _CACHE_LOCK:
        _CACHE_MEM = None


def autotune(
    key: str,
    candidates: Iterable[tuple],
    measure: Callable[[tuple], float | None],
    default: tuple,
) -> tuple:
    """Warm-hit-or-sweep: return the cached winner for ``key`` if the disk /
    in-process cache has one, else time ``measure(candidate)`` over the
    candidates (``None`` = candidate infeasible for this shape), persist the
    winner, and return it.  A warm hit performs ZERO sweeps — asserted by
    tests/test_kernel_runtime.py across two processes.  A candidate that
    raises is reported on stderr and skipped; when no candidate could run at
    all the sweep raises instead of persisting an untested default."""
    global _SWEEPS
    cands = [tuple(c) for c in candidates]
    with _CACHE_LOCK:
        hit = _load_cache().get(key)
    if hit is not None and tuple(hit) in cands:
        return tuple(hit)
    _SWEEPS += 1
    best, best_t = tuple(default), float("inf")
    failures = []
    for cand in cands:
        try:
            dt = measure(cand)
        except Exception as e:  # noqa: BLE001 - one candidate's failure
            failures.append(f"{cand}: {type(e).__name__}: {e}")
            print(f"autotune {key}: candidate {cand} failed: "
                  f"{type(e).__name__}: {e}", file=sys.stderr)
            continue
        if dt is not None and dt < best_t:
            best, best_t = cand, dt
    if failures and best_t == float("inf"):
        raise RuntimeError(
            f"autotune {key}: no candidate ran ({len(failures)} failed): "
            + "; ".join(failures)
        )
    with _CACHE_LOCK:
        _store_cache(key, best)
    return best


def sweep_count() -> int:
    """Number of autotune sweeps this process has actually run."""
    return _SWEEPS


# --------------------------------------------------------------------------
# FlagGems-style shape sweep (benchmarks/kernels_bench.py wires this in)
# --------------------------------------------------------------------------


def timing_backends(spec: KernelImpl) -> dict[str, Callable]:
    """The backend table worth timing on this host: the XLA fallback always,
    plus the Pallas kernel (compiled on TPU, interpret elsewhere — labelled
    so the row is honest about what ran)."""
    interp = jax.default_backend() != "tpu"
    label = "pallas_interpret" if interp else "pallas"
    return {
        "xla": spec.xla,
        label: functools.partial(spec.pallas, interpret=interp),
    }


def shape_sweep(
    op: str,
    cases: Sequence[tuple[str, Callable[[], tuple], dict | None]],
    reps: int = 2,
) -> list[tuple[str, str, float]]:
    """Time every backend of ``op`` across a shape table.

    ``cases`` rows are ``(label, make_args, kwargs)`` where ``make_args``
    builds the positional args for the op's public signature.  Returns
    ``(case_label, backend, us_per_call)`` rows; a backend that cannot run a
    case yields ``nan`` rather than aborting the sweep."""
    spec = KERNEL_OPS.get(op)
    rows: list[tuple[str, str, float]] = []
    for label, make_args, kw in cases:
        args = make_args()
        kw = dict(kw or {})
        for bname, fn in timing_backends(spec).items():
            call = lambda: jax.block_until_ready(fn(*args, **kw))
            try:
                call()  # compile + warm
                t0 = time.perf_counter()
                for _ in range(reps):
                    call()
                us = (time.perf_counter() - t0) / reps * 1e6
            except Exception as e:  # noqa: BLE001 - the row records nan
                print(f"shape_sweep {op}/{label}/{bname} failed: "
                      f"{type(e).__name__}: {e}", file=sys.stderr)
                us = float("nan")
            rows.append((label, bname, us))
    return rows
