"""Jit'd public wrapper for the fused serve epilogue: pads (t, K) to lane
multiples, routes backend selection through the unified kernel runtime, and
slices the moment rows back out.

Padding is harmless by construction: padded K columns of ``G``/``Ainv``/
``P``/``walpha`` are zero (so they contribute nothing to the matmuls) and
padded t columns carry ``gss = prior = 1`` (so the rbcm logs and PoE
precisions stay finite) — the caller only ever sees rows ``[:, :t]``.
"""
from __future__ import annotations

import functools
import time

import jax
import jax.numpy as jnp

from .. import runtime
from .epilogue import epilogue_pallas, epilogue_fleet_pallas, LANE
from .ref import (  # noqa: F401
    epilogue_moments_ref,
    epilogue_moments_fleet_ref,
    EPILOGUE_FUSES,
)

_epilogue_xla = functools.partial(jax.jit, static_argnames=("fuse",))(
    epilogue_moments_ref
)

_epilogue_fleet_xla = functools.partial(jax.jit, static_argnames=("fuse",))(
    epilogue_moments_fleet_ref
)


def _pad_to(a, mult, axis, value=0.0):
    pad = (-a.shape[axis]) % mult
    if pad == 0:
        return a
    widths = [(0, 0)] * a.ndim
    widths[axis] = (0, pad)
    return jnp.pad(a, widths, constant_values=value)


def _epilogue_kernel_path(G, Ainv, P, walpha, gss, prior, w, *, fuse,
                          interpret: bool):
    m, t, K = G.shape
    f32 = functools.partial(jnp.asarray, dtype=jnp.float32)
    Gp = _pad_to(_pad_to(f32(G), LANE, 1), LANE, 2)
    Ap = _pad_to(_pad_to(f32(Ainv), LANE, 1), LANE, 2)
    Pp = _pad_to(_pad_to(f32(P), LANE, 1), LANE, 2)
    wap = _pad_to(f32(walpha)[:, None, :], LANE, 2)  # (m, 1, Kp)
    gssp = _pad_to(f32(gss)[None, :], LANE, 1, value=1.0)  # (1, tp)
    priorp = _pad_to(f32(prior)[None, :], LANE, 1, value=1.0)
    tp = gssp.shape[1]
    wp = f32(w)[:, None, None] * jnp.ones((m, 1, tp), jnp.float32)  # (m, 1, tp)
    S = epilogue_pallas(Gp, Ap, Pp, wap, gssp, priorp, wp,
                        fuse=fuse, interpret=interpret)
    return S[:3, :t]


runtime.register_kernel_op(runtime.KernelImpl(
    name="epilogue",
    pallas=_epilogue_kernel_path,
    xla=lambda G, Ainv, P, walpha, gss, prior, w, fuse: _epilogue_xla(
        G, Ainv, P, walpha, gss, prior, w, fuse=fuse
    ),
    ref=epilogue_moments_ref,
))


def epilogue_moments(G, Ainv, P, walpha, gss, prior, w, *, fuse,
                     interpret: bool | None = None):
    """Summed fusion moment rows S (3, t) for a fleet of cached Nyström
    experts — the fused serve epilogue (see ref.py for operand shapes).
    Callers finish with the fusion's ``finalize(S, m, prior)``."""
    d = runtime.choose(interpret)
    if d.kind == "xla":
        return _epilogue_xla(G, Ainv, P, walpha, gss, prior, w, fuse=fuse)
    return _epilogue_kernel_path(
        G, Ainv, P, walpha, gss, prior, w, fuse=fuse, interpret=d.interpret
    )


# --------------------------------------------------------------------------
# tenant-batched ("fleet") epilogue: the same op with a leading tenant axis
# --------------------------------------------------------------------------

# the fleet shape family's sweep menu: candidate t-tiles for the kernel's
# test-point axis (a tile must divide the LANE-padded t; infeasible
# candidates are skipped by the measure closure)
runtime.register_tune_candidates(
    "epilogue_fleet", ((LANE,), (2 * LANE,), (4 * LANE,))
)


def _epilogue_fleet_kernel_path(G, Ainv, P, walpha, gss, prior, w, *, fuse,
                                interpret: bool, block=None):
    T, m, t, K = G.shape
    f32 = functools.partial(jnp.asarray, dtype=jnp.float32)
    Gp = _pad_to(_pad_to(f32(G), LANE, 2), LANE, 3)
    Ap = _pad_to(_pad_to(f32(Ainv), LANE, 2), LANE, 3)
    Pp = _pad_to(_pad_to(f32(P), LANE, 2), LANE, 3)
    wap = _pad_to(f32(walpha)[:, :, None, :], LANE, 3)  # (T, m, 1, Kp)
    gssp = _pad_to(f32(gss)[:, None, :], LANE, 2, value=1.0)  # (T, 1, tp)
    priorp = _pad_to(f32(prior)[:, None, :], LANE, 2, value=1.0)
    tp = gssp.shape[2]
    wp = f32(w)[:, :, None, None] * jnp.ones((T, m, 1, tp), jnp.float32)
    if block is not None and tp % int(block):
        block = None  # tuned tile from another shape bucket: full-t fallback
    S = epilogue_fleet_pallas(Gp, Ap, Pp, wap, gssp, priorp, wp,
                              fuse=fuse, block=block, interpret=interpret)
    return S[:, :3, :t]


runtime.register_kernel_op(runtime.KernelImpl(
    name="epilogue_fleet",
    pallas=_epilogue_fleet_kernel_path,
    xla=lambda G, Ainv, P, walpha, gss, prior, w, fuse: _epilogue_fleet_xla(
        G, Ainv, P, walpha, gss, prior, w, fuse=fuse
    ),
    ref=epilogue_moments_fleet_ref,
))


def fleet_epilogue_block(T: int, m: int, t: int, K: int, *, fuse: str = "kl",
                         interpret: bool | None = None):
    """Resolve the tuned t-tile for a fleet-shaped epilogue launch.

    This runs OUTSIDE any trace — the fleet predict jit takes the winner as
    a STATIC argument, which is what lets the sweep happen at all (inside
    the traced program the operands are tracers and timing is meaningless).
    Returns ``None`` (kernel default: full t) when the XLA fallback will
    serve the launch, or when sweeping is pointless (interpret mode without
    REPRO_AUTOTUNE_INTERPRET=1).  Misses sweep synthetic zero operands of
    the launch shape and persist the winner through the runtime's autotune
    cache, so fleet-shaped launches warm-hit across processes exactly like
    the single-tenant families."""
    d = runtime.choose(interpret)
    if d.kind != "pallas":
        return None
    if d.interpret and not runtime.interpret_autotune():
        return None
    tp = t + (-t) % LANE
    Kp = K + (-K) % LANE
    key = runtime.cache_key(
        "epilogue_fleet", shapes=((T, m, t, K),), dtype=jnp.float32,
        extra=(f"fuse={fuse}",),
    )
    ops = None  # built lazily: only a cache MISS pays the allocation

    def measure(cand):
        nonlocal ops
        (bt,) = cand
        if tp % bt:
            return None
        if ops is None:
            ops = (
                jnp.zeros((T, m, tp, Kp), jnp.float32),
                jnp.zeros((T, m, Kp, Kp), jnp.float32),
                jnp.zeros((T, m, Kp, Kp), jnp.float32),
                jnp.zeros((T, m, 1, Kp), jnp.float32),
                jnp.ones((T, 1, tp), jnp.float32),
                jnp.ones((T, 1, tp), jnp.float32),
                jnp.ones((T, m, 1, tp), jnp.float32),
            )
        fn = lambda: epilogue_fleet_pallas(
            *ops, fuse=fuse, block=bt, interpret=d.interpret
        )
        jax.block_until_ready(fn())  # compile + warm
        t0 = time.perf_counter()
        jax.block_until_ready(fn())
        return time.perf_counter() - t0

    win = runtime.autotune(
        key, runtime.tune_candidates("epilogue_fleet"), measure, (LANE,)
    )
    bt = int(win[0])
    return bt if tp % bt == 0 else None


def epilogue_moments_fleet(G, Ainv, P, walpha, gss, prior, w, *, fuse,
                           block=None, interpret: bool | None = None):
    """Per-tenant summed fusion moment rows S (T, 3, t) — the fused serve
    epilogue batched over a leading tenant axis (operand shapes in ref.py).
    ONE kernel launch covers the whole mixed-tenant micro-batch; callers
    finish with a vmapped ``finalize``.  ``block``: tuned t-tile from
    :func:`fleet_epilogue_block` (static; None = kernel default)."""
    d = runtime.choose(interpret)
    if d.kind == "xla":
        return _epilogue_fleet_xla(G, Ainv, P, walpha, gss, prior, w,
                                   fuse=fuse)
    return _epilogue_fleet_kernel_path(
        G, Ainv, P, walpha, gss, prior, w, fuse=fuse, interpret=d.interpret,
        block=block,
    )
