"""Numerically-guarded Cholesky factorizations.

Every factorization site in the repo used to carry its own ``_JITTER = 1e-6``
constant and hope.  This module centralizes that:

* :data:`DEFAULT_JITTER` — the one pinned constant (1e-6, unchanged from the
  legacy per-module copies so existing tolerances are untouched).
* :func:`chol_jittered` — the legacy behaviour as a named helper: one shot,
  fixed jitter, fully differentiable.  Used at every site that sits under
  ``jax.grad`` (training losses), because :func:`jax.lax.while_loop` is not
  reverse-mode differentiable.
* :func:`chol_safe` — fit-time factorizations: bit-identical first attempt,
  then geometric jitter escalation under ``lax.while_loop`` when the factor
  comes back non-finite (rank-deficient / badly-conditioned Gram).  On the
  well-conditioned path the loop body never executes, so the cost is one
  Cholesky plus an ``isfinite`` reduction — and since it is only called at
  fit/update time, the warm predict path still contains zero factorizations
  (``predict_op_counts`` unchanged).

Both helpers take the FULL jitter ``eps`` (already scaled by trace/size where
the call site wants that) so the first-attempt arithmetic is expression-
identical to the code it replaces.

* :data:`MATMUL_PRECISION` / :func:`full_precision` — the matmul precision
  every fit, serve and update program is traced under.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

__all__ = ["DEFAULT_JITTER", "MATMUL_PRECISION", "full_precision",
           "chol_jittered", "chol_safe", "eigh_sym"]

DEFAULT_JITTER = 1e-6

# A TPU runs a float32 matmul at its DEFAULT precision as one bfloat16 pass
# (8 mantissa bits).  On a TPU v5e that put a max abs error of 0.11 on the
# inner products of kin40k-shaped rows (2.6e-6 at HIGHEST), and through the
# solves and the training it moved a center fit's served means by up to 1.8
# (std(y) 4.6; SMSE 0.143 against 0.133).  The GP programs therefore run
# every matmul at full float32 precision.  CPU float32 matmuls are full
# float32 either way.
MATMUL_PRECISION = "highest"


def full_precision(fn):
    """Run ``fn`` with :data:`MATMUL_PRECISION` as the default matmul
    precision, so every program it traces (jit caches key on the setting)
    multiplies in full float32 on any backend."""

    @functools.wraps(fn)
    def wrapped(*args, **kwargs):
        with jax.default_matmul_precision(MATMUL_PRECISION):
            return fn(*args, **kwargs)

    return wrapped


def eigh_sym(M):
    """Eigendecomposition of a symmetric matrix — the ONE on-device ``eigh``
    home (repro.analysis.lint: ``raw-eigh``).

    ``jnp.linalg.eigh`` silently reads only one triangle, so a nominally
    symmetric input hides asymmetry bugs; callers symmetrize explicitly at
    the call site (``eigh_sym(0.5 * (B + B.T))``) where the input is only
    symmetric up to roundoff.  Centralized so eigh policy changes (clipping,
    dtype promotion, a backend switch) happen in one place, like the Cholesky
    jitter policy above."""
    return jnp.linalg.eigh(M)


def chol_jittered(M, eps):
    """``cholesky(M + eps * I)`` — one shot, differentiable.

    Use at sites under ``jax.grad`` (NLML, ELBO, Nyström completion inside the
    training loss): ``lax.while_loop`` has no reverse-mode rule, so these
    cannot escalate.  ``eps`` is the full jitter value (may be a traced
    scalar, e.g. ``noise_var + DEFAULT_JITTER``)."""
    n = M.shape[-1]
    return jnp.linalg.cholesky(M + eps * jnp.eye(n, dtype=M.dtype))


def chol_safe(M, eps=0.0, *, growth=10.0, max_tries=6):
    """Cholesky with geometric jitter escalation on non-finite factors.

    First attempt is ``cholesky(M + eps * I)`` — bit-identical to the legacy
    call it replaces (``eps=0.0`` compiles to no added diagonal).  If that
    factor contains NaN/Inf (jnp.linalg.cholesky returns NaNs rather than
    raising), retries with ``M + (eps + base * growth**t) * I`` for
    t = 0..max_tries-1 under ``lax.while_loop``; ``base`` is scaled to the
    matrix (``max(eps, DEFAULT_JITTER * (|tr M|/n + DEFAULT_JITTER))``) so the
    escalation is meaningful for both unit-scale and large Grams.

    vmap-safe: the loop carry select is per-element (``jnp.where``), so in a
    batched call an already-finite element keeps its original factor even
    while a sibling element escalates.
    """
    n = M.shape[-1]
    eye = jnp.eye(n, dtype=M.dtype)
    eps = jnp.asarray(eps, M.dtype)
    L0 = jnp.linalg.cholesky(M + eps * eye)
    # escalation base: must be strictly positive even when eps == 0
    scale = jnp.abs(jnp.trace(M, axis1=-2, axis2=-1)) / n
    base = jnp.maximum(eps, DEFAULT_JITTER * (scale + DEFAULT_JITTER))

    def cond(carry):
        t, L = carry
        return (t < max_tries) & ~jnp.all(jnp.isfinite(L))

    growth = jnp.asarray(growth, M.dtype)

    def body(carry):
        t, L = carry
        # explicit cast: float ** int32 has no promotion path under
        # jax_numpy_dtype_promotion=strict (the strict-mode runtime contract)
        L_new = jnp.linalg.cholesky(M + (eps + base * growth ** t.astype(M.dtype)) * eye)
        ok = jnp.isfinite(L)
        return t + 1, jnp.where(ok, L, L_new)

    _, L = jax.lax.while_loop(cond, body, (jnp.int32(0), L0))
    return L
