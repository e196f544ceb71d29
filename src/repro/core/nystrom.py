"""Nyström completion of the gram matrix (paper §5, eq. 61).

Given the first K rows ``G_KN`` of an N x N gram matrix (the center machine's
exact local block plus the quantization-estimated cross blocks), approximate

    Ghat = G_NK  G_KK^{-1}  G_KN .

Ghat agrees with G on the first K rows/cols; the error is the Schur complement
of G_KK.  Optionally make the diagonal exact (Snelson & Ghahramani '05 /
FITC-style correction mentioned by the paper) when local diagonals are shipped
(O(N) extra floats).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .linalg_safe import DEFAULT_JITTER, chol_jittered, chol_safe

__all__ = [
    "nystrom_complete",
    "nystrom_nlml",
    "nystrom_cross",
    "nystrom_posterior",
    "nystrom_factors",
    "nystrom_apply",
    "nystrom_serve_cache",
    "nystrom_projector",
    "nystrom_apply_cached",
    "nystrom_kinv",
    "chol_update",
    "chol_update_rank",
    "chol_append",
    "chol_append_at",
]


def nystrom_complete(G_KK, G_KN, exact_diag=None):
    """Ghat = G_NK G_KK^{-1} G_KN   (eq. 61).

    G_KK: (K, K) exact; G_KN: (K, N) first K rows (incl. the K x K block).
    exact_diag: optional (N,) true diagonal to pin (FITC correction)."""
    K = G_KK.shape[0]
    # differentiated (a training gram hook): one-shot jitter —
    # lax.while_loop escalation has no reverse-mode rule
    L = chol_jittered(G_KK, DEFAULT_JITTER * jnp.trace(G_KK) / K)
    W = jax.scipy.linalg.solve_triangular(L, G_KN, lower=True)  # (K, N)
    Ghat = W.T @ W
    if exact_diag is not None:
        Ghat = Ghat + jnp.diag(jnp.maximum(exact_diag - jnp.diagonal(Ghat), 0.0))
    return Ghat


def nystrom_nlml(G_KK, G_KN, y, noise_var):
    """Negative log marginal likelihood of ``y`` under the completed gram,
    ``nlml_from_gram(nystrom_complete(G_KK, G_KN), y, noise_var)``, in
    woodbury form: with W = L_KK^{-1} G_KN and s2 = noise_var + jitter,

      log|W^T W + s2 I_N| = (N - K) log s2 + log|s2 I_K + W W^T|
      y^T (W^T W + s2 I_N)^{-1} y = (y^T y - |L_M^{-1} W y|^2) / s2

    with L_M = chol(s2 I_K + W W^T).  O(N K^2) per evaluation and only K x K
    factorizations, so the training loss never forms or factors the N x N
    matrix.  At N = 10,000 the dense loss's training scan took 280 s to
    compile for a TPU v5e, this one 35 s.
    Differentiable: one-shot jitter throughout (see ``nystrom_complete``)."""
    K = G_KK.shape[0]
    N = G_KN.shape[1]
    L = chol_jittered(G_KK, DEFAULT_JITTER * jnp.trace(G_KK) / K)
    W = jax.scipy.linalg.solve_triangular(L, G_KN, lower=True)  # (K, N)
    s2 = noise_var + DEFAULT_JITTER
    Lm = chol_jittered(W @ W.T, s2)
    b = jax.scipy.linalg.solve_triangular(Lm, W @ y, lower=True)
    quad = (y @ y - b @ b) / s2
    logdet = (N - K) * jnp.log(s2) + 2.0 * jnp.sum(jnp.log(jnp.diagonal(Lm)))
    return 0.5 * quad + 0.5 * logdet + 0.5 * N * jnp.log(2.0 * jnp.pi)


def nystrom_cross(G_KK, G_KN, G_star_K):
    """Test-train covariance through the SAME Nyström map:
    Q_*N = G_*K G_KK^{-1} G_KN (Quiñonero-Candela & Rasmussen's FITC test
    covariance).  Pairing the raw k(x*, x) cross-covariance with a
    Nyström-structured train gram amplifies y-components outside the rank-K
    span — see CenterGP.predict."""
    K = G_KK.shape[0]
    L = chol_jittered(G_KK, DEFAULT_JITTER * jnp.trace(G_KK) / K)
    W = jax.scipy.linalg.solve_triangular(L, G_KN, lower=True)  # (K, N)
    B = jax.scipy.linalg.solve_triangular(L, G_star_K.T, lower=True)  # (K, t)
    return B.T @ W


def nystrom_kinv(W, L_M, s2, v):
    """(Ghat + s2 I)^{-1} v in woodbury form:
    (s2 I + W^T W)^{-1} = (I - W^T (s2 I + W W^T)^{-1} W) / s2."""
    t = W @ v
    t = jax.scipy.linalg.cho_solve((L_M, True), t)
    return (v - W.T @ t) / s2


def nystrom_factors(G_KK, G_KN, y, noise_var):
    """Fit-time factorization of the Nyström predictive — everything
    query-independent, computed ONCE:

      L_KK = chol(G_KK + jitter)          (K, K)
      W    = L_KK^{-1} G_KN               (K, N)
      L_M  = chol(s2 I + W W^T)           (K, K)
      alpha = (Ghat + s2 I)^{-1} y        (N,)

    Returned as a dict of arrays so the factor set round-trips through
    ``repro.checkpoint`` with stable key paths.  :func:`nystrom_apply`
    consumes it per query batch with NO further factorization (triangular
    solves only) — the serve-path invariant ``FittedProtocol`` relies on."""
    K = G_KK.shape[0]
    # fit-time: escalate jitter on non-finite factors (rank-deficient grams
    # from corrupted/demoted wire rows) rather than serving NaNs
    L = chol_safe(G_KK, DEFAULT_JITTER * jnp.trace(G_KK) / K)
    W = jax.scipy.linalg.solve_triangular(L, G_KN, lower=True)  # (K, N)
    s2 = noise_var + DEFAULT_JITTER
    M = s2 * jnp.eye(K, dtype=W.dtype) + W @ W.T
    Lm = chol_safe(M)
    alpha = nystrom_kinv(W, Lm, s2, y)
    return {"L_KK": L, "W": W, "L_M": Lm, "alpha": alpha}


def nystrom_apply(factors, G_star_K, g_star_star, noise_var):
    """Query-time half of the Nyström predictive: O(t N K) triangular solves
    against cached :func:`nystrom_factors` — no Cholesky factorization."""
    L, W, Lm, alpha = factors["L_KK"], factors["W"], factors["L_M"], factors["alpha"]
    s2 = noise_var + DEFAULT_JITTER
    # test cross-covariances via the same Nyström map: G_*N = G_*K G_KK^{-1} G_KN
    B = jax.scipy.linalg.solve_triangular(L, G_star_K.T, lower=True)  # (K, t)
    G_sN = B.T @ W  # (t, N)
    mean = G_sN @ alpha
    V = jax.vmap(lambda v: nystrom_kinv(W, Lm, s2, v), in_axes=1, out_axes=1)(G_sN.T)
    var = g_star_star - jnp.sum(G_sN.T * V, axis=0)
    return mean, jnp.maximum(var, 1e-12)


def nystrom_serve_cache(factors):
    """Fused-serve-epilogue operands, precomputed from :func:`nystrom_factors`
    output — all K-sized and CAPACITY-INDEPENDENT (K never grows under
    streaming updates, so these need no ``streaming._GROWTH`` entries):

      Ainv   = L_KK^{-1}        (K, K)  explicit triangular inverse
      walpha = W alpha          (K,)

    With these, :func:`nystrom_apply_cached` serves a query batch with
    matmuls only — no triangular solve against the O(N)-sized ``W`` in the
    hot path.  The keys live in the artifact's ``factors`` dict, so they
    round-trip through checkpoints; artifacts saved before the cache existed
    simply lack the keys and serve on the unfused path."""
    L, W, alpha = factors["L_KK"], factors["W"], factors["alpha"]
    K = L.shape[0]
    Ainv = jax.scipy.linalg.solve_triangular(
        L, jnp.eye(K, dtype=L.dtype), lower=True
    )
    return {"Ainv": Ainv, "walpha": W @ alpha}


def nystrom_projector(L_M, s2):
    """The quad-form projector of the cached serve, P = (U - U M^{-1} U)/s2
    with U = W W^T and M = s2 I + U = L_M L_M^T, computed from L_M alone as
    P = I - s2 M^{-1} (equal: U M^{-1} U = U - s2 M^{-1} U), so the
    artifact keeps no U.  The difference form subtracts two
    matrices of the size of U's largest eigenvalue and divides by s2, so in
    float32 it keeps little of P once that eigenvalue is many times s2: at
    sarcos's 44,484 columns it moved served latent variances by a tenth of
    the prior on a TPU v5e.  This form never exceeds the identity."""
    eye = jnp.eye(L_M.shape[0], dtype=L_M.dtype)
    return eye - s2 * jax.scipy.linalg.cho_solve((L_M, True), eye)


def nystrom_apply_cached(factors, G_star_K, g_star_star, noise_var):
    """Fused-epilogue twin of :func:`nystrom_apply`: algebraically equal, but
    O(t K^2 + K^3) matmuls against the :func:`nystrom_serve_cache` operands
    instead of O(t N K) solves against W.  Derivation: with
    B = L_KK^{-1} G_*K^T the Nyström cross-covariance is G_*N = B^T W, so

      mean = G_*N alpha = B^T (W alpha)
      quad = diag(G_*N (Ghat + s2 I)^{-1} G_*N^T) = diag(B^T P B),
      P    = (U - U M^{-1} U) / s2            (woodbury through L_M,
                                               :func:`nystrom_projector`)

    — no per-column :func:`nystrom_kinv`, no O(N) operand anywhere."""
    Ainv, Lm, walpha = factors["Ainv"], factors["L_M"], factors["walpha"]
    s2 = noise_var + DEFAULT_JITTER
    B = Ainv @ G_star_K.T  # (K, t)
    mean = B.T @ walpha
    P = nystrom_projector(Lm, s2)  # (K, K)
    var = g_star_star - jnp.sum(B * (P @ B), axis=0)
    return mean, jnp.maximum(var, 1e-12)


def nystrom_posterior(G_KK, G_KN, y, noise_var, G_star_K, g_star_star, exact_diag=None):
    """GP posterior with the Nyström gram, solved in O(N K^2) woodbury form:
    factorize (:func:`nystrom_factors`) then apply (:func:`nystrom_apply`).

    Ghat + s^2 I = s^2 I + W^T W with W = L^{-1} G_KN — avoid forming N x N when
    no exact_diag correction is requested.
    """
    if exact_diag is not None:
        # fall back to the dense path (still fine for the paper's N ~ 1e3)
        Ghat = nystrom_complete(G_KK, G_KN, exact_diag)
        from .gp import posterior_from_gram

        return posterior_from_gram(Ghat, G_star_K, g_star_star, y, noise_var)
    f = nystrom_factors(G_KK, G_KN, y, noise_var)
    return nystrom_apply(f, G_star_K, g_star_star, noise_var)


# --------------------------------------------------------------------------
# streaming rank-k factor maintenance (FittedProtocol.update)
# --------------------------------------------------------------------------


def chol_update(L, x):
    """Rank-1 Cholesky update: chol(L L^T + x x^T) in O(K^2) — the classic
    Givens sweep, written as a fori_loop so it jits and vmaps."""
    K = L.shape[0]
    idx = jnp.arange(K)

    def body(k, carry):
        L, x = carry
        Lkk, xk = L[k, k], x[k]
        r = jnp.sqrt(Lkk * Lkk + xk * xk)
        c, s = r / Lkk, xk / Lkk
        below = idx > k
        col = L[:, k]
        newcol = jnp.where(below, (col + s * x) / c, col).at[k].set(r)
        x = jnp.where(below, c * x - s * newcol, x)
        return L.at[:, k].set(newcol), x

    L, _ = jax.lax.fori_loop(0, K, body, (L, x))
    return L


def chol_update_rank(L, V):
    """Rank-k update chol(L L^T + V V^T): scan of rank-1 sweeps over the
    columns of V (k, n_new) — O(n_new K^2), never refactorizes the K x K."""
    L, _ = jax.lax.scan(lambda Lc, v: (chol_update(Lc, v), None), L, V.T)
    return L


def chol_append(L, C_on, C_nn):
    """Grow a Cholesky factor by appended rows/cols WITHOUT refactorizing the
    existing block: given L = chol(A) and the bordered matrix
    [[A, C_on], [C_on^T, C_nn]], return its (n+k, n+k) factor

        [[L, 0], [X^T, chol(S)]],   X = L^{-1} C_on,  S = C_nn - X^T X.

    Only the NEW k x k Schur block is factorized — O(n k^2 + k^3)."""
    X = jax.scipy.linalg.solve_triangular(L, C_on, lower=True)  # (n, k)
    S = C_nn - X.T @ X
    Ls = chol_safe(S)
    n, k = C_on.shape
    top = jnp.concatenate([L, jnp.zeros((n, k), L.dtype)], axis=1)
    bot = jnp.concatenate([X.T, Ls], axis=1)
    return jnp.concatenate([top, bot], axis=0)


def chol_append_at(L, C_on, C_nn, pos):
    """Capacity-aware :func:`chol_append`: write the bordered factor rows IN
    PLACE at (traced) slot ``pos`` of a padded-capacity factor buffer instead
    of growing the array.

    ``L`` is (C, C) with the live block in ``[:pos, :pos]`` and every padded
    slot holding the identity pattern (unit diagonal, zeros elsewhere — see
    ``streaming.grow_to_capacity``); ``C_on`` is (C, k) with zero rows at
    every slot >= ``pos``.  Under that contract the forward solve is EXACT:
    padded rows of ``X = L^{-1} C_on`` come out zero (0 right-hand side, zero
    off-diagonals, unit pivot), so ``S = C_nn - X^T X`` equals the true Schur
    complement of the live block and the written rows ``[X^T | chol(S)]``
    reproduce :func:`chol_append` bit-for-bit in the occupied slots.  Shapes
    never change, so consecutive in-bucket appends reuse one traced program
    (the retrace-free streaming contract of ``base.update``)."""
    X = jax.scipy.linalg.solve_triangular(L, C_on, lower=True)  # (C, k)
    S = C_nn - X.T @ X
    rows = jax.lax.dynamic_update_slice(X.T, chol_safe(S), (0, pos))  # (k, C)
    return jax.lax.dynamic_update_slice(L, rows, (pos, 0))
