"""Plain reference of the distributed GP the benchmark drives.

Written from the paper (arXiv:1705.02627, §4.2 per-symbol scheme, §5.2
broadcast protocol with the KL barycenter, eqs. 61-64)
in straightforward ``jax.numpy`` float32, with no kernels, no padding, no
batching of machines and no caches.  It imports nothing of the program and
takes nothing it made: data, machine split and hyperparameters all come from
the seed and from its own training.

Every matmul runs at the precision the configuration states (``highest``);
``precision="high"`` gives the control the check must fail.

Semantics, as the configuration files state them:

* split: ``jax.random.permutation(key, n)`` cut into m near-equal chunks;
* wire (§4.2): machine j fits a decorrelating transform to (Qx = S_j, Qy),
  S the second moment X^T X / n and Qy the sum of the other machines'
  moments; bits go greedily (Algorithm 1) to the
  largest distortion drop, at most ``min(max_bits, R)`` per dimension;
  symbols are coded with equiprobable Gaussian bins and decoded to their
  centroids (eq. 39);
* Nyström (eq. 61): the basis is the receiver's own exact points, the columns
  every point it holds (own exact, others decoded), squared norms taken of
  the points as held; SE kernel ``a exp(-|x - x'|^2 / l2)``;
* training: Adam (lr, 0.9, 0.999, 1e-8) on the Nyström marginal likelihood
  from a = l2 = 1, noise = 0.1, at machine 0;
* prediction: every machine's Nyström posterior, cross-covariances through
  the same map, the m experts fused by the KL barycenter.
"""
from __future__ import annotations

import dataclasses

import numpy as np
import jax
import jax.numpy as jnp
from scipy.special import ndtri

JITTER = 1e-6
COLUMN_BUCKET = 8192  # column counts round up to this, see nystrom_posterior


# --------------------------------------------------------------------------
# the §4.2 per-symbol wire
# --------------------------------------------------------------------------


def gauss_edges(rate: int) -> np.ndarray:
    n = 1 << rate
    return ndtri(np.arange(1, n) / n)


def gauss_centroids(rate: int) -> np.ndarray:
    n = 1 << rate
    a = np.concatenate([[-np.inf], gauss_edges(rate), [np.inf]])
    pdf = np.where(np.isfinite(a), np.exp(-0.5 * a**2) / np.sqrt(2 * np.pi), 0.0)
    return n * (pdf[:-1] - pdf[1:])


def unit_distortion(rate: int) -> float:
    c = gauss_centroids(rate)
    return float(1.0 - np.sum(c**2) / (1 << rate))


def _sqrt_and_inv_sqrt(M):
    w, v = jnp.linalg.eigh(M)
    s = jnp.sqrt(jnp.clip(w, 0.0, None))
    inv = jnp.where(s > 1e-12 * jnp.max(s), 1.0 / jnp.where(s == 0, 1.0, s), 0.0)
    return (v * s) @ v.T, (v * inv) @ v.T


def fit_scheme(Qx, Qy, bits: int, cap: int):
    """(T, T_inv, sigma, rates) of one machine's per-symbol scheme."""
    half, inv_half = _sqrt_and_inv_sqrt(Qy)
    B = half @ Qx @ half
    lam, U = jnp.linalg.eigh(0.5 * (B + B.T))
    lam = jnp.clip(lam[::-1], 0.0, None)
    U = U[:, ::-1]
    lam_h = np.asarray(lam, np.float32)
    e = np.asarray([unit_distortion(r) for r in range(cap + 2)], np.float32)
    rates = np.zeros(lam_h.shape[0], np.int64)
    for _ in range(bits):
        gain = lam_h * (e[rates] - e[np.minimum(rates + 1, cap + 1)])
        gain = np.where(rates >= cap, -np.inf, gain).astype(np.float32)
        j = int(np.argmax(gain))
        if not gain[j] > 0.0:
            break
        rates[j] += 1
    return U.T @ half, inv_half @ U, jnp.sqrt(lam), rates


def code_tables(rates, cap: int):
    """Per-dimension (edges, centroids) of the standard normal, padded."""
    n = 1 << cap
    edges = np.full((len(rates), n - 1), np.inf, np.float32)
    cents = np.zeros((len(rates), n), np.float32)
    for i, r in enumerate(rates):
        edges[i, : (1 << int(r)) - 1] = gauss_edges(int(r))
        cents[i, : 1 << int(r)] = gauss_centroids(int(r))
    return jnp.asarray(edges), jnp.asarray(cents)


def code_and_decode(scheme, X, cap: int):
    """X (n, d) -> its reconstruction after coding under ``scheme``."""
    T, T_inv, sigma, rates = scheme
    edges, cents = code_tables(rates, cap)
    Z = X @ T.T
    codes = jnp.sum(Z[:, :, None] > (edges * sigma[:, None])[None], axis=-1)
    Zhat = jnp.take_along_axis((cents * sigma[:, None])[None], codes[:, :, None],
                               axis=2)[:, :, 0]
    return Zhat @ T_inv.T


def second_moment(X):
    return X.T @ X / X.shape[0]


# --------------------------------------------------------------------------
# SE kernel, Nyström likelihood and posterior
# --------------------------------------------------------------------------


def se(p, A, B, sqA=None, sqB=None):
    sqA = jnp.sum(A**2, -1) if sqA is None else sqA
    sqB = jnp.sum(B**2, -1) if sqB is None else sqB
    d2 = jnp.maximum(sqA[:, None] + sqB[None, :] - 2.0 * (A @ B.T), 0.0)
    return jnp.exp(p[0]) * jnp.exp(-d2 / jnp.exp(p[1]))


def _chol(M, eps):
    return jnp.linalg.cholesky(M + eps * jnp.eye(M.shape[0], dtype=M.dtype))


def nystrom_nlml(p, Xc, Xcols, y):
    K, N = Xc.shape[0], Xcols.shape[0]
    G_KK = se(p, Xc, Xc)
    G_KN = se(p, Xc, Xcols)
    L = _chol(G_KK, JITTER * jnp.trace(G_KK) / K)
    W = jax.scipy.linalg.solve_triangular(L, G_KN, lower=True)
    s2 = jnp.exp(p[2]) + JITTER
    Lm = _chol(W @ W.T, s2)
    b = jax.scipy.linalg.solve_triangular(Lm, W @ y, lower=True)
    quad = (y @ y - b @ b) / s2
    logdet = (N - K) * jnp.log(s2) + 2.0 * jnp.sum(jnp.log(jnp.diagonal(Lm)))
    return 0.5 * quad + 0.5 * logdet + 0.5 * N * jnp.log(2.0 * jnp.pi)


def train(Xc, Xcols, y, steps: int, lr: float):
    """Adam on the Nyström NLML from a = l2 = 1, noise = 0.1."""
    p0 = jnp.asarray([0.0, 0.0, np.log(0.1)], jnp.float32)
    grad = jax.grad(nystrom_nlml)

    def body(carry, i):
        p, m, v = carry
        g = grad(p, Xc, Xcols, y)
        m = 0.9 * m + 0.1 * g
        v = 0.999 * v + 0.001 * g * g
        t = i + 1.0
        p = p - lr * (m / (1 - 0.9**t)) / (jnp.sqrt(v / (1 - 0.999**t)) + 1e-8)
        return (p, m, v), None

    z = jnp.zeros(3, jnp.float32)
    (p, _, _), _ = jax.lax.scan(body, (p0, z, z),
                                jnp.arange(steps, dtype=jnp.float32))
    return p


def nystrom_posterior(p, Xc, Xcols, sq_cols, y, Xq, valid):
    """Latent predictive (mean, var) at Xq: basis Xc, columns Xcols with the
    squared norms ``sq_cols`` as held by the receiver.  Columns whose
    ``valid`` is 0 are padding: their kernel column is zeroed, so they add
    nothing (this keeps one compiled program for every column count)."""
    K = Xc.shape[0]
    sq_c = jnp.sum(Xc**2, -1)
    G_KK = se(p, Xc, Xc, sq_c, sq_c)
    G_KN = se(p, Xc, Xcols, sq_c, sq_cols) * valid[None, :]
    G_Kq = se(p, Xc, Xq, sq_c)
    L = _chol(G_KK, JITTER * jnp.trace(G_KK) / K)
    W = jax.scipy.linalg.solve_triangular(L, G_KN, lower=True)  # (K, N)
    Bq = jax.scipy.linalg.solve_triangular(L, G_Kq, lower=True)  # (K, t)
    s2 = jnp.exp(p[2]) + JITTER
    Lm = jnp.linalg.cholesky(s2 * jnp.eye(K) + W @ W.T)
    # (W^T W + s2 I)^{-1} = (I - W^T (s2 I + W W^T)^{-1} W) / s2
    Wy = W @ y
    alpha_core = jax.scipy.linalg.cho_solve((Lm, True), Wy)
    G_qN = Bq.T @ W  # (t, N)
    mean = (G_qN @ y - G_qN @ (W.T @ alpha_core)) / s2
    U = W @ W.T
    V = U @ Bq  # W G_Nq
    quad = (jnp.sum(G_qN**2, -1)
            - jnp.sum(V * jax.scipy.linalg.cho_solve((Lm, True), V), 0)) / s2
    var = jnp.exp(p[0]) - quad
    return mean, jnp.maximum(var, 1e-12)


# --------------------------------------------------------------------------
# the protocols
# --------------------------------------------------------------------------


def split(X, y, m: int, key):
    perm = np.asarray(jax.random.permutation(key, X.shape[0]))
    chunks = np.array_split(perm, m)
    return [(jnp.asarray(X[c], jnp.float32), jnp.asarray(y[c], jnp.float32))
            for c in chunks]


@dataclasses.dataclass
class Fit:
    params: object
    parts: list          # exact (X_j, y_j)
    schemes: list        # per machine (T, T_inv, sigma, rates)
    decoded: list        # per machine reconstruction
    cap: int


def fit(cfg: dict, X, y, key, precision: str = "highest") -> Fit:
    with jax.default_matmul_precision(precision):
        return _fit(cfg, X, y, key)


def _fit(cfg, X, y, key):
    m, bits = cfg["m"], cfg["bits_per_sample"]
    cap = min(cfg["max_bits"], bits)
    parts = split(np.asarray(X), np.asarray(y), m, key)
    if cfg["protocol"] != "broadcast":
        raise NotImplementedError("the reference covers the broadcast protocol")
    S = [second_moment(Xj) for Xj, _ in parts]
    schemes, decoded = [], []
    for j, (Xj, _) in enumerate(parts):
        sch = fit_scheme(S[j], sum(S) - S[j], bits, cap)
        schemes.append(sch)
        decoded.append(code_and_decode(sch, Xj, cap))
    X0 = parts[0][0]
    cols = jnp.concatenate([X0] + decoded[1:], axis=0)
    ycols = jnp.concatenate([yj for _, yj in parts])
    p = jax.jit(train, static_argnums=(3, 4))(
        X0, cols, ycols, cfg["steps"], cfg["lr"])
    return Fit(p, parts, schemes, decoded, cap)


def _columns(f: Fit, receiver: int):
    """The columns (points, targets) machine ``receiver`` holds."""
    X = [Xj if j == receiver else f.decoded[j]
         for j, (Xj, _) in enumerate(f.parts)]
    y = [yj for _, yj in f.parts]
    return jnp.concatenate(X), jnp.concatenate(y)


def experts(f: Fit, Xq, precision: str = "highest"):
    """Every machine's latent predictive (mus, vars), each (m, t)."""
    Xq = jnp.asarray(Xq, jnp.float32)
    mus, vs = [], []
    with jax.default_matmul_precision(precision):
        post = jax.jit(nystrom_posterior)
        for i in range(len(f.parts)):
            Xcols, ycols = _columns(f, i)
            n = Xcols.shape[0]
            pad = -n % COLUMN_BUCKET
            valid = jnp.concatenate([jnp.ones(n), jnp.zeros(pad)])
            Xcols = jnp.pad(Xcols, ((0, pad), (0, 0)))
            mu, v = post(f.params, f.parts[i][0], Xcols,
                         jnp.sum(Xcols**2, -1), jnp.pad(ycols, (0, pad)), Xq,
                         valid)
            mus.append(np.asarray(mu, np.float64))
            vs.append(np.asarray(v, np.float64))
    return np.stack(mus), np.stack(vs)


def fuse(mus, vs):
    """KL barycenter (eqs. 63-64) of the experts."""
    mu = mus.mean(0)
    return mu, (vs + (mu[None] - mus) ** 2).mean(0)
