"""Exact Gaussian-process regression in JAX (paper §2).

Kernels: the paper's linear kernel (eq. 4) ``k = a x^T x' + b`` and the squared
exponential (eq. 65) ``k = s * exp(-||x-x'||^2 / l^2)``.

Hyperparameters are trained by maximizing the log marginal likelihood with
jax.grad + Adam (gradient-based, as in the paper §5.1).  All linear algebra is
Cholesky-based in float64-free JAX default (float32) but with jitter; set
``jax.config.update('jax_enable_x64', True)`` in experiments needing tighter
conditioning.

Everything here consumes *gram matrices*, so the distributed variants can feed
quantization-estimated grams straight in.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp

from ..spans import span
from .linalg_safe import DEFAULT_JITTER, chol_jittered, chol_safe
from .nystrom import nystrom_nlml
from .registry import KERNELS, KernelSpec, register_kernel

__all__ = [
    "GPParams",
    "linear_gram",
    "se_gram",
    "kernel_from_inner",
    "prior_diag",
    "gram_fn",
    "posterior_factors",
    "posterior_apply",
    "posterior_from_gram",
    "nlml_from_gram",
    "GPModel",
    "make_adam_step",
    "nystrom_from_inner",
    "train_scan",
    "train_trace_count",
    "train_gp",
]


def _inner_products(X, X2, backend: str):
    """X @ X2^T, optionally through the Pallas tiled-gram kernel.

    Every kernel in this module consumes inner products only, so this is the
    single routing point for ``gram_backend``."""
    if backend == "pallas":
        from ..kernels.gram.ops import gram as gram_kernel

        return gram_kernel(X, X2)
    if backend != "xla":
        raise ValueError(f"unknown gram backend {backend!r}")
    return X @ X2.T


class GPParams(NamedTuple):
    """Unconstrained (log-space) hyperparameters.

    linear kernel: a = exp(log_a), b = exp(log_b)
    se kernel:     s = exp(log_a), l^2 = exp(log_b)
    noise:         sigma_eps^2 = exp(log_noise)
    """

    log_a: jnp.ndarray
    log_b: jnp.ndarray
    log_noise: jnp.ndarray


def init_params(a=1.0, b=1.0, noise=0.1) -> GPParams:
    return GPParams(
        log_a=jnp.log(jnp.asarray(a, jnp.float32)),
        log_b=jnp.log(jnp.asarray(b, jnp.float32)),
        log_noise=jnp.log(jnp.asarray(noise, jnp.float32)),
    )


def linear_gram(params: GPParams, X, X2=None, *, backend: str = "xla"):
    """Paper eq. (4): k(x, x') = a <x, x'> + b.  Consumes inner products only."""
    X2 = X if X2 is None else X2
    return jnp.exp(params.log_a) * _inner_products(X, X2, backend) + jnp.exp(params.log_b)


def _sqdist(X, X2, backend: str = "xla"):
    n1 = jnp.sum(X**2, -1, keepdims=True)
    n2 = jnp.sum(X2**2, -1, keepdims=True)
    return jnp.maximum(n1 + n2.T - 2.0 * _inner_products(X, X2, backend), 0.0)


def se_gram(params: GPParams, X, X2=None, *, backend: str = "xla"):
    """Paper eq. (65): k = s exp(-||x - x'||^2 / l^2).

    Note ||x-x'||^2 = |x|^2 + |x'|^2 - 2<x,x'> — also inner-product based, which
    is why the paper's quantized-inner-product machinery covers RBF kernels."""
    X2 = X if X2 is None else X2
    return jnp.exp(params.log_a) * jnp.exp(
        -_sqdist(X, X2, backend) / jnp.exp(params.log_b)
    )


def _linear_from_inner(params: GPParams, ip, sq_x, sq_x2):
    return jnp.exp(params.log_a) * ip + jnp.exp(params.log_b)


def _se_from_inner(params: GPParams, ip, sq_x, sq_x2):
    sq = jnp.maximum(sq_x[:, None] + sq_x2[None, :] - 2.0 * ip, 0.0)
    return jnp.exp(params.log_a) * jnp.exp(-sq / jnp.exp(params.log_b))


def _linear_prior_diag(params: GPParams, sq_x):
    return jnp.exp(params.log_a) * sq_x + jnp.exp(params.log_b)


def _se_prior_diag(params: GPParams, sq_x):
    return jnp.full_like(jnp.asarray(sq_x), jnp.exp(params.log_a))


register_kernel(KernelSpec(
    name="linear", gram=linear_gram,
    from_inner=_linear_from_inner, prior_diag=_linear_prior_diag,
))
register_kernel(KernelSpec(
    name="se", gram=se_gram,
    from_inner=_se_from_inner, prior_diag=_se_prior_diag,
))


def kernel_from_inner(kernel: str, params: GPParams, ip, sq_x, sq_x2):
    """Gram block from precomputed inner products ``ip = X @ X2^T`` and squared
    norms — the form the fused dequantize+gram (qgram) path produces.

    ``kernel`` names a :data:`~repro.core.registry.KERNELS` entry (builtin:
    ``linear`` eq. 4, ``se`` eq. 65; extend with ``register_kernel``)."""
    return KERNELS.get(kernel).from_inner(params, ip, sq_x, sq_x2)


def prior_diag(kernel: str, params: GPParams, sq_x):
    """Prior variances k(x, x) from squared norms: the kernel-diagonal
    special case every predictive needs (linear: a|x|²+b; SE: constant s)."""
    return KERNELS.get(kernel).prior_diag(params, sq_x)


def gram_fn(kernel: str, backend: str = "xla") -> Callable:
    fn = KERNELS.get(kernel).gram
    if backend == "xla":
        return fn
    return functools.partial(fn, backend=backend)


def posterior_factors(G, y, noise_var):
    """Fit-time half of the dense GP predictive: factorize the train gram ONCE
    into ``{"L": chol(G + noise I), "alpha": (G + noise I)^{-1} y}``.
    :func:`posterior_apply` serves any number of query batches from these with
    triangular solves only (the ``FittedProtocol`` serve-path invariant)."""
    n = G.shape[0]
    noise = jnp.asarray(noise_var)
    noise = jnp.broadcast_to(noise, (n,)) if noise.ndim <= 1 else noise
    K = G + jnp.diag(noise + DEFAULT_JITTER)
    # fit-time: jitter already on the diagonal; escalate only if the factor
    # still comes back non-finite (rank-deficient gram)
    L = chol_safe(K)
    alpha = jax.scipy.linalg.cho_solve((L, True), y)
    return {"L": L, "alpha": alpha}


def posterior_apply(factors, G_star_n, g_star_star):
    """Query-time half: O(t n^2) solves against cached :func:`posterior_factors`
    — no Cholesky factorization."""
    mean = G_star_n @ factors["alpha"]
    V = jax.scipy.linalg.solve_triangular(factors["L"], G_star_n.T, lower=True)
    var = g_star_star - jnp.sum(V**2, axis=0)
    return mean, jnp.maximum(var, 1e-12)


def posterior_from_gram(G, G_star_n, g_star_star, y, noise_var):
    """Posterior mean/variance given gram blocks (paper eqs. 2-3; eq. 3's sign
    typo fixed: the data term is SUBTRACTED).

    G: (n, n) train gram; G_star_n: (t, n) test-train; g_star_star: (t,) prior
    variances at test points; y: (n,); noise_var: scalar or per-point (n,)
    (heteroscedastic, used by pseudo-point aggregation).
    Returns (mean (t,), var (t,))."""
    return posterior_apply(
        posterior_factors(G, y, noise_var), G_star_n, g_star_star
    )


def nlml_from_gram(G, y, noise_var):
    """Negative log marginal likelihood -log N(y | 0, G + sigma^2 I)."""
    n = G.shape[0]
    # differentiated (training loss): one-shot jitter — while_loop escalation
    # has no reverse-mode rule
    L = chol_jittered(G, noise_var + DEFAULT_JITTER)
    alpha = jax.scipy.linalg.cho_solve((L, True), y)
    return (
        0.5 * y @ alpha
        + jnp.sum(jnp.log(jnp.diagonal(L)))
        + 0.5 * n * jnp.log(2.0 * jnp.pi)
    )


@dataclasses.dataclass
class GPModel:
    """A trained GP bound to (possibly reconstructed/quantized) inputs."""

    kernel: str
    params: GPParams
    X: jnp.ndarray | None  # None where the caller trained on a gram hook alone
    y: jnp.ndarray
    gram_backend: str = "xla"

    def predict(self, X_star):
        k = gram_fn(self.kernel, self.gram_backend)
        G = k(self.params, self.X)
        G_sn = k(self.params, X_star, self.X)
        g_ss = jnp.diagonal(k(self.params, X_star, X_star))
        return posterior_from_gram(
            G, G_sn, g_ss, self.y, jnp.exp(self.params.log_noise)
        )

    def nlml(self):
        G = gram_fn(self.kernel, self.gram_backend)(self.params, self.X)
        return nlml_from_gram(G, self.y, jnp.exp(self.params.log_noise))


def make_adam_step(loss: Callable, lr: float) -> Callable:
    """One Adam update ``step(i, params, m, v) -> (params, m, v)`` for the
    given scalar loss — minimal inline Adam (repro.optim is for the NN stack;
    keep core standalone).  Shared by both of train_gp's programs."""
    b1, b2, eps = 0.9, 0.999, 1e-8

    def step(i, p, m, v):
        g = jax.grad(loss)(p)
        m = jax.tree.map(lambda a, b: b1 * a + (1 - b1) * b, m, g)
        v = jax.tree.map(lambda a, b: b2 * a + (1 - b2) * b * b, v, g)
        t = i + 1.0
        mh = jax.tree.map(lambda a: a / (1 - b1**t), m)
        vh = jax.tree.map(lambda a: a / (1 - b2**t), v)
        p = jax.tree.map(lambda a, mm, vv: a - lr * mm / (jnp.sqrt(vv) + eps), p, mh, vh)
        return p, m, v

    return step


# Incremented INSIDE the traced bodies of the training programs, so it counts
# traces, not calls: fits of same-shaped data leave it flat.
_TRAIN_TRACES = [0]


def train_trace_count() -> int:
    """How many times this process has traced a training program
    (:func:`train_scan`, or the ``impl="loop"`` step) — a fit of data
    shaped like an earlier fit's leaves it unchanged."""
    return _TRAIN_TRACES[0]


def nystrom_from_inner(p: GPParams, operands, kernel: str):
    """The Nyström gram hook: the pair ``(G_KK, G_KN)`` from the inner
    products ``ip_KK`` (K, K) and ``ip_KN`` (K, N) and the squared norms
    ``sq_K`` (K,) and ``sq_N`` (N,) held in ``operands``."""
    sq_K = operands["sq_K"]
    return (kernel_from_inner(kernel, p, operands["ip_KK"], sq_K, sq_K),
            kernel_from_inner(kernel, p, operands["ip_KN"], sq_K, operands["sq_N"]))


def _dense_xla(p: GPParams, operands, kernel: str):
    return gram_fn(kernel)(p, operands["X"])


def _dense_pallas(p: GPParams, operands, kernel: str):
    return gram_fn(kernel, "pallas")(p, operands["X"])


# the gram hook of a fit with none given: the kernel on ``operands["X"]``
_DENSE_GRAMS = {"xla": _dense_xla, "pallas": _dense_pallas}


def _train_loss(p: GPParams, operands, kernel: str, gram: Callable):
    """Negative log marginal likelihood of ``operands["y"]`` under the gram
    ``gram(p, operands, kernel)``; a Nyström pair ``(G_KK, G_KN)`` trains on
    the completed gram (eq. 61) without forming the N x N matrix."""
    G = gram(p, operands, kernel)
    noise = jnp.exp(p.log_noise)
    if isinstance(G, tuple):
        return nystrom_nlml(*G, operands["y"], noise)
    return nlml_from_gram(G, operands["y"], noise)


@functools.partial(jax.jit, static_argnames=("kernel", "gram", "steps", "lr"))
def train_scan(p, m, v, operands, *, kernel: str, gram: Callable, steps: int,
               lr: float):
    """``steps`` Adam updates of the hyperparameters as ONE program (a
    ``lax.scan``); the data are arguments, so every fit of same-shaped data
    under the same static configuration reuses the compiled program."""
    _TRAIN_TRACES[0] += 1  # runs at trace time only
    step = make_adam_step(
        lambda q: _train_loss(q, operands, kernel, gram), lr)

    def body(carry, i):
        return step(i, *carry), None

    (p, m, v), _ = jax.lax.scan(
        body, (p, m, v), jnp.arange(steps, dtype=jnp.float32))
    return p, m, v


@functools.partial(jax.jit, static_argnames=("kernel", "gram", "lr"))
def _train_step(i, p, m, v, operands, *, kernel: str, gram: Callable,
                lr: float):
    """One Adam update: the ``impl="loop"`` baseline's program."""
    _TRAIN_TRACES[0] += 1  # runs at trace time only
    return make_adam_step(
        lambda q: _train_loss(q, operands, kernel, gram), lr)(i, p, m, v)


def train_gp(
    X,
    y,
    kernel: str = "se",
    params: GPParams | None = None,
    steps: int = 200,
    lr: float = 0.05,
    gram: Callable | None = None,
    operands: dict | None = None,
    impl: str = "scan",
    gram_backend: str = "xla",
) -> GPModel:
    """Maximize marginal likelihood with Adam.

    With no ``gram`` the loss is the GP's on the points ``X`` (through
    ``gram_backend``'s inner products: ``"pallas"`` uses the tiled kernel,
    differentiable via its custom VJP).  Distributed variants train on a gram
    they assemble themselves (e.g. from quantized inner products): ``gram``
    is a pure, module-level function ``gram(p, operands, kernel) -> G`` and
    ``operands`` a dict of the arrays it reads (``y`` joins it under
    ``"y"``).  A ``gram`` that returns the Nyström pair ``(G_KK, G_KN)``
    trains on the completed gram (eq. 61) through
    :func:`~repro.core.nystrom.nystrom_nlml`, never forming the N x N matrix
    (:func:`nystrom_from_inner` is that hook over inner products).

    The training program is jitted once per shape: ``gram``, ``kernel``,
    ``steps`` and ``lr`` are its static arguments and the operands its
    arguments, so a later fit of same-shaped data, new data included, reuses
    it.  A closure over arrays, or any function object made anew per call,
    would be a new static argument each time and rebuild the program in
    every fit, with the data baked in as constants: so ``gram`` must not be
    one.  :func:`train_trace_count` counts the builds; after each call the
    marker span ``repro.fit.train.program`` records, as ``built``, how many
    this call made.

    ``impl="scan"`` (default) runs the whole optimizer loop as ONE compiled
    ``jax.lax.scan`` program (:func:`train_scan`) — one dispatch for all
    ``steps``.  ``impl="loop"`` dispatches one jitted step per iteration
    (O(steps) host round-trips); it exists as the baseline for
    benchmarks/hotpath_bench.py."""
    if gram is None:
        if gram_backend not in _DENSE_GRAMS:
            raise ValueError(f"unknown gram backend {gram_backend!r}")
        X = jnp.asarray(X)
        gram, operands = _DENSE_GRAMS[gram_backend], {"X": X}
    y = jnp.asarray(y)
    operands = dict(operands, y=y)
    if impl not in ("scan", "loop"):
        raise ValueError(f"unknown train impl {impl!r}")
    params = params or init_params()
    m = jax.tree.map(jnp.zeros_like, params)
    v = jax.tree.map(jnp.zeros_like, params)
    static = dict(kernel=kernel, gram=gram, lr=lr)

    if steps:  # with none, no optimizer program is built or run
        built = _TRAIN_TRACES[0]
        if impl == "loop":
            for i in range(steps):
                params, m, v = _train_step(jnp.float32(i), params, m, v,
                                           operands, **static)
        else:
            params, m, v = train_scan(params, m, v, operands, steps=steps,
                                      **static)
        with span("fit.train.program", built=_TRAIN_TRACES[0] - built):
            pass
    return GPModel(kernel=kernel, params=params, X=X, y=y, gram_backend=gram_backend)
