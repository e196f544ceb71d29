"""GP substrate tests: posterior correctness, training, Nyström."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core.gp import (
    init_params, linear_gram, se_gram, posterior_from_gram, nlml_from_gram, train_gp,
)
from repro.core.nystrom import nystrom_complete, nystrom_nlml, nystrom_posterior


def test_posterior_matches_naive_formula():
    rng = np.random.default_rng(0)
    n, t, d = 30, 7, 3
    X = rng.normal(size=(n, d)).astype(np.float32)
    Xs = rng.normal(size=(t, d)).astype(np.float32)
    y = rng.normal(size=n).astype(np.float32)
    p = init_params(a=0.7, b=0.2, noise=0.3)
    G = np.asarray(se_gram(p, jnp.asarray(X)), np.float64)
    Gsn = np.asarray(se_gram(p, jnp.asarray(Xs), jnp.asarray(X)), np.float64)
    gss = np.asarray(se_gram(p, jnp.asarray(Xs)), np.float64).diagonal()
    K = G + 0.3 * np.eye(n)
    mean_ref = Gsn @ np.linalg.solve(K, y)
    var_ref = gss - np.einsum("tn,nm,tm->t", Gsn, np.linalg.inv(K), Gsn)
    mean, var = posterior_from_gram(
        jnp.asarray(G, jnp.float32), jnp.asarray(Gsn, jnp.float32),
        jnp.asarray(gss, jnp.float32), jnp.asarray(y), 0.3,
    )
    np.testing.assert_allclose(np.asarray(mean), mean_ref, rtol=2e-3, atol=2e-3)
    np.testing.assert_allclose(np.asarray(var), var_ref, rtol=2e-2, atol=2e-3)


def test_nlml_matches_gaussian_logpdf():
    rng = np.random.default_rng(1)
    n = 20
    X = rng.normal(size=(n, 2)).astype(np.float32)
    y = rng.normal(size=n).astype(np.float32)
    p = init_params()
    G = np.asarray(linear_gram(p, jnp.asarray(X)), np.float64)
    K = G + np.exp(float(p.log_noise)) * np.eye(n)
    sign, logdet = np.linalg.slogdet(K)
    ref = 0.5 * (y @ np.linalg.solve(K, y) + logdet + n * np.log(2 * np.pi))
    val = float(nlml_from_gram(jnp.asarray(G, jnp.float32), jnp.asarray(y), np.exp(float(p.log_noise))))
    assert val == pytest.approx(ref, rel=1e-3)


def test_training_reduces_nlml_and_fits():
    rng = np.random.default_rng(2)
    n, d = 150, 4
    X = rng.normal(size=(n, d)).astype(np.float32)
    y = (np.sin(X @ np.ones(d)) + 0.05 * rng.normal(size=n)).astype(np.float32)
    m0 = train_gp(X, y, kernel="se", steps=0)
    m1 = train_gp(X, y, kernel="se", steps=150)
    assert float(m1.nlml()) < float(m0.nlml())
    mu, var = m1.predict(X[:20])
    assert np.mean((np.asarray(mu) - y[:20]) ** 2) < 0.1 * np.var(y)
    assert np.all(np.asarray(var) > 0)


def test_nystrom_exact_on_first_block_and_lowrank():
    rng = np.random.default_rng(3)
    n, K_, d = 40, 20, 10  # linear gram rank <= d+1 = 11 < K: Nyström ~exact
    X = rng.normal(size=(n, d)).astype(np.float32)
    p = init_params(a=1.0, b=0.1, noise=0.1)
    G = np.asarray(linear_gram(p, jnp.asarray(X)), np.float64)  # rank <= d+1
    Gh = np.asarray(nystrom_complete(
        jnp.asarray(G[:K_, :K_], jnp.float32), jnp.asarray(G[:K_, :], jnp.float32)))
    np.testing.assert_allclose(Gh[:K_, :], G[:K_, :], rtol=2e-3, atol=2e-3)
    # linear-kernel gram has rank <= d+1 <= K: Nyström is (nearly) exact
    np.testing.assert_allclose(Gh, G, rtol=3e-2, atol=3e-2)


def test_nystrom_posterior_equals_dense_path():
    rng = np.random.default_rng(4)
    n, K_, t, d = 50, 20, 6, 4
    X = rng.normal(size=(n, d)).astype(np.float32)
    Xs = rng.normal(size=(t, d)).astype(np.float32)
    y = rng.normal(size=n).astype(np.float32)
    p = init_params(noise=0.2)
    k = lambda A, B=None: se_gram(p, jnp.asarray(A), None if B is None else jnp.asarray(B))
    G_KK = k(X[:K_])
    G_KN = k(X[:K_], X)
    Ghat = nystrom_complete(G_KK, G_KN)
    from repro.core.gp import posterior_from_gram
    G_sK = k(Xs, X[:K_])
    # dense reference: G_*N from the same Nyström map
    L = np.linalg.cholesky(np.asarray(G_KK, np.float64) + 1e-6 * np.trace(np.asarray(G_KK)) / K_ * np.eye(K_))
    W = np.linalg.solve(L, np.asarray(G_KN, np.float64))
    GsN = np.linalg.solve(L, np.asarray(G_sK, np.float64).T).T @ W
    gss = np.asarray(k(Xs)).diagonal()
    mu_ref, var_ref = posterior_from_gram(
        jnp.asarray(Ghat), jnp.asarray(GsN, jnp.float32), jnp.asarray(gss), jnp.asarray(y), 0.2)
    mu, var = nystrom_posterior(G_KK, G_KN, jnp.asarray(y), 0.2, G_sK, jnp.asarray(gss))
    np.testing.assert_allclose(np.asarray(mu), np.asarray(mu_ref), rtol=1e-2, atol=1e-2)
    np.testing.assert_allclose(np.asarray(var), np.asarray(var_ref), rtol=5e-2, atol=1e-2)


@pytest.mark.parametrize("noise", [1e-2, 0.3])
def test_nystrom_nlml_equals_dense_completed_nlml(noise):
    """The woodbury training loss is the dense NLML of the completed gram,
    value and gradient (checked in float64, where the two orderings of the
    same algebra agree to rounding)."""
    jax.config.update("jax_enable_x64", True)
    try:
        rng = np.random.default_rng(3)
        N, K, d = 60, 12, 3
        X = rng.normal(size=(N, d))
        y = rng.normal(size=N)

        def grams(log_ls):
            p = init_params(1.0, 1.0, 0.1)._replace(log_b=log_ls)
            Xc = jnp.asarray(X[:K])
            return se_gram(p, Xc), se_gram(p, Xc, jnp.asarray(X))

        dense = lambda ls: nlml_from_gram(nystrom_complete(*grams(ls)),
                                          jnp.asarray(y), noise)
        wood = lambda ls: nystrom_nlml(*grams(ls), jnp.asarray(y), noise)
        ls = jnp.asarray(0.4, jnp.float64)
        np.testing.assert_allclose(float(wood(ls)), float(dense(ls)),
                                   rtol=1e-9)
        np.testing.assert_allclose(float(jax.grad(wood)(ls)),
                                   float(jax.grad(dense)(ls)), rtol=1e-6)
    finally:
        jax.config.update("jax_enable_x64", False)
