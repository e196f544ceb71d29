"""Deterministic synthetic data generators.

* ``lm_batch_stream`` — token batches for the transformer drivers (Zipf-ish
  marginal + Markov bigram structure so the loss has signal).
* ``regression_dataset`` — GP-regression datasets statistically matched to the
  paper's benchmarks (same n/d/noise regime); real files are used instead when
  present (benchmarks pass --data-dir).
* ``mnist_like_two_digits`` — two-cluster high-dim image-like data for the
  Fig. 3c/d PCA comparison (28x28, digit-dependent covariance).
"""
from __future__ import annotations

import dataclasses
import zlib

import numpy as np
import jax
import jax.numpy as jnp

DATASET_SPECS = {
    # name: (n_train, n_test, d) as in the paper §6
    "sarcos": (1000, 4449, 21),
    "kin40k": (1000, 30000, 8),
    "abalone": (1000, 1044, 8),
}


def lm_batch_stream(vocab_size: int, batch: int, seq: int, seed: int = 0):
    """Infinite deterministic stream of (tokens, labels) int32 batches."""
    rng = np.random.default_rng(seed)
    # fixed random bigram preference: tok -> preferred successor
    succ = rng.integers(0, vocab_size, size=vocab_size)
    step = 0
    while True:
        r = np.random.default_rng((seed, step))
        toks = np.empty((batch, seq + 1), dtype=np.int64)
        toks[:, 0] = r.zipf(1.3, size=batch) % vocab_size
        noise = r.random((batch, seq))
        rand_next = r.integers(0, vocab_size, size=(batch, seq))
        for t in range(seq):
            follow = succ[toks[:, t]]
            toks[:, t + 1] = np.where(noise[:, t] < 0.65, follow, rand_next[:, t])
        yield {
            "tokens": jnp.asarray(toks[:, :-1], jnp.int32),
            "labels": jnp.asarray(toks[:, 1:], jnp.int32),
        }
        step += 1


def regression_dataset(name: str, seed: int = 0, data_dir: str | None = None,
                       n_train: int | None = None):
    """(X_train, y_train, X_test, y_test) float32, normalized like the paper:
    inputs zero-mean unit-variance, targets centered.  ``n_train`` overrides
    the paper's training size (the dataset's input shape and target law are
    unchanged); the data is a function of (name, seed) alone."""
    if data_dir is not None:
        loaded = _try_load_real(name, data_dir)
        if loaded is not None:
            return loaded
    spec_train, n_test, d = DATASET_SPECS[name]
    n_train = spec_train if n_train is None else int(n_train)
    # crc32, not hash(): str hashes are salted per process
    rng = np.random.default_rng((zlib.crc32(name.encode()) & 0xFFFF, seed))
    # anisotropic inputs (random covariance); target roughness matched to the
    # real dataset's character (KIN40K is famously high-frequency/nonlinear,
    # SARCOS moderately smooth, ABALONE nearly monotone)
    freq, feats = {"kin40k": (4.0, 64), "sarcos": (2.0, 16), "abalone": (1.0, 8)}[name]
    A = rng.normal(size=(d, d)) / np.sqrt(d)
    Xall = rng.normal(size=(n_train + n_test, d)) @ A.T
    W1 = rng.normal(size=(d, feats)) / np.sqrt(d)
    w2 = rng.normal(size=feats)
    f = np.tanh(Xall @ W1) @ w2 + 0.3 * np.sin(freq * Xall @ W1[:, 0])
    y = f + 0.05 * np.std(f) * rng.normal(size=f.shape[0])
    X_tr, X_te = Xall[:n_train], Xall[n_train:]
    y_tr, y_te = y[:n_train], y[n_train:]
    mu, sd = X_tr.mean(0), X_tr.std(0) + 1e-9
    X_tr = (X_tr - mu) / sd
    X_te = (X_te - mu) / sd
    ym = y_tr.mean()
    return (
        X_tr.astype(np.float32), (y_tr - ym).astype(np.float32),
        X_te.astype(np.float32), (y_te - ym).astype(np.float32),
    )


def _try_load_real(name: str, data_dir: str):
    import os

    path = os.path.join(data_dir, f"{name}.npz")
    if not os.path.exists(path):
        return None
    z = np.load(path)
    return (z["X_train"], z["y_train"], z["X_test"], z["y_test"])


def mnist_like_two_digits(n_per_digit: int = 1000, seed: int = 0):
    """Two 784-dim clusters with digit-specific low-rank covariance — the
    Fig. 3c/d setting (digit 6 on machine 1, digit 7 on machine 2)."""
    rng = np.random.default_rng(seed)
    d = 784

    def digit(k):
        basis = rng.normal(size=(d, 30)) / np.sqrt(d)
        scales = np.geomspace(5.0, 0.1, 30)
        z = rng.normal(size=(n_per_digit, 30)) * scales
        return (z @ basis.T + 0.05 * rng.normal(size=(n_per_digit, d))).astype(np.float32)

    return digit(6), digit(7)
