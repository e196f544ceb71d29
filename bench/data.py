"""Seeded inputs of the benchmark: the datasets and the seeds derived from a
run's ``--seed``.

``regression_dataset`` is a copy of the synthetic generator of the program
(``repro.data.synthetic``), kept here so that the benchmark's inputs do not
move when the program changes.  The paper's datasets are matched in input
dimension and target character: kin40k is d=8 with a high-frequency target.
"""
from __future__ import annotations

import zlib

import numpy as np

DATASET_SPECS = {
    # name: (n_train, n_test, d) as in the paper §6
    "sarcos": (1000, 4449, 21),
    "kin40k": (1000, 30000, 8),
    "abalone": (1000, 1044, 8),
}


def regression_dataset(name: str, seed: int, n_train: int | None = None):
    """(X_train, y_train, X_test, y_test) float32: inputs standardized on the
    training rows, targets centered; a function of (name, seed) alone."""
    spec_train, n_test, d = DATASET_SPECS[name]
    n_train = spec_train if n_train is None else int(n_train)
    rng = np.random.default_rng((zlib.crc32(name.encode()) & 0xFFFF, seed))
    freq, feats = {"kin40k": (4.0, 64), "sarcos": (2.0, 16),
                   "abalone": (1.0, 8)}[name]
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
    return (X_tr.astype(np.float32), (y_tr - ym).astype(np.float32),
            X_te.astype(np.float32), (y_te - ym).astype(np.float32))


def derived_seeds(seed: int, n: int) -> list[int]:
    """``n`` 31-bit seeds derived from any whole ``seed`` (the run's
    ``--seed`` may exceed 32 bits)."""
    ss = np.random.SeedSequence(int(seed))
    return [int(s) & 0x7FFFFFFF for s in ss.generate_state(n, np.uint32)]
