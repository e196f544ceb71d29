"""What the drivers share: building the program's estimator from a
configuration file, the seeded data of a run, and the comparison of the
program's answers with the plain reference (``reference.py``)."""
from __future__ import annotations

import dataclasses

import numpy as np

from . import data as bdata
from . import reference

# DGPConfig fields a configuration file may set
_DGP_KEYS = ("protocol", "scheme", "kernel", "fusion", "impl", "gram_backend",
             "gram_mode", "bits_per_sample", "max_bits", "steps", "lr")


def estimator(cfg: dict):
    from repro.core import DGPConfig, DistributedGP

    return DistributedGP(DGPConfig(**{k: cfg[k] for k in _DGP_KEYS}))


@dataclasses.dataclass
class Dataset:
    X: np.ndarray
    y: np.ndarray
    X_test: np.ndarray
    y_test: np.ndarray
    split_seed: int

    def key(self):
        import jax

        return jax.random.PRNGKey(self.split_seed)


def dataset(cfg: dict, seed: int) -> Dataset:
    """The training set and held-out pool of one seed, and the seed of its
    machine split."""
    data_seed, split_seed = bdata.derived_seeds(seed, 2)
    X, y, Xt, yt = bdata.regression_dataset(cfg["dataset"], data_seed,
                                            cfg["n_train"])
    return Dataset(X, y, Xt, yt, split_seed)


def query_rows(ds: Dataset, rng, batches: int, batch: int) -> np.ndarray:
    """``batches`` disjoint batches of held-out rows, (batches, batch, d)."""
    idx = rng.choice(ds.X_test.shape[0], size=batches * batch, replace=False)
    return ds.X_test[idx].reshape(batches, batch, -1)


def reference_fit(cfg: dict, ds: Dataset, precision: str = "highest"):
    return reference.fit(cfg, ds.X, ds.y, ds.key(), precision)


def reference_answers(ref, Xq, precision: str = "highest"):
    """The reference's (mean, latent variance) at query rows Xq (n, d)."""
    return reference.fuse(*reference.experts(ref, Xq, precision))


def gaps(mu, var, mu_ref, var_ref, y_scale: float, prior_var: float) -> dict:
    """How far answers lie from the reference's: the widest gap of the means
    as a share of the targets' standard deviation, and of the latent
    variances as a share of the reference's prior variance."""
    mu, var = np.asarray(mu, np.float64).ravel(), np.asarray(var, np.float64).ravel()
    return {
        "mean_gap": float(np.max(np.abs(mu - np.ravel(mu_ref))) / y_scale),
        "var_gap": float(np.max(np.abs(var - np.ravel(var_ref))) / prior_var),
    }
