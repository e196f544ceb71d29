"""End-to-end behaviour tests for the full system."""
import os
import subprocess
import sys

import numpy as np
import jax
import pytest

from repro.core import DGPConfig, DistributedGP
from repro.data import regression_dataset, DATASET_SPECS, mnist_like_two_digits
from repro.checkpoint import save_checkpoint, restore_checkpoint, latest_step


def test_checkpoint_roundtrip(tmp_path):
    X, y, _, _ = regression_dataset("abalone", n_train=120)
    art = DistributedGP(DGPConfig(protocol="broadcast", bits_per_sample=24,
                                  steps=2)).fit(X, y, 4)
    tree = {"params": art.params, "factors": art.factors,
            "codes": art.wire.codes}
    assert art.wire.codes.dtype == np.uint32  # the packed wire words
    d = str(tmp_path)
    save_checkpoint(d, 7, tree)
    assert latest_step(d) == 7
    restored = restore_checkpoint(d, 7, tree)
    leaves, restored_leaves = jax.tree.leaves(tree), jax.tree.leaves(restored)
    assert len(leaves) == len(restored_leaves)
    for a, b in zip(leaves, restored_leaves):
        a, b = np.asarray(a), np.asarray(b)
        assert a.dtype == b.dtype
        np.testing.assert_array_equal(a, b)


@pytest.mark.parametrize("name", sorted(DATASET_SPECS))
def test_regression_data_matches_paper_specs(name):
    n_tr, n_te, d = DATASET_SPECS[name]
    X_tr, y_tr, X_te, y_te = regression_dataset(name)
    assert X_tr.shape == (n_tr, d) and X_te.shape == (n_te, d)
    assert y_tr.shape == (n_tr,) and y_te.shape == (n_te,)
    # normalized as in the paper: zero-mean unit-variance inputs
    np.testing.assert_allclose(X_tr.mean(0), 0.0, atol=1e-4)
    np.testing.assert_allclose(X_tr.std(0), 1.0, atol=1e-3)
    assert abs(float(y_tr.mean())) < 1e-4 * max(1.0, np.abs(y_tr).max())


_DUMP = """
import sys
import numpy as np
from repro.data import regression_dataset
np.savez(sys.argv[2], *regression_dataset(sys.argv[1], seed=3, n_train=64))
"""


@pytest.mark.parametrize("name", sorted(DATASET_SPECS))
def test_regression_dataset_is_stable_across_processes(name, tmp_path):
    """The data is a function of (name, seed) alone: two processes with
    different string-hash salts draw the same arrays as this one."""
    here = regression_dataset(name, seed=3, n_train=64)
    src = os.path.join(os.path.dirname(__file__), "..", "src")
    for salt in ("1", "2"):
        path = str(tmp_path / f"{salt}.npz")
        env = dict(os.environ, PYTHONPATH=src, PYTHONHASHSEED=salt)
        out = subprocess.run([sys.executable, "-c", _DUMP, name, path],
                             capture_output=True, text=True, env=env,
                             timeout=120)
        assert out.returncode == 0, out.stderr[-2000:]
        there = np.load(path)
        for i, a in enumerate(here):
            np.testing.assert_array_equal(there[f"arr_{i}"], a)


def test_mnist_like_two_digits():
    six, seven = mnist_like_two_digits(n_per_digit=50, seed=1)
    # one 28x28 image per row, as many of each digit
    assert six.shape == seven.shape == (50, 784)
    assert six.dtype == seven.dtype == np.float32
    again = mnist_like_two_digits(n_per_digit=50, seed=1)
    np.testing.assert_array_equal(again[0], six)
    np.testing.assert_array_equal(again[1], seven)
    other = mnist_like_two_digits(n_per_digit=50, seed=2)
    assert not np.array_equal(other[0], six)
    # digit-specific covariance: the leading principal directions differ
    top = [np.linalg.svd(a, full_matrices=False)[2][0] for a in (six, seven)]
    assert abs(float(top[0] @ top[1])) < 0.5
