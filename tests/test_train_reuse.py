"""One compiled training program per shape: ``train_gp`` passes the data to
its jitted program as arguments, so fits of new data shaped like an earlier
fit's reuse the program (``gp.train_trace_count`` stays flat), and machine
0's training inputs in a broadcast fit come from one jitted assembly."""
import jax
import numpy as np
import pytest

from repro.core import DGPConfig, DistributedGP
from repro.core.gp import train_trace_count
from repro.core.protocols import broadcast

SMALL = dict(bits_per_sample=8, steps=8)


def _data(n, seed, d=4):
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(n, d)).astype(np.float32)
    return X, (np.sin(X.sum(1)) + 0.1 * rng.normal(size=n)).astype(np.float32)


def _fit(est, n, seed):
    art = est.fit(*_data(n, seed), 4, key=jax.random.PRNGKey(seed))
    return np.asarray(jax.tree_util.tree_leaves(art.params))


@pytest.mark.parametrize("protocol", ["broadcast", "center", "poe"])
def test_same_shaped_new_data_reuses_the_training_program(protocol):
    """n=483 over m=4 splits 121/121/121/120; n=487 splits 122/122/122/121,
    a new shape for machine 0 in every protocol."""
    est = DistributedGP(DGPConfig(protocol=protocol, **SMALL))
    jax.clear_caches()
    c0 = train_trace_count()
    _fit(est, 483, seed=1)
    second = _fit(est, 483, seed=2)
    assert train_trace_count() - c0 == 1
    _fit(est, 487, seed=3)
    assert train_trace_count() - c0 == 2

    jax.clear_caches()
    fresh = _fit(est, 483, seed=2)
    assert train_trace_count() - c0 == 3
    np.testing.assert_allclose(second, fresh, rtol=1e-6)


def _eager_operands0(ip_own, ip_peers, sq_own, sq_dec, y, lengths):
    """The per-block concatenation the jitted assembly replaces, in numpy."""
    n0, m = lengths[0], len(lengths)
    ip_KK = ip_own[:n0, :n0]
    ip_KN = np.concatenate(
        [ip_KK] + [ip_peers[j][: lengths[j], :n0].T for j in range(1, m)], axis=1)
    sq_K = sq_own[:n0]
    sq_N = np.concatenate([sq_K] + [sq_dec[j][: lengths[j]] for j in range(1, m)])
    y0 = np.concatenate([y[j][: lengths[j]] for j in range(m)])
    return {"ip_KK": ip_KK, "ip_KN": ip_KN, "sq_K": sq_K, "sq_N": sq_N, "y": y0}


@pytest.mark.parametrize("path", ["batched", "mesh"])
def test_machine0_assembly_matches_the_eager_concatenation(path):
    lengths, n_pad, d = (5, 3, 4, 5), 5, 3
    m = len(lengths)
    rng = np.random.default_rng(0)
    X = rng.normal(size=(m, n_pad, d)).astype(np.float32)
    dec = (X + 0.1 * rng.normal(size=X.shape)).astype(np.float32)
    y = rng.normal(size=(m, n_pad)).astype(np.float32)
    sq_exact, sq_dec = (X**2).sum(-1), (dec**2).sum(-1)
    ip_own = X[0] @ X[0].T
    ip_peers = np.einsum("jnd,md->jnm", dec, X[0])
    if path == "batched":
        A = np.einsum("ind,imd->inm", X, X)
        B0 = np.einsum("jnd,md->jnm", dec, X[0])  # machine 0's wire column
        got = broadcast._train_operands0(A, B0, sq_exact, sq_dec, y,
                                         lengths=lengths)
    else:
        got = broadcast._mesh_train_operands0(X, dec, sq_exact, sq_dec, y,
                                              lengths=lengths)
    want = _eager_operands0(ip_own, ip_peers, sq_exact[0], sq_dec, y, lengths)
    assert set(got) == set(want)
    assert got["ip_KN"].shape == (lengths[0], sum(lengths))
    for k in want:
        np.testing.assert_allclose(np.asarray(got[k]), want[k], rtol=1e-5,
                                   atol=1e-5, err_msg=k)
