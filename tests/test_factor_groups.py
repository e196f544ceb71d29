"""The broadcast factor build in groups of receivers
(``broadcast.broadcast_factor_group``): one program that builds ``group``
receivers' Nyström factors into the donated artifact, called once per group,
each group's wire products computed inside it, the group size chosen from
the device's memory (``broadcast.factor_group_size``).

Locked here: every group size gives the artifact of the one-group build and
agrees with the serial host oracle at an uneven split with two-word packed
rows; the group size the v5e's memory gives at the paper's two deployments;
no value in the build program larger than its group's views; and a refit of
same-shaped data traces nothing new."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis.jaxpr_walk import walk_jaxpr
from repro.comm.accounting import row_bits
from repro.core import DGPConfig, DistributedGP
from repro.core.gp import GPParams
from repro.core.protocols import broadcast
from repro.core.protocols.base import WireState

# m=7 machines over 103 points: shards of 15 and 14 rows (padded rows masked);
# d=21 at R=64 bits per sample packs each row into two 32-bit words
M, N, D, R = 7, 103, 21, 64
CFG = dict(protocol="broadcast", bits_per_sample=R, steps=5)
# bytes_limit that memory_stats() reports for one TPU v5e chip
V5E_BYTES_LIMIT = 16_909_336_064


def _data(seed, n=N):
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(n, D)).astype(np.float32)
    y = (np.sin(X[:, :3].sum(1)) + 0.05 * rng.normal(size=n)).astype(np.float32)
    Xt = rng.normal(size=(16, D)).astype(np.float32)
    return X, y, Xt


def _fit_with_group(monkeypatch, group, **cfg):
    monkeypatch.setattr(broadcast, "factor_group_size",
                        lambda m, n_pad, limit: group)
    X, y, Xt = _data(0)
    est = DistributedGP(DGPConfig(gram_backend="pallas", **CFG, **cfg))
    art = est.fit(X, y, M, key=jax.random.PRNGKey(0))
    return est, art, Xt


@pytest.fixture(scope="module")
def one_group():
    """The single-group build (k = m) and the host oracle's fit of the same
    split."""
    X, y, Xt = _data(0)
    key = jax.random.PRNGKey(0)
    est = DistributedGP(DGPConfig(gram_backend="pallas", **CFG))
    art = est.fit(X, y, M, key=key)
    host = DistributedGP(DGPConfig(impl="host", train_impl="loop", **CFG))
    oracle = host.fit(X, y, M, key=key)
    return art, est.predict(art, Xt), oracle.predict(Xt), oracle


def test_the_split_is_uneven_with_two_word_rows(one_group):
    art = one_group[0]
    assert sorted(set(art.fit_lengths)) == [14, 15]
    assert row_bits(R, D, art.max_bits) == 64
    assert art.wire.codes.shape[-1] == 2


@pytest.mark.parametrize("group", [1, 3, M])
def test_grouped_build_matches_one_group_and_the_host_oracle(
        monkeypatch, one_group, group):
    """Group sizes 1, 3 (three groups, the last shifted back over the one
    before) and m.  Against the one-group build: the same float32 operations
    on the same inputs per receiver, batched differently, so equal to float32
    rounding (rtol 1e-5 of each factor).  Against the host oracle: its wire
    decodes in float64 scipy and its predict solves each dense view, so the
    tolerance on the fused answers is test_conformance's broadcast one
    (5e-3); the three ledgers are integers and equal."""
    art1, (mu1, v1), (mu_h, v_h), oracle = one_group
    est, art, Xt = _fit_with_group(monkeypatch, group)
    assert set(art.factors) == set(art1.factors)
    for k in art1.factors:
        assert art.factors[k].shape == art1.factors[k].shape
        scale = float(jnp.max(jnp.abs(art1.factors[k]))) or 1.0
        np.testing.assert_allclose(np.asarray(art.factors[k]),
                                   np.asarray(art1.factors[k]),
                                   rtol=1e-5, atol=1e-5 * scale, err_msg=k)
    mu, v = est.predict(art, Xt)
    np.testing.assert_allclose(np.asarray(mu), np.asarray(mu1), atol=1e-5)
    np.testing.assert_allclose(np.asarray(v), np.asarray(v1), atol=1e-5)
    np.testing.assert_allclose(np.asarray(mu), np.asarray(mu_h), atol=5e-3)
    np.testing.assert_allclose(np.asarray(v), np.asarray(v_h), atol=5e-3)
    assert art.wire_bits == oracle.wire_bits
    assert art.payload_bits == oracle.payload_bits
    assert art.integrity_bits == oracle.integrity_bits


@pytest.mark.parametrize("m,n_pad,want", [
    (40, 250, 40),   # kin40k: 10,000 points, one group of every receiver
    (40, 1113, 4),   # sarcos: 44,484 points, ten groups of four
])
def test_group_size_at_the_papers_deployments(m, n_pad, want):
    assert broadcast.factor_group_size(m, n_pad, V5E_BYTES_LIMIT) == want


def test_group_size_limits():
    m, n_pad = 40, 1113
    # no limit to read (the CPU): one group
    assert broadcast.factor_group_size(m, n_pad, None) == m
    # less than one receiver's views beside the artifact: one at a time
    assert broadcast.factor_group_size(m, n_pad, 1e9) == 1
    # a limit that admits 7 receivers: six groups, balanced to 7 (the last
    # one shifted back by two), never 6 x 7 = 42 receivers' worth of groups
    N = m * n_pad
    resident = 4 * m * (n_pad * N + 4 * n_pad * n_pad + N + n_pad)
    per = 4 * broadcast._VIEW_COPIES * n_pad * N
    limit = (resident + 7 * per + per // 2) / (1 - broadcast._HEADROOM)
    assert broadcast.factor_group_size(m, n_pad, limit) == 7
    # 9 admitted: five groups of 8, not 4 of 9 and one of 4
    limit = (resident + 9 * per + per // 2) / (1 - broadcast._HEADROOM)
    assert broadcast.factor_group_size(m, n_pad, limit) == 8


def test_build_program_holds_no_more_than_its_groups_views():
    """Every value of the group program has at most group * n_pad * m *
    n_pad elements, except the artifact's ``W`` rows (m, n_pad, m * n_pad)
    the group is written into: no (m, m, n_pad, n_pad) products, no m
    views."""
    m, n_pad, d, group = 6, 5, 3, 2
    bound = group * n_pad * m * n_pad
    f32 = functools.partial(jnp.zeros, dtype=jnp.float32)
    wire = WireState(
        jnp.zeros((m, n_pad, 1), jnp.uint32), f32((m, n_pad, d)),
        f32((m, d, d)), jnp.full((m, d), 4, jnp.int32), f32((m, d)),
        f32((m, d, 1 << 12)), f32((m, d, d)))
    p = GPParams(jnp.float32(0.0), jnp.float32(0.0), jnp.float32(-2.0))
    args = (p, f32((m, n_pad, n_pad)), f32((m, n_pad, d)), f32((m, n_pad)),
            wire, f32((m, n_pad)), f32((m, n_pad)), f32((m * n_pad,)))
    static = dict(kernel="se", group=group, backend="pallas", pack_bits=20,
                  serve_cache=True)
    factors = broadcast.broadcast_factor_buffers(*args, **static)
    jaxpr = jax.make_jaxpr(functools.partial(
        broadcast.broadcast_factor_group, **static))(factors, 4, *args)
    artifact_rows = (m, n_pad, m * n_pad)
    seen = 0
    for eqn in walk_jaxpr(jaxpr):
        for v in eqn.outvars:
            shape = tuple(getattr(v.aval, "shape", ()))
            if int(np.prod(shape)) > bound:
                assert shape == artifact_rows, (eqn.primitive.name, shape)
                seen += 1
    assert seen, "the artifact's W rows are written in the program"


def test_refit_of_same_shaped_data_traces_nothing_new():
    est = DistributedGP(DGPConfig(gram_backend="pallas", **CFG))
    programs = (broadcast.broadcast_factor_group,
                broadcast.broadcast_factor_buffers,
                broadcast._train_inner_products, broadcast._train_operands0)
    for seed in (1, 2):
        X, y, _ = _data(seed)
        jax.block_until_ready(est.fit(X, y, M, key=jax.random.PRNGKey(seed)).factors)
        if seed == 1:
            sizes = [f._cache_size() for f in programs]
    assert [f._cache_size() for f in programs] == sizes


def test_serve_projector_keeps_float32_accuracy():
    """The cached serve's projector P = (U - U M^{-1} U)/s2, M = s2 I + U,
    at a conditioning like sarcos's (U's largest eigenvalue ~1e5 times s2):
    float32 within 1e-4 of the float64 value, entry by entry (P's entries
    are at most 1; the difference form is off by 1.6e-2 here)."""
    from repro.core.nystrom import nystrom_projector

    rng = np.random.default_rng(3)
    K, N, s2 = 64, 4096, 0.4
    W = rng.normal(size=(K, N)) * np.geomspace(3.0, 1e-3, K)[:, None]
    U = W @ W.T
    M = s2 * np.eye(K) + U
    want = (U - U @ np.linalg.solve(M, U)) / s2
    L = jnp.linalg.cholesky(jnp.asarray(M, jnp.float32))
    got = np.asarray(nystrom_projector(L, jnp.float32(s2)), np.float64)
    assert np.max(np.abs(got - want)) < 1e-4
