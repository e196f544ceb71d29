"""Chaos suite: the fault-injection plane (repro.faults), CRC-guarded wire
demotion, degraded-mode serving, and the numerical guardrails.

Locked invariants:
  * a :class:`~repro.faults.FaultPlan` is a frozen, hashable, mergeable value
    that round-trips through ``DGPConfig`` json metadata;
  * the CRC-16 framing detects EVERY single-bit flip and (empirically) all
    1%-rate random corruption — corrupted rows are demoted to the masked-row
    path IDENTICALLY on the batched and mesh impls, and the integrity ledger
    still charges the original (pre-demotion) row counts;
  * losing machines at fit or serve time degrades accuracy, never finiteness:
    predictions stay finite, KL-fused variance inflates (losing experts must
    never shrink uncertainty), and ``health()`` reports the loss instead of
    the caller discovering NaNs;
  * ``chol_safe`` recovers rank-deficient Grams by geometric jitter
    escalation while the well-conditioned path stays bit-identical, and the
    warm predict program still contains zero factorizations;
  * hostile inputs (NaN/Inf queries, NaN update batches, all-masked shards,
    absurd pack widths, bit-rotted checkpoints) fail loud or degrade soft —
    never propagate garbage silently.

The mesh halves run IN-PROCESS on the conftest's 8 forced host devices.
"""
import dataclasses
import json
import os

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core import DGPConfig, DistributedGP, jax_scheme
from repro.core.linalg_safe import DEFAULT_JITTER, chol_jittered, chol_safe
from repro.core.distributed_gp import predict_op_counts
from repro.faults import (
    FaultPlan,
    apply_to_parts,
    corrupt_words,
    drop_machine,
    flip_words,
    nan_shard,
    straggler,
)


# --------------------------------------------------------------------------
# shared fixtures
# --------------------------------------------------------------------------

M, N, D = 8, 160, 4


def _data(seed=0, n=N, d=D, n_test=16):
    rng = np.random.default_rng(seed)
    w = rng.normal(size=d)
    X = rng.normal(size=(n, d)).astype(np.float32)
    y = (np.sin(X @ w) + 0.05 * rng.normal(size=n)).astype(np.float32)
    Xt = rng.normal(size=(n_test, d)).astype(np.float32)
    return X, y, Xt


def _cfg(impl="batched", protocol="broadcast", **kw):
    base = dict(protocol=protocol, impl=impl, steps=4, bits_per_sample=12)
    if protocol == "poe":
        base.update(bits_per_sample=0, gram_mode="dense", fusion="rbcm")
    base.update(kw)
    return DGPConfig(**base)


def _finite(*arrays):
    return all(np.isfinite(np.asarray(a)).all() for a in arrays)


# --------------------------------------------------------------------------
# the fault plan: a frozen, mergeable, serializable value
# --------------------------------------------------------------------------


def test_fault_plan_merge_and_roundtrip():
    plan = (drop_machine(3) | corrupt_words(0.01, seed=7)
            | nan_shard(5) | straggler(1, delay=0.2))
    assert plan.drop == (3,) and plan.nan == (5,)
    assert plan.flip_rate == pytest.approx(0.01) and plan.seed == 7
    assert plan.straggle == ((1, 0.2),)
    assert plan.active
    # frozen + hashable: usable as static jit metadata
    hash(plan)
    with pytest.raises(dataclasses.FrozenInstanceError):
        plan.flip_rate = 0.5
    # dict round-trip is exact (this is what DGPConfig persists)
    assert FaultPlan.from_dict(plan.asdict()) == plan
    assert not FaultPlan().active


def test_fault_plan_through_config_roundtrip():
    cfg = _cfg(faults=drop_machine(2) | corrupt_words(0.005))
    cfg2 = DGPConfig.from_dict(json.loads(json.dumps(cfg.asdict())))
    assert cfg2.faults == cfg.faults
    # a healthy config carries no plan at all
    assert _cfg().faults is None


def test_apply_to_parts_drop_and_nan():
    X, y, _ = _data()
    parts = [(X[i * 20:(i + 1) * 20], y[i * 20:(i + 1) * 20]) for i in range(M)]
    new, removed = apply_to_parts(parts, drop_machine(3) | nan_shard(5))
    assert new[3][0].shape[0] == 0 and removed > 0
    assert new[5][0].shape[0] < 20  # NaN-poisoned rows filtered out
    for j in (0, 1, 2, 4, 6, 7):
        np.testing.assert_array_equal(np.asarray(new[j][0]), np.asarray(parts[j][0]))


# --------------------------------------------------------------------------
# the bit-flip channel and the CRC that catches it
# --------------------------------------------------------------------------


def test_flip_words_deterministic_and_rate():
    rng = np.random.default_rng(0)
    words = jnp.asarray(rng.integers(0, 2**32, (64, 3), dtype=np.uint32))
    key = jax.random.PRNGKey(11)
    a = flip_words(words, 0.02, key)
    b = flip_words(words, 0.02, key)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))  # seeded channel
    assert flip_words(words, 0.0, key) is words  # rate 0 is the identity
    flips = bin(int(np.bitwise_xor(np.asarray(a), np.asarray(words))
                    .astype(np.uint64).sum() % 1))  # noqa: F841 (popcount below)
    xor = np.bitwise_xor(np.asarray(a), np.asarray(words))
    n_flipped = int(np.unpackbits(xor.view(np.uint8)).sum())
    n_bits = words.size * 32
    assert 0.5 * 0.02 * n_bits < n_flipped < 2.0 * 0.02 * n_bits


def test_crc_detects_every_single_bit_flip():
    rng = np.random.default_rng(1)
    words = jnp.asarray(rng.integers(0, 2**32, (2,), dtype=np.uint32))[None, :]
    crc0 = int(jax_scheme.crc_words(words)[0])
    crc_jit = jax.jit(jax_scheme.crc_words)
    for w in range(2):
        for b in range(32):
            flipped = np.asarray(words).copy()
            flipped[0, w] ^= np.uint32(1) << np.uint32(b)
            assert int(crc_jit(jnp.asarray(flipped))[0]) != crc0, (w, b)


def test_crc_detection_rate_at_one_percent():
    """The acceptance bound: >= 1 - 2^-16 detection at a 1% flip rate.  With
    ~500 corrupted rows the expected number of misses is ~0.008, so a fixed
    seed should see zero — we assert the bound, not perfection."""
    rng = np.random.default_rng(2)
    n_rows, W = 600, 4
    words = jnp.asarray(rng.integers(0, 2**32, (n_rows, W), dtype=np.uint32))
    clean = jax_scheme.crc_words(words)
    rx = flip_words(words, 0.01, jax.random.PRNGKey(3))
    dirty = jax_scheme.crc_words(rx)
    corrupted = np.any(np.asarray(rx) != np.asarray(words), axis=-1)
    # P(row corrupted) = 1 - 0.99^128 ~ 0.72 at 1% over 4 words
    assert corrupted.sum() > 0.6 * n_rows
    detected = (np.asarray(dirty) != np.asarray(clean)) & corrupted
    rate = detected.sum() / corrupted.sum()
    assert rate >= 1.0 - 2.0**-16


# --------------------------------------------------------------------------
# fit-time faults: drop / NaN / corruption through every impl
# --------------------------------------------------------------------------


@pytest.mark.parametrize("protocol", ["broadcast", "poe"])
@pytest.mark.parametrize("impl", ["batched", "mesh"])
def test_drop_machine_fit_survives(protocol, impl):
    X, y, Xt = _data()
    est = DistributedGP(_cfg(impl, protocol, faults=drop_machine(3)))
    art = est.fit(X, y, M)
    assert art.lengths[3] == 0
    mu, var = est.predict(art, Xt)
    assert _finite(mu, var) and np.all(np.asarray(var) > 0)
    h = est.health(art)
    assert h.status == "degraded" and h.machines_lost == (3,)
    if protocol == "broadcast":  # kl fusion inflates by m / m_alive
        assert h.variance_inflation == pytest.approx(M / (M - 1))


def test_drop_guards_fail_loud():
    X, y, _ = _data()
    with pytest.raises(ValueError, match="machine 0"):
        DistributedGP(_cfg(faults=drop_machine(0))).fit(X, y, M)
    with pytest.raises(ValueError, match="center"):
        DistributedGP(
            _cfg(protocol="center", faults=drop_machine(0))
        ).fit(X, y, M)
    with pytest.raises(ValueError, match="every row"):
        DistributedGP(
            _cfg(faults=FaultPlan(drop=tuple(range(M))))
        ).fit(X, y, M)


def test_nan_shard_fit_filters_rows():
    X, y, Xt = _data()
    est = DistributedGP(_cfg(protocol="center", faults=nan_shard(2)))
    art = est.fit(X, y, M)
    assert 0 < art.lengths[2] < N // M  # poisoned rows filtered, shard kept
    mu, var = est.predict(art, Xt)
    assert _finite(mu, var)


def test_corruption_demotes_identically_batched_vs_mesh():
    """The CRC demotion contract: the same seeded channel corrupts the same
    packed words on both impls, so the surviving row sets — and therefore the
    fitted artifacts — are identical by construction."""
    X, y, Xt = _data()
    arts = {}
    for impl in ("batched", "mesh"):
        est = DistributedGP(_cfg(impl, faults=corrupt_words(0.01, seed=3)))
        arts[impl] = est.fit(X, y, M)
    ab, am = arts["batched"], arts["mesh"]
    assert ab.rows_demoted == am.rows_demoted > 0
    assert ab.lengths == am.lengths
    # integrity is charged on what was TRANSMITTED (original rows), so the
    # ledger matches the clean fit even though rows were demoted on receive
    clean = DistributedGP(_cfg()).fit(X, y, M)
    assert ab.integrity_bits == am.integrity_bits == clean.integrity_bits
    mu_b, s2_b = DistributedGP(_cfg()).predict(ab, Xt)
    mu_m, s2_m = DistributedGP(_cfg("mesh")).predict(am, Xt)
    assert _finite(mu_b, s2_b, mu_m, s2_m)
    np.testing.assert_allclose(np.asarray(mu_m), np.asarray(mu_b), atol=1e-4)
    np.testing.assert_allclose(np.asarray(s2_m), np.asarray(s2_b), atol=1e-4)


def test_corruption_health_reports_demotion():
    X, y, _ = _data()
    est = DistributedGP(_cfg(faults=corrupt_words(0.02, seed=5)))
    art = est.fit(X, y, M)
    h = est.health(art)
    assert h.rows_demoted == art.rows_demoted > 0
    assert h.status == "degraded"


# --------------------------------------------------------------------------
# serve-time degradation: availability masks through fusion
# --------------------------------------------------------------------------


def test_degraded_predict_batched_matches_mesh():
    X, y, Xt = _data(seed=4)
    ab = DistributedGP(_cfg()).fit(X, y, M)
    am = DistributedGP(_cfg("mesh")).fit(X, y, M)
    av = np.ones(M, np.float32)
    av[[2, 6]] = 0.0
    mu_b, s2_b = DistributedGP(_cfg()).predict(ab, Xt, available=av)
    mu_m, s2_m = DistributedGP(_cfg("mesh")).predict(am, Xt, available=av)
    assert _finite(mu_b, s2_b, mu_m, s2_m)
    np.testing.assert_allclose(np.asarray(mu_m), np.asarray(mu_b), atol=1e-4)
    np.testing.assert_allclose(np.asarray(s2_m), np.asarray(s2_b), atol=1e-4)


def test_kl_variance_never_shrinks_under_loss():
    X, y, Xt = _data(seed=5)
    est = DistributedGP(_cfg())
    art = est.fit(X, y, M)
    _, s2_full = est.predict(art, Xt)
    for lost in ([7], [1, 4], [1, 3, 5, 7]):
        av = np.ones(M, np.float32)
        av[lost] = 0.0
        mu, s2 = est.predict(art, Xt, available=av)
        assert _finite(mu, s2)
        assert np.all(np.asarray(s2) >= np.asarray(s2_full) - 1e-6), lost
        h = est.health(art, av)
        assert h.machines_lost == tuple(lost)
        assert h.variance_inflation == pytest.approx(M / (M - len(lost)))


@pytest.mark.parametrize("fusion", ["poe", "gpoe", "bcm", "rbcm"])
def test_poe_family_degraded_serving(fusion):
    X, y, Xt = _data(seed=6)
    est = DistributedGP(_cfg(protocol="poe", fusion=fusion))
    art = est.fit(X, y, M)
    av = np.ones(M, np.float32)
    av[0] = 0.0
    mu, s2 = est.predict(art, Xt, available=av)
    assert _finite(mu, s2) and np.all(np.asarray(s2) > 0)
    # all-alive mask serves (numerically) the healthy program
    mu1, s21 = est.predict(art, Xt, available=np.ones(M, np.float32))
    mu0, s20 = est.predict(art, Xt)
    np.testing.assert_allclose(np.asarray(mu1), np.asarray(mu0), atol=1e-4)
    np.testing.assert_allclose(np.asarray(s21), np.asarray(s20), atol=1e-4)


def test_center_ignores_availability():
    """The §5.1 center holds every decoded shard locally — machine loss after
    fit cannot change its predictive (the mask is surface parity only)."""
    X, y, Xt = _data(seed=7)
    est = DistributedGP(_cfg(protocol="center"))
    art = est.fit(X, y, M)
    av = np.ones(M, np.float32)
    av[4] = 0.0
    mu0, s20 = est.predict(art, Xt)
    mu1, s21 = est.predict(art, Xt, available=av)
    np.testing.assert_array_equal(np.asarray(mu1), np.asarray(mu0))
    np.testing.assert_array_equal(np.asarray(s21), np.asarray(s20))


def test_availability_mask_validated():
    X, y, Xt = _data(seed=8)
    est = DistributedGP(_cfg())
    art = est.fit(X, y, M)
    with pytest.raises(ValueError, match="available"):
        est.predict(art, Xt, available=np.ones(M - 1, np.float32))
    with pytest.raises(TypeError, match="health"):
        est.health(object())


# --------------------------------------------------------------------------
# numerical guardrails: chol_safe + hostile inputs
# --------------------------------------------------------------------------


def test_chol_safe_bit_identical_when_well_conditioned():
    rng = np.random.default_rng(9)
    A = rng.normal(size=(12, 12))
    Mx = jnp.asarray(A @ A.T + 12 * np.eye(12), jnp.float32)
    L_ref = jnp.linalg.cholesky(Mx + DEFAULT_JITTER * jnp.eye(12, dtype=jnp.float32))
    L = chol_safe(Mx, DEFAULT_JITTER)
    np.testing.assert_array_equal(np.asarray(L), np.asarray(L_ref))
    np.testing.assert_array_equal(
        np.asarray(chol_jittered(Mx, DEFAULT_JITTER)), np.asarray(L_ref)
    )


def test_chol_safe_recovers_rank_deficient():
    rng = np.random.default_rng(10)
    U = rng.normal(size=(16, 3)).astype(np.float32)
    Mx = jnp.asarray(U @ U.T)  # rank 3 of 16: plain cholesky returns NaN
    assert not np.isfinite(np.asarray(jnp.linalg.cholesky(Mx))).all()
    L = chol_safe(Mx)
    assert np.isfinite(np.asarray(L)).all()
    err = np.abs(np.asarray(L @ L.T) - np.asarray(Mx)).max()
    assert err < 1e-2  # reconstruction within the escalated jitter


def test_chol_safe_vmap_mixed_batch():
    """Per-element escalation: a healthy batch element keeps its original
    factor bit-identically even while a rank-deficient sibling escalates."""
    rng = np.random.default_rng(11)
    A = rng.normal(size=(8, 8))
    good = (A @ A.T + 8 * np.eye(8)).astype(np.float32)
    U = rng.normal(size=(8, 2)).astype(np.float32)
    bad = U @ U.T
    batch = jnp.stack([jnp.asarray(good), jnp.asarray(bad)])
    L = jax.vmap(lambda m: chol_safe(m, DEFAULT_JITTER))(batch)
    assert np.isfinite(np.asarray(L)).all()
    L_good = chol_safe(jnp.asarray(good), DEFAULT_JITTER)
    np.testing.assert_array_equal(np.asarray(L[0]), np.asarray(L_good))


def test_warm_predict_has_zero_factorizations():
    """chol_safe lives at fit time only: the warm serve program still contains
    zero cholesky/eigh equations — jitter escalation costs nothing per query."""
    X, y, Xt = _data(seed=12)
    art = DistributedGP(_cfg()).fit(X, y, M)
    assert predict_op_counts(art, Xt) == {"cholesky": 0, "eigh": 0}


def test_hostile_query_rows_degrade_to_prior():
    X, y, Xt = _data(seed=13)
    est = DistributedGP(_cfg())
    art = est.fit(X, y, M)
    Xbad = Xt.copy()
    Xbad[3] = np.nan
    Xbad[7] = np.inf
    mu, var = est.predict(art, Xbad)
    assert _finite(mu, var)
    mu0, var0 = est.predict(art, Xt)
    # healthy rows unaffected; poisoned rows report zero mean + prior variance
    keep = np.ones(len(Xt), bool)
    keep[[3, 7]] = False
    np.testing.assert_allclose(np.asarray(mu)[keep], np.asarray(mu0)[keep],
                               atol=1e-6)
    assert np.asarray(mu)[3] == 0.0 and np.asarray(mu)[7] == 0.0
    assert np.asarray(var)[3] > np.median(np.asarray(var0))  # prior, not 0


def test_hostile_update_batch_filters_and_warns():
    X, y, Xt = _data(seed=14)
    est = DistributedGP(_cfg())
    art = est.fit(X, y, M)
    Xn = np.random.default_rng(0).normal(size=(6, D)).astype(np.float32)
    yn = np.zeros(6, np.float32)
    Xn[2] = np.nan
    yn[4] = np.inf
    with pytest.warns(UserWarning, match="non-finite"):
        art2 = est.update(art, Xn, yn, machine=1)
    assert art2.lengths[1] == art.lengths[1] + 4  # 2 poisoned rows dropped
    mu, var = est.predict(art2, Xt)
    assert _finite(mu, var)


def test_pack_codes_width_overflow_fails_loud():
    with pytest.raises(ValueError, match="overflow"):
        jax_scheme.pack_codes(
            jnp.zeros((1, 2**27), jnp.uint32), 32
        )


def test_all_masked_shard_transmits_nothing():
    """An all-masked (zero-row) shard in q_all_gather: finite outputs, zero
    words, zero charge on all three ledgers for that machine."""
    from jax.sharding import Mesh, PartitionSpec as P
    from repro.comm import q_all_gather
    from repro.comm.accounting import side_info_bits, CRC_BITS
    from jax import shard_map

    m, n_loc, d, bits = 4, 10, 5, 15
    rng = np.random.default_rng(15)
    X = rng.normal(size=(m * n_loc, d)).astype(np.float32)
    mask = np.ones((m, n_loc), np.float32)
    mask[2, :] = 0.0  # machine 2 has nothing to say
    mesh = Mesh(np.asarray(jax.devices()[:m]), ("m",))
    fn = shard_map(
        lambda x, mk: q_all_gather(x, "m", bits, mask=mk[0], return_state=True)[1],
        mesh=mesh, in_specs=(P("m", None), P("m", None)), out_specs=P(),
        check_vma=False,
    )
    st = jax.jit(fn)(X, mask)
    assert np.isfinite(np.asarray(st["decoded"])).all()
    assert np.all(np.asarray(st["codes"])[2] == 0)
    rates = np.asarray(st["rates"])
    n_valid = mask.sum(axis=1).astype(int)
    live = [j for j in range(m) if n_valid[j] > 0]
    assert int(st["wire_bits"]) == sum(
        int(rates[j].sum()) * int(n_valid[j]) + side_info_bits(d) for j in live
    )
    assert int(st["integrity_bits"]) == CRC_BITS * int(n_valid[live].sum())


def test_q_all_gather_flip_fault_demotes_peers_not_self():
    """Collective-level corruption: flipped peer rows fail their CRC and are
    demoted in the gathered mask, while each machine's own block stays valid
    (it never crossed the wire)."""
    from jax.sharding import Mesh, PartitionSpec as P
    from repro.comm import q_all_gather
    from jax import shard_map

    m, n_loc, d, bits = 4, 12, 5, 15
    rng = np.random.default_rng(16)
    X = rng.normal(size=(m * n_loc, d)).astype(np.float32)
    mesh = Mesh(np.asarray(jax.devices()[:m]), ("m",))
    plan = corrupt_words(0.05, seed=9)
    fn = shard_map(
        lambda x: q_all_gather(x, "m", bits, return_state=True,
                               faults=plan)[1],
        mesh=mesh, in_specs=P("m", None), out_specs=P(), check_vma=False,
    )
    st = jax.jit(fn)(X)
    gmask = np.asarray(st["mask"])  # (m, n_loc) as seen by machine 0
    assert np.isfinite(np.asarray(st["decoded"])).all()
    assert np.all(gmask[0] == 1.0)  # own rows exempt from channel noise
    assert gmask[1:].sum() < (m - 1) * n_loc  # some peer rows demoted


def test_vq_scheme_rejects_flip_faults():
    X, y, _ = _data(seed=17)
    cfg = _cfg(scheme="vq", bits_per_sample=8, faults=corrupt_words(0.01))
    with pytest.raises(NotImplementedError, match="vq"):
        DistributedGP(cfg).fit(X, y, M)


# --------------------------------------------------------------------------
# checkpoint integrity (format v4)
# --------------------------------------------------------------------------


def _corrupt_npz_array(directory, key):
    path = os.path.join(directory, "ckpt_00000000.npz")
    arrays = dict(np.load(path))
    arr = arrays[key]
    flat = arr.reshape(-1).copy()
    flat[0] = flat[0] + 1 if np.issubdtype(arr.dtype, np.integer) else flat[0] + 0.5
    arrays[key] = flat.reshape(arr.shape)
    np.savez(path, **arrays)


def test_checkpoint_checksum_catches_bitrot(tmp_path):
    X, y, Xt = _data(seed=18)
    est = DistributedGP(_cfg())
    art = est.fit(X, y, M)
    d = str(tmp_path)
    est.save(art, d)
    meta = json.load(open(os.path.join(d, "meta_00000000.json")))
    assert meta["format_version"] >= 4 and meta["array_checksums"]
    # clean round trip first
    art2 = DistributedGP.load(d)
    mu, s2 = est.predict(art2, Xt)
    assert _finite(mu, s2)
    # now rot one array: load must name the bad array, not serve garbage
    bad_key = sorted(meta["array_checksums"])[0]
    _corrupt_npz_array(d, bad_key)
    from repro.checkpoint import CorruptCheckpointError

    with pytest.raises(CorruptCheckpointError, match=bad_key.split("/")[0]):
        DistributedGP.load(d)


def test_checkpoint_missing_array_named(tmp_path):
    X, y, _ = _data(seed=19)
    est = DistributedGP(_cfg())
    est.save(est.fit(X, y, M), str(tmp_path))
    path = os.path.join(str(tmp_path), "ckpt_00000000.npz")
    arrays = dict(np.load(path))
    victim = sorted(arrays)[-1]
    del arrays[victim]
    np.savez(path, **arrays)
    from repro.checkpoint import CorruptCheckpointError

    with pytest.raises(CorruptCheckpointError, match="missing array"):
        DistributedGP.load(str(tmp_path))


def test_legacy_checkpoint_without_checksums_loads(tmp_path):
    """v1-v3 artifacts carry no checksum table: they load unverified (and
    un-rotted v4 data with the table stripped behaves exactly like v3)."""
    X, y, Xt = _data(seed=20)
    est = DistributedGP(_cfg())
    art = est.fit(X, y, M)
    d = str(tmp_path)
    est.save(art, d)
    mp = os.path.join(d, "meta_00000000.json")
    meta = json.load(open(mp))
    del meta["array_checksums"]
    meta["format_version"] = 3
    json.dump(meta, open(mp, "w"))
    art2 = DistributedGP.load(d)
    mu, s2 = est.predict(art2, Xt)
    mu0, s20 = est.predict(art, Xt)
    np.testing.assert_allclose(np.asarray(mu), np.asarray(mu0), atol=1e-5)
    np.testing.assert_allclose(np.asarray(s2), np.asarray(s20), atol=1e-5)


# --------------------------------------------------------------------------
# faults x streaming: updates against a degraded fleet, corrupted batches
# --------------------------------------------------------------------------


def test_update_to_dropped_machine_is_refused():
    """A machine that transmitted nothing at fit time has no frozen codebooks
    to stream under: update() targeting it fails loud, and routing the batch
    to a survivor works."""
    X, y, Xt = _data(seed=20)
    est = DistributedGP(_cfg(faults=drop_machine(3)))
    art = est.fit(X, y, M)
    assert art.lengths[3] == 0
    rng = np.random.default_rng(20)
    Xn = rng.normal(size=(5, D)).astype(np.float32)
    yn = np.zeros(5, np.float32)
    with pytest.raises(ValueError, match="no rows at fit time"):
        est.update(art, Xn, yn, machine=3)
    art2 = est.update(art, Xn, yn, machine=1)  # survivors still stream
    assert art2.lengths[1] == art.lengths[1] + 5
    assert art2.lengths[3] == 0
    mu, var = est.predict(art2, Xt)
    assert _finite(mu, var) and np.all(np.asarray(var) > 0)


def test_corrupt_update_batch_demotes_only_new_rows():
    """Under a flip-rate plan a streamed batch crosses the physical wire:
    CRC-failing NEW rows are demoted (fit-time rows are untouchable), the
    FULL transmission is still charged to all three ledgers, and the
    artifact keeps serving."""
    from repro.comm.accounting import CRC_BITS

    X, y, Xt = _data(seed=21)
    est = DistributedGP(_cfg(faults=corrupt_words(0.05, seed=7)))
    art = est.fit(X, y, M)
    n_new = 40
    rng = np.random.default_rng(21)
    Xn = rng.normal(size=(n_new, D)).astype(np.float32)
    yn = np.zeros(n_new, np.float32)
    art2 = est.update(art, Xn, yn, machine=1)
    demoted_new = art2.rows_demoted - art.rows_demoted
    survived = art2.lengths[1] - art.lengths[1]
    # every transmitted row is accounted for: kept or demoted, nothing lost
    assert survived + demoted_new == n_new
    assert demoted_new > 0  # 5%/bit over 32-bit words: corruption is certain
    assert survived > 0
    # only machine 1's count moved
    for j in range(M):
        if j != 1:
            assert art2.lengths[j] == art.lengths[j]
    # the ledgers charge what was TRANSMITTED, not what survived
    rate1 = int(np.asarray(art.wire.rates[1]).sum())
    W = art.wire.codes.shape[-1]
    assert art2.wire_bits == art.wire_bits + n_new * rate1
    assert art2.payload_bits == art.payload_bits + n_new * 32 * W
    assert art2.integrity_bits == art.integrity_bits + n_new * CRC_BITS
    h = est.health(art2)
    assert h.status == "degraded" and h.rows_demoted == art2.rows_demoted
    mu, var = est.predict(art2, Xt)
    assert _finite(mu, var) and np.all(np.asarray(var) > 0)


def test_degraded_mask_predict_correct_after_updates():
    """Availability-masked serving stays correct on a streamed (bucketed)
    artifact: the KL-fused variance still never shrinks under machine loss,
    and batched == mesh on identically streamed artifacts."""
    X, y, Xt = _data(seed=22)
    ab = DistributedGP(_cfg()).fit(X, y, M)
    am = DistributedGP(_cfg("mesh")).fit(X, y, M)
    rng = np.random.default_rng(22)
    for j, n_new in [(1, 6), (4, 9)]:
        Xn = rng.normal(size=(n_new, D)).astype(np.float32)
        yn = np.zeros(n_new, np.float32)
        ab = DistributedGP(_cfg()).update(ab, Xn, yn, machine=j)
        am = DistributedGP(_cfg("mesh")).update(am, Xn, yn, machine=j)
    av = np.ones(M, np.float32)
    av[[2, 6]] = 0.0
    mu_b, s2_b = DistributedGP(_cfg()).predict(ab, Xt, available=av)
    mu_m, s2_m = DistributedGP(_cfg("mesh")).predict(am, Xt, available=av)
    assert _finite(mu_b, s2_b, mu_m, s2_m)
    np.testing.assert_allclose(np.asarray(mu_m), np.asarray(mu_b), atol=1e-4)
    np.testing.assert_allclose(np.asarray(s2_m), np.asarray(s2_b), atol=1e-4)
    _, s2_full = DistributedGP(_cfg()).predict(ab, Xt)
    assert np.all(np.asarray(s2_b) >= np.asarray(s2_full) - 1e-6)
