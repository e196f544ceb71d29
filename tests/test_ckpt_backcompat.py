"""Checkpoint back-compat: pre-redesign artifacts keep loading.

``tests/fixtures/legacy_artifact/`` is a committed format-version-1
checkpoint (PR-3 era ``meta.json``: no ``config``, ``scheme`` or
``format_version`` keys) of a tiny §5.1 center fit, plus the predictions the
original artifact produced (``expected.npz``).  Locked here:

  * ``load_artifact`` reads it, defaults the scheme to ``per_symbol``, and
    reconstructs a ``DGPConfig`` from the legacy metadata;
  * predictions from the restored artifact match the recorded ones to
    ``RECORDED_ATOL`` (the serve path is unchanged by the metadata upgrade);
  * re-saving writes a current-format checkpoint (config recorded) that
    round-trips bitwise.
"""
import json
import os

import numpy as np
import pytest

from repro.core import DGPConfig, DistributedGP
from repro.core.config import ARTIFACT_FORMAT_VERSION
from repro.core.protocols import load_artifact, predict, save_artifact, update

FIXTURE = os.path.join(os.path.dirname(__file__), "fixtures", "legacy_artifact")

# expected.npz was recorded under an older jax, whose XLA CPU code generation
# rounds float32 differently (fusion order, vectorized exp).  The installed
# jax reproduces it to 1.9e-6 in mu and 3.0e-6 in s2, on values of order
# 0.1-1.4; 1e-5 leaves 3x headroom over that rounding drift and is still far
# below what any change to the serve path itself would move.
RECORDED_ATOL = 1e-5


def _assert_recorded(got, want):
    np.testing.assert_allclose(np.asarray(got), want, rtol=0,
                               atol=RECORDED_ATOL)


def _expected():
    z = np.load(os.path.join(FIXTURE, "expected.npz"))
    return z["Xt"], z["mu"], z["s2"]


def test_fixture_is_actually_legacy_format():
    with open(os.path.join(FIXTURE, "meta_00000000.json")) as f:
        meta = json.load(f)
    for key in ("format_version", "scheme", "config"):
        assert key not in meta


def test_legacy_artifact_loads_with_reconstructed_config():
    art = load_artifact(FIXTURE)
    assert art.scheme == "per_symbol"
    assert isinstance(art.config, DGPConfig)
    assert art.config.protocol == art.protocol == "center"
    assert art.config.bits_per_sample == art.bits_per_sample == 8
    assert art.config.kernel == art.kernel
    assert art.config.impl == "batched"
    # training knobs were never recorded pre-redesign: defaults
    assert art.config.steps == DGPConfig().steps
    # the legacy int32 code plane is packed on load — every restored
    # artifact carries the one shared wire representation; the payload was
    # never measured pre-v3, so its ledger stays 0
    assert art.wire.codes.dtype == np.uint32
    assert art.payload_bits == 0
    Xt, mu_exp, s2_exp = _expected()
    mu, s2 = predict(art, Xt)
    _assert_recorded(mu, mu_exp)
    _assert_recorded(s2, s2_exp)


def test_legacy_artifact_roundtrips_to_current_format(tmp_path):
    art = load_artifact(FIXTURE)
    save_artifact(art, str(tmp_path))
    with open(os.path.join(str(tmp_path), "meta_00000000.json")) as f:
        meta = json.load(f)
    assert meta["format_version"] == ARTIFACT_FORMAT_VERSION
    assert meta["scheme"] == "per_symbol"
    assert meta["config"]["protocol"] == "center"
    art2 = load_artifact(str(tmp_path))
    assert art2.config == art.config
    Xt, mu_exp, s2_exp = _expected()
    mu, s2 = predict(art2, Xt)
    _assert_recorded(mu, mu_exp)
    _assert_recorded(s2, s2_exp)
    # the re-saved checkpoint serves bitwise what the legacy load serves
    mu0, s20 = predict(art, Xt)
    np.testing.assert_array_equal(np.asarray(mu), np.asarray(mu0))
    np.testing.assert_array_equal(np.asarray(s2), np.asarray(s20))


def test_legacy_artifact_supports_streaming_and_facade():
    """The restored artifact is a full citizen: the facade serves it and
    update() keeps charging the frozen per-machine rate to the ledger."""
    art = load_artifact(FIXTURE)
    est = DistributedGP(art.config)
    Xt, mu_exp, _ = _expected()
    mu, _ = est.predict(art, Xt)
    _assert_recorded(mu, mu_exp)
    rng = np.random.default_rng(0)
    Xn = rng.normal(size=(4, Xt.shape[1])).astype(np.float32)
    art2 = update(art, Xn, np.zeros(4, np.float32), machine=1)
    rate = int(np.asarray(art.wire.rates[1]).sum())
    assert art2.wire_bits == art.wire_bits + 4 * rate
    mu2, s22 = predict(art2, Xt)
    assert np.all(np.isfinite(np.asarray(mu2))) and np.all(np.asarray(s22) > 0)
