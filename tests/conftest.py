import os

# Give in-process tests a multi-device CPU platform.  This must run before the
# first jax import (conftest is imported before any test module).  Subprocess
# tests (test_comm / test_mesh_gp / test_qpsum_training) overwrite XLA_FLAGS
# themselves, and the entry points force a device count only when run as a
# command, so importing one never stomps this setting.  In-process mesh tests
# (test_conformance, the in-process halves of test_comm) rely on these 8
# devices.
if "--xla_force_host_platform_device_count" not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=8"
    ).strip()

import numpy as np
import pytest


@pytest.fixture(scope="session")
def rng():
    return np.random.default_rng(0)


def rand_cov(rng, d, scale=1.0):
    A = rng.normal(size=(d, d))
    return scale * (A @ A.T) / d


@pytest.fixture
def cov_pair(rng):
    d = 12
    return rand_cov(rng, d), rand_cov(rng, d), d


@pytest.fixture
def strict_device_guard():
    """Run the guarded block under jax's strictest runtime modes: any IMPLICIT
    host<->device transfer (a numpy array silently crossing into a jitted
    program, a traced value concretized on host) and any implicit dtype
    promotion raise instead of silently costing a sync / widening to f64.

    The warm-serve and streaming-update paths must pass under both — they are
    the runtime complement of the jaxpr-level contracts in repro.analysis
    (``check_contracts`` proves no callback primitive is IN the program; this
    proves the dispatch loop AROUND the program moves nothing by accident).
    Explicit jax.device_put/device_get remain allowed.
    """
    import jax

    with jax.transfer_guard("disallow"), \
            jax.numpy_dtype_promotion("strict"):
        yield
