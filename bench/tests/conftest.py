"""Run the benchmark's pieces on the CPU at a small size: the harness's look
for a TPU is skipped, the configuration shrunk, nothing else changed.

With ``JAX_PLATFORMS=tpu`` the tests that need a chip run instead
(``test_control.py``); the rest then would hold the chip, so run those
files alone there."""
import os
import sys

ON_CHIP = os.environ.get("JAX_PLATFORMS") == "tpu"
if not ON_CHIP:  # the CPU, before anything imports jax, with no disk cache
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ["JAX_COMPILATION_CACHE_DIR"] = ""

import pytest  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
for p in (ROOT, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

# small sizes a CPU test can hold
SMALL_CFG = {"n_train": 480, "m": 8, "steps": 8}
SMALL_TRAFFIC = {"check_batches": 2}


def shrink(path: str, obj):
    if "/configs/" in path:
        return dict(obj, **SMALL_CFG)
    if "/traffic/" in path:
        return dict(obj, **{k: v for k, v in SMALL_TRAFFIC.items() if k in obj})
    return obj


@pytest.fixture
def small(monkeypatch):
    """The harness with the TPU check skipped and the sizes shrunk."""
    import jax
    from bench import common, run as brun

    monkeypatch.setattr(common, "require_tpu",
                        lambda count: jax.devices()[:max(count, 1)])
    real = brun._read_json
    monkeypatch.setattr(brun, "_read_json", lambda path: shrink(path, real(path)))
    return brun


@pytest.fixture
def cpu_run(small):
    """``run(workload, seed, seconds, trace)``: drive bench/run.py in this
    process at the small size."""

    def go(workload, seed=7, seconds=1.0, trace=0):
        rc = small.main(["--workload", workload, "--seed", str(seed),
                         "--seconds", str(seconds), "--trace", str(trace)])
        assert rc == 0
        return rc

    return go
