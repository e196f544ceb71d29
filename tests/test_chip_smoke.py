"""chip_smoke.py's contract off the chip, and the fixed cache paths it uses.

The smoke run must never fall back to the CPU: without a TPU it exits 1 with
a message and prints no verdict, and a copy of the script outside a checkout
fails the same way.  The compilation cache and the autotune cache live at
fixed paths in the checkout unless the environment names one.
"""
import json
import os
import shutil
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(cmd, cwd, drop=(), **env):
    base = {k: v for k, v in os.environ.items() if k not in drop}
    return subprocess.run(
        cmd, cwd=cwd, capture_output=True, text=True, timeout=300,
        env=dict(base, JAX_PLATFORMS="cpu", **env),
    )


def _no_verdict(stdout: str) -> bool:
    last = (stdout.strip().splitlines() or [""])[-1]
    try:
        return not isinstance(json.loads(last), dict)
    except ValueError:
        return True


def test_smoke_refuses_the_cpu():
    r = _run([sys.executable, os.path.join(ROOT, "chip_smoke.py")], ROOT)
    assert r.returncode == 1
    assert "no TPU found" in r.stderr
    assert _no_verdict(r.stdout)


def test_smoke_outside_a_checkout_fails(tmp_path):
    script = shutil.copy(os.path.join(ROOT, "chip_smoke.py"), tmp_path)
    r = _run([sys.executable, script], str(tmp_path),
             PYTHONPATH=os.path.join(ROOT, "src"))
    assert r.returncode == 1
    assert "no repro package" in r.stderr
    assert _no_verdict(r.stdout)


_CACHE_PROBE = """
import jax
from repro.compat import setup_compilation_cache
print(setup_compilation_cache())
print(jax.config.jax_compilation_cache_dir)
"""


@pytest.mark.parametrize("env_dir", [None, "named"])
def test_compilation_cache_directory(tmp_path, env_dir):
    """A named directory is used as it is; a CPU-only process otherwise
    keeps the cache off (on an accelerator the fixed checkout path below)."""
    env = {"PYTHONPATH": os.path.join(ROOT, "src")}
    if env_dir:
        env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path / env_dir)
    r = _run([sys.executable, "-c", _CACHE_PROBE], str(tmp_path),
             drop=("JAX_COMPILATION_CACHE_DIR",), **env)
    assert r.returncode == 0, r.stderr
    used, configured = r.stdout.strip().splitlines()[-2:]
    want = str(tmp_path / env_dir) if env_dir else "None"
    assert used == configured == want


def test_cache_paths_are_fixed_in_the_checkout(monkeypatch):
    from repro import compat
    from repro.kernels import runtime

    monkeypatch.delenv("REPRO_TUNE_CACHE", raising=False)
    assert compat.COMPILATION_CACHE_DIR == os.path.join(ROOT, ".jax_cache")
    # autotune winners sit beside the compilation cache
    assert runtime.cache_path() == os.path.join(ROOT, ".autotune.json")
