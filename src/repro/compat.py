"""Process-level JAX setup shared by the entry points.

* :func:`make_mesh` — every mesh this repo builds has Auto axes.
* :func:`cost_analysis_dict` — ``compiled.cost_analysis()`` as a dict.
* :func:`host_device_count_flags` / :func:`force_host_device_count` — the
  one place that edits ``XLA_FLAGS`` (CPU placeholder devices).
* :func:`setup_compilation_cache` — the persistent compilation cache at a
  fixed path, called by every entry point that compiles at size.
"""
from __future__ import annotations

import os

import jax

__all__ = [
    "CHECKOUT_ROOT",
    "COMPILATION_CACHE_DIR",
    "make_mesh",
    "cost_analysis_dict",
    "host_device_count_flags",
    "force_host_device_count",
    "setup_compilation_cache",
]

# the source checkout this package runs from (src/repro/compat.py -> root)
CHECKOUT_ROOT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)
COMPILATION_CACHE_DIR = os.path.join(CHECKOUT_ROOT, ".jax_cache")


def host_device_count_flags(n: int, existing: str = "") -> str:
    """An XLA_FLAGS string forcing ``n`` host platform devices, with any
    inherited ``--xla_force_host_platform_device_count`` stripped first
    (repeated XLA flags are last-wins, so a stale one would defeat ours) and
    every other inherited flag preserved."""
    import re

    stripped = re.sub(
        r"--xla_force_host_platform_device_count=\d+\s*", "", existing or ""
    )
    return (f"--xla_force_host_platform_device_count={n} " + stripped).strip()


def force_host_device_count(n: int) -> None:
    """Set XLA_FLAGS in os.environ to force ``n`` host devices — must run
    before the jax backend initializes (first device query; importing jax is
    fine).  Only the host (CPU) platform reads the flag; an attached
    accelerator keeps its own device count.  Shared by launch/dryrun (512
    placeholder devices) and serve_gp --mesh (one device per machine)."""
    os.environ["XLA_FLAGS"] = host_device_count_flags(
        n, os.environ.get("XLA_FLAGS", "")
    )


def make_mesh(axis_shapes, axis_names, **kw):
    """``jax.make_mesh`` with every axis Auto (the sharding-in-types Explicit
    mode is not used anywhere in this repo)."""
    auto = (jax.sharding.AxisType.Auto,) * len(axis_names)
    return jax.make_mesh(axis_shapes, axis_names, axis_types=auto, **kw)


def cost_analysis_dict(compiled) -> dict:
    """``compiled.cost_analysis()`` as a dict (None on backends that report
    no cost model)."""
    return dict(compiled.cost_analysis() or {})


def setup_compilation_cache() -> str | None:
    """Turn on JAX's persistent compilation cache; return its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is read by JAX itself and no
    other directory is set here.  Otherwise, on an accelerator, the cache
    lives at the fixed :data:`COMPILATION_CACHE_DIR` of the checkout: the
    path is part of what makes a later process hit, so it is never built
    from a temporary name, a process id or the time.  Every program is
    cached, however fast it compiled, so a second run in the same checkout
    compiles nothing it has compiled before.  A CPU-only process (tests,
    CI) keeps JAX's default, no cache (returns None): its compiles are cheap,
    and parallel test workers would all write one directory."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        if jax.default_backend() == "cpu":
            return None
        path = COMPILATION_CACHE_DIR
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path
