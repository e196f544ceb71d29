"""``DistributedGP`` — the one front door to the paper's protocols.

One validated :class:`~repro.core.config.DGPConfig` in, one estimator out::

    from repro.core import DGPConfig, DistributedGP

    cfg = DGPConfig(protocol="center", scheme="per_symbol", bits_per_sample=24)
    est = DistributedGP(cfg)
    art = est.fit(X, y, m=40)          # wire + train + factorize ONCE
    mu, var = est.predict(art, X_query)  # warm: triangular solves only
    art = est.update(art, X_new, y_new, machine=3)
    est.save(art, "ckpt/")             # est.load("ckpt/") serves identically

Every combination the legacy entry points exposed as loose kwargs is a config
field: 3 protocols × 3 impls × 2 wire schemes × kernels/fusions/backends, all
validated at ``DGPConfig`` construction against the registries
(:mod:`repro.core.registry`), so a typo fails with the known names in hand
rather than deep inside ``fit``.

``impl="host"`` returns the serial oracle models (:class:`~.protocols.center.
CenterGP`, ``HostBroadcastGP``, ``HostPoEGP``) — same ``.predict`` surface,
no artifact; the batched/mesh impls return a checkpointable
:class:`~repro.core.protocols.base.FittedProtocol`.
"""
from __future__ import annotations

import dataclasses

import jax

from ..spans import fit_span
from .config import DGPConfig
from .gp import GPParams
from .linalg_safe import full_precision
from .registry import PROTOCOLS
from .protocols import base as _base
from .protocols.base import FittedProtocol, split_machines

__all__ = ["DistributedGP"]


class DistributedGP:
    """Estimator facade over one :class:`~repro.core.config.DGPConfig`.

    Construct with a config (or config fields as keyword overrides) and use
    ``fit`` / ``predict`` / ``update`` / ``save`` / ``load``.  The instance is
    stateless beyond its config: ``fit`` returns the artifact, and every other
    method takes it explicitly — the fit-once/serve-many split stays visible.
    """

    def __init__(self, config: DGPConfig | None = None, **overrides):
        if config is None:
            config = DGPConfig(**overrides)
        elif not isinstance(config, DGPConfig):
            raise TypeError(
                f"DistributedGP expects a DGPConfig, got {type(config).__name__}"
            )
        elif overrides:
            config = dataclasses.replace(config, **overrides)
        self.config = config

    def __repr__(self):
        return f"DistributedGP({self.config!r})"

    # -- lifecycle -----------------------------------------------------------

    @fit_span()
    @full_precision
    def fit(
        self, X=None, y=None, m: int | None = None, *, parts=None, key=None,
        params: GPParams | None = None,
    ):
        """Run the configured protocol ONCE and return the serving artifact.

        Either pass the pooled dataset ``(X, y, m)`` — it is split uniformly
        at random across ``m`` machines (paper §6), ``key`` seeding the split
        — or pass ``parts`` (a list of per-machine ``(X_j, y_j)`` shards,
        e.g. from :func:`~repro.core.protocols.base.split_machines`) when the
        placement is already decided.

        Returns a :class:`~repro.core.protocols.base.FittedProtocol` for the
        batched/mesh impls; ``impl="host"`` returns the serial oracle model
        (same ``.predict`` surface, no artifact/streaming)."""
        if parts is None:
            if X is None or y is None or m is None:
                raise ValueError(
                    "fit() needs either (X, y, m) or parts=[(X_j, y_j), ...]"
                )
            if key is None:
                key = jax.random.PRNGKey(0)
            parts = split_machines(X, y, m, key)
        elif X is not None or y is not None or m is not None or key is not None:
            raise ValueError(
                "pass either (X, y, m[, key]) or parts, not both — parts are "
                "already placed, so a split key would be silently unused"
            )
        cfg = self.config
        spec = PROTOCOLS.get(cfg.protocol)
        if cfg.impl == "host":
            if spec.fit_host is None:
                raise NotImplementedError(
                    f"protocol {cfg.protocol!r} has no host oracle"
                )
            return spec.fit_host(parts, cfg, params)
        return spec.fit(parts, cfg, params)

    def predict(self, art, X_star, available=None):
        """Serve one query batch: (mean, var) at ``X_star`` from the cached
        factors — no refit, no refactorization (see
        :func:`~repro.core.protocols.base.predict`).

        ``available``: optional (m,) machine-availability mask for
        degraded-mode serving — fusion renormalizes over surviving machines
        (see :func:`~repro.core.protocols.base.serve_health` and
        docs/fault_model.md)."""
        if isinstance(art, FittedProtocol):
            return _base.predict(art, X_star, available)
        return art.predict(X_star, available)  # host oracle models

    def health(self, art, available=None):
        """Degradation report for a fitted artifact (machines lost, rows
        demoted, variance inflation) — see
        :func:`~repro.core.protocols.base.serve_health`."""
        if not isinstance(art, FittedProtocol):
            raise TypeError(
                "health() needs a FittedProtocol artifact (impl='host' oracle "
                "models carry no shard table to report on)"
            )
        return _base.serve_health(art, available)

    def update(self, art, X_new, y_new, machine: int = 0):
        """Stream new points into a fitted artifact (frozen codebooks, rank-k
        factor growth — see :func:`~repro.core.protocols.base.update`)."""
        if not isinstance(art, FittedProtocol):
            raise TypeError(
                "update() needs a FittedProtocol artifact (impl='host' oracle "
                "models do not support streaming)"
            )
        return _base.update(art, X_new, y_new, machine)

    def save(self, art, directory: str, step: int = 0) -> str:
        """Checkpoint an artifact (config recorded in ``meta.json``)."""
        if not isinstance(art, FittedProtocol):
            raise TypeError("save() needs a FittedProtocol artifact")
        return _base.save_artifact(art, directory, step)

    @staticmethod
    def load(directory: str, step: int | None = None, shardings=None) -> FittedProtocol:
        """Restore an artifact checkpoint (pre-redesign checkpoints load with
        a reconstructed default config) — see
        :func:`~repro.core.protocols.base.load_artifact`."""
        return _base.load_artifact(directory, step, shardings)
