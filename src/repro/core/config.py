"""``DGPConfig`` — the one typed config behind :class:`~repro.core.api.DistributedGP`.

Every knob the four legacy entry points took as loose stringly-typed kwargs
(``protocol=``, ``impl=``, ``gram_backend=``, ``kernel=``, ``fuse=``/
``method=``, ...) lives here as a validated field of ONE frozen dataclass.
Validation happens at construction — a typo'd scheme name fails with the
registry's known names in the message, not 40 frames deep inside ``fit`` —
and the config rides on the fitted artifact (and its checkpoint ``meta.json``)
so a served model always knows exactly how it was produced.
"""
from __future__ import annotations

import dataclasses

from . import quantizers as Q
from .registry import FUSIONS, KERNELS, PROTOCOLS, SCHEMES

__all__ = ["DGPConfig", "IMPLS", "GRAM_BACKENDS", "GRAM_MODES", "TRAIN_IMPLS",
           "SERVE_EPILOGUES"]

IMPLS = ("host", "batched", "mesh")
GRAM_BACKENDS = ("xla", "pallas")
GRAM_MODES = ("nystrom", "nystrom_fitc", "direct", "dense")
TRAIN_IMPLS = ("scan", "loop")
SERVE_EPILOGUES = ("fused", "unfused")

# the artifact format written by save_artifact; bumped when the checkpoint
# layout changes.  1 = pre-DGPConfig artifacts (loaded via defaults);
# 2 = config in meta.json, unpacked int32 wire codes; 3 = PACKED uint32 wire
# codes + recorded payload_bits (v1/v2 still load — codes pack on restore;
# see docs/wire_format.md); 4 = per-array CRC32 checksums + the integrity
# ledger in meta.json (v1-v3 load unverified); 5 = streaming buffers:
# capacity-padded factor arrays plus the stream/* leaves (per-machine counts,
# occupied-column counter, device-resident ledgers) — v1-v4 load at exact
# capacity and pad up on their first update(); 6 = fused-serve-epilogue
# cache keys (factors/Ainv, factors/walpha) on Nyström artifacts — v1-v5
# load fine and simply serve on the unfused path (the keys are absent); v6
# files written with the retired factors/U carry it unread
ARTIFACT_FORMAT_VERSION = 6


def _ensure_registered() -> None:
    """Builtins register at import time; importing the protocols package here
    makes a bare ``from repro.core.config import DGPConfig`` self-sufficient."""
    from . import protocols  # noqa: F401  (registers schemes + protocols)


def _check_choice(kind: str, value: str, choices: tuple) -> None:
    if value not in choices:
        raise ValueError(
            f"unknown {kind} {value!r}: known {kind}s are {', '.join(choices)}"
        )


@dataclasses.dataclass(frozen=True)
class DGPConfig:
    """Validated, hashable description of one distributed-GP configuration.

    Fields
    ------
    protocol : ``center`` (§5.1) | ``broadcast`` (§5.2) | ``poe`` (zero-rate
        baseline) — a :data:`~repro.core.registry.PROTOCOLS` name.
    scheme : what actually crosses the wire — ``per_symbol`` (§4.2 int codes)
        or ``vq`` (the §4.1 Theorem-2 optimal test channel); a
        :data:`~repro.core.registry.SCHEMES` name.  Ignored by ``poe``
        (nothing crosses the wire at zero rate).
    kernel : ``se`` | ``linear`` — a :data:`~repro.core.registry.KERNELS` name.
    fusion : how per-machine predictives meet (broadcast fusion rule or PoE
        combiner): ``kl`` | ``poe`` | ``gpoe`` | ``bcm`` | ``rbcm`` — a
        :data:`~repro.core.registry.FUSIONS` name.
    impl : execution substrate — ``host`` (serial scipy oracle), ``batched``
        (one vmapped jit), ``mesh`` (machines are devices).
    gram_backend : ``xla`` | ``pallas`` (tiled gram + fused dequantize+gram
        kernels; batched impl only).
    gram_mode : train-gram assembly — ``nystrom`` (eq. 61), ``nystrom_fitc``
        (Snelson–Ghahramani exact diagonal), ``direct``, or ``dense`` (PoE).
    bits_per_sample : the paper's R — wire bits each transmitting machine
        spends per point (0 = zero-rate).
    max_bits : per-dimension rate cap of the per-symbol allocator.
    steps, lr, train_impl : hyperparameter-training knobs (Adam by marginal
        likelihood; ``scan`` compiles the loop into one program).
    center : which machine is the §5.1 center.
    serve_epilogue : ``fused`` (default) precomputes the K-sized serve cache
        (``nystrom_serve_cache``) at fit time so predict runs the fused
        matmul-only epilogue; ``unfused`` keeps the legacy O(t N K)
        solve-based serve path (parity/debugging — the two are algebraically
        equal, asserted by tests/test_kernel_runtime.py).
    faults : optional :class:`~repro.faults.FaultPlan` injected at fit time —
        dropped/NaN shards and packed-word bit flips (with CRC demotion of
        corrupted rows); ``None`` = a healthy fleet (see docs/fault_model.md).
    """

    protocol: str = "center"
    scheme: str = "per_symbol"
    kernel: str = "se"
    fusion: str = "kl"
    impl: str = "batched"
    gram_backend: str = "xla"
    gram_mode: str = "nystrom"
    bits_per_sample: int = 24
    max_bits: int = Q.DEFAULT_MAX_BITS
    steps: int = 150
    lr: float = 0.05
    train_impl: str = "scan"
    center: int = 0
    serve_epilogue: str = "fused"
    faults: object = None  # FaultPlan | None (frozen+hashable, rides as static meta)

    def __post_init__(self):
        _ensure_registered()
        # registry-backed names: the error carries the menu
        for registry, value in (
            (PROTOCOLS, self.protocol), (SCHEMES, self.scheme),
            (KERNELS, self.kernel), (FUSIONS, self.fusion),
        ):
            registry.get(value)
        _check_choice("impl", self.impl, IMPLS)
        _check_choice("gram_backend", self.gram_backend, GRAM_BACKENDS)
        _check_choice("gram_mode", self.gram_mode, GRAM_MODES)
        _check_choice("train_impl", self.train_impl, TRAIN_IMPLS)
        _check_choice("serve_epilogue", self.serve_epilogue, SERVE_EPILOGUES)
        if self.bits_per_sample < 0:
            raise ValueError(f"bits_per_sample must be >= 0, got {self.bits_per_sample}")
        if self.max_bits < 0:
            raise ValueError(f"max_bits must be >= 0, got {self.max_bits}")
        if self.steps < 0:
            raise ValueError(f"steps must be >= 0, got {self.steps}")
        if self.center < 0:
            raise ValueError(f"center must be >= 0, got {self.center}")
        if self.gram_backend == "pallas" and self.impl != "batched":
            # the pallas gram/qgram kernels eat the batched wire's int codes;
            # the host oracle has no wire state and the mesh path assembles
            # grams device-local
            raise ValueError(
                f'gram_backend="pallas" requires impl="batched", got '
                f"{self.impl!r}"
            )
        if self.scheme == "vq":
            # the test channel is simulated host-side on the batched substrate;
            # there are no int codes for the pallas qgram kernels to eat, and
            # poe has no wire at all
            if self.protocol == "poe":
                raise ValueError(
                    'scheme="vq" does not apply to protocol="poe" '
                    "(zero-rate: nothing crosses the wire)"
                )
            if self.impl != "batched":
                raise ValueError(
                    f'scheme="vq" supports impl="batched" only, got {self.impl!r}'
                )
            if self.gram_backend != "xla":
                raise ValueError(
                    'scheme="vq" has no int wire codes for the pallas qgram '
                    'path: use gram_backend="xla"'
                )
        if self.faults is not None:
            from ..faults import FaultPlan

            if not isinstance(self.faults, FaultPlan):
                raise TypeError(
                    f"faults must be a repro.faults.FaultPlan or None, got "
                    f"{type(self.faults).__name__}"
                )

    # -- conversions ---------------------------------------------------------

    def asdict(self) -> dict:
        """JSON-ready dict (checkpoint ``meta.json`` records this)."""
        return dataclasses.asdict(self)

    @classmethod
    def from_dict(cls, d: dict) -> "DGPConfig":
        known = {f.name for f in dataclasses.fields(cls)}
        d = {k: v for k, v in d.items() if k in known}
        if isinstance(d.get("faults"), dict):
            from ..faults import FaultPlan

            d["faults"] = FaultPlan.from_dict(d["faults"])
        return cls(**d)

    @classmethod
    def from_legacy_meta(cls, meta: dict) -> "DGPConfig":
        """Reconstruct a best-effort config from a pre-redesign artifact's
        ``meta.json`` (format version 1: no ``config`` block).  Training knobs
        (steps/lr) are not recorded in old checkpoints, so they stay at
        defaults; everything the serve path needs is recovered exactly."""
        return cls(
            protocol=meta["protocol"],
            scheme=meta.get("scheme", "per_symbol"),
            kernel=meta["kernel"],
            fusion=meta["fuse"] or "kl",
            impl="batched",  # checkpoints always restore single-host
            gram_backend=meta["gram_backend"],
            gram_mode=meta["gram_mode"],
            bits_per_sample=meta["bits_per_sample"],
            max_bits=meta["max_bits"],
        )
