"""Shared machinery of the §5 distributed-GP protocols.

This module owns everything the protocol implementations
(:mod:`.center`, :mod:`.broadcast`, :mod:`.poe`, :mod:`.mesh`) share:

* the padded-shard layout every vmapped stage runs on (:class:`PaddedShards`),
* the wire-state container and the §4 bit-accounting formula
  (:class:`WireState`, :func:`_wire_bits`),
* the serving artifact (:class:`FittedProtocol`) and its
  :func:`fit` / :func:`predict` / :func:`update` /
  :func:`save_artifact` / :func:`load_artifact` lifecycle,
* the serve-path introspection hooks (:func:`serve_trace_count`,
  :func:`predict_op_counts`).

Protocols and wire schemes are looked up in :mod:`repro.core.registry`
(``PROTOCOLS`` / ``SCHEMES``) — this module never names a concrete protocol,
which is what lets ``register_protocol`` / ``register_scheme`` extend the
system without touching the dispatch below.
"""
from __future__ import annotations

import collections
import dataclasses
from functools import partial

import numpy as np
import jax
import jax.numpy as jnp

from ..gp import GPParams, gram_fn, prior_diag
from ..linalg_safe import full_precision
from ..nystrom import nystrom_complete
from ..registry import PROTOCOLS, SCHEMES
from ...spans import fit_span

__all__ = [
    "split_machines",
    "pad_parts",
    "PaddedShards",
    "WireState",
    "WireRun",
    "ServeHealth",
    "serve_health",
    "StreamState",
    "FittedProtocol",
    "fit",
    "predict",
    "update",
    "save_artifact",
    "load_artifact",
    "serve_trace_count",
    "update_trace_count",
    "predict_op_counts",
]


def split_machines(X, y, m: int, key) -> list[tuple[jnp.ndarray, jnp.ndarray]]:
    """Random uniform split across m machines (paper §6: 'randomly distributed
    across 40 machines')."""
    n = X.shape[0]
    perm = jax.random.permutation(key, n)
    chunks = np.array_split(np.asarray(perm), m)
    return [(jnp.asarray(X)[c], jnp.asarray(y)[c]) for c in chunks]


# --------------------------------------------------------------------------
# uniform padded shards — the layout every vmapped protocol stage runs on
# --------------------------------------------------------------------------


class PaddedShards(collections.namedtuple("PaddedShards", "X y mask lengths")):
    """(m, n_pad, d) machine shards; invalid rows are zero with mask 0.

    ``lengths`` holds the per-machine true row counts (python ints)."""

    __slots__ = ()


def pad_parts(parts) -> PaddedShards:
    m = len(parts)
    d = parts[0][0].shape[1]
    lengths = tuple(int(p[0].shape[0]) for p in parts)
    n_pad = max(lengths)
    X = np.zeros((m, n_pad, d), np.float32)
    y = np.zeros((m, n_pad), np.float32)
    mask = np.zeros((m, n_pad), np.float32)
    for j, (Xj, yj) in enumerate(parts):
        X[j, : lengths[j]] = np.asarray(Xj, np.float32)
        y[j, : lengths[j]] = np.asarray(yj, np.float32)
        mask[j, : lengths[j]] = 1.0
    return PaddedShards(jnp.asarray(X), jnp.asarray(y), jnp.asarray(mask), lengths)


class WireState(collections.namedtuple(
    "WireState", "codes decoded T_inv rates sigma scaled_cents T"
)):
    """Everything the wire protocol produced, for every machine at once.

    This is the fit-once scheme state: ``(T, T_inv, sigma, rates)`` per machine
    are the frozen codebooks/transforms that :func:`update` reuses to encode
    NEW symbols without refitting (only their ``rates.sum()`` wire bits are
    spent), and ``codes``/``scaled_cents`` feed the fused dequantize+gram
    kernel under ``gram_backend="pallas"``.

    Fields: codes (m, n_pad, W) uint32 PACKED words — the physical code plane
    (``jax_scheme.pack_codes``: each row's d codes concatenated at their
    allocated widths, W = ceil(R/32); padded rows are all-zero words; unpack
    at the machine's ``rates``).  This is the SAME buffer the mesh collectives
    move, the packed qgram kernels consume, and format-v3 checkpoints store.
    decoded (m, n_pad, d) reconstructions [padded rows zero]; T_inv (m, d, d)
    decorrelating inverses; rates (m, d) int32 per-dim bit allocation;
    sigma (m, d); scaled_cents (m, d, C) qgram decode tables; T (m, d, d)
    forward transforms.  The ``vq`` scheme fills ``decoded`` only (identity
    transforms, a zero-width word buffer — its channel state rides in the
    artifact's ``data`` dict instead)."""

    __slots__ = ()


class WireRun(collections.namedtuple(
    "WireRun",
    "state wire_bits payload_bits integrity_bits extras shards rows_demoted",
)):
    """What one ``SchemeSpec.run`` produced: the :class:`WireState`, the three
    ledgers (Theorem-1 ``wire_bits``, measured packed ``payload_bits``, CRC
    ``integrity_bits`` — all integers, all charged for what was TRANSMITTED,
    before any demotion), scheme-private ``extras``, the possibly
    fault-compacted :class:`PaddedShards` the protocol must assemble from
    (compaction moves each machine's CRC-surviving rows to the front, with
    ``lengths``/``mask`` shrunk to match), and ``rows_demoted`` — how many
    transmitted rows the receiver's CRC check rejected and masked out."""

    __slots__ = ()


def _wire_bits(rates, lengths, d: int, skip=None) -> int:
    """Paper §4 accounting: R bits/sample on the wire + side info per
    transmitting machine (the shared formula:
    :func:`repro.comm.accounting.wire_bits_formula`)."""
    from ...comm.accounting import wire_bits_formula

    return wire_bits_formula(rates, lengths, d, skip=skip)


def _mask_gram(G, mask_r, mask_c=None, pin_diag=True):
    """Zero padded rows/cols; optionally pin their diagonal to 1 so Cholesky
    stays SPD.  A point with k(·, pad)=0, y_pad=0 contributes nothing to the
    posterior, which makes the padded program bit-compatible with the
    unpadded one."""
    mask_c = mask_r if mask_c is None else mask_c
    Gm = G * (mask_r[:, None] * mask_c[None, :])
    if pin_diag:
        Gm = Gm + jnp.diag(1.0 - mask_r)
    return Gm


# --------------------------------------------------------------------------
# fit-once / serve-many: the FittedProtocol artifact
# --------------------------------------------------------------------------


@partial(
    jax.tree_util.register_dataclass,
    data_fields=[
        "counts", "cols", "wire_bits", "payload_bits", "integrity_bits",
        "rows_demoted",
    ],
    meta_fields=[],
)
@dataclasses.dataclass
class StreamState:
    """The device-resident mutable state of a streaming artifact.

    Everything :func:`update` changes per batch that is NOT a factor/data
    buffer lives here as int32 ARRAY leaves — per-machine row counts, the
    occupied-column counter of the capacity-padded buffers, and the three §4
    ledgers plus the CRC demotion count.  Keeping these as pytree data (not
    treedef metadata) is what makes consecutive updates and the warm predict
    share one traced program: bumping a ledger changes a leaf's value, never
    the treedef, so the jit cache keyed on (treedef, avals) still hits.

    ``counts`` (m,): true rows per machine (fit survivors + streamed rows).
    ``cols`` (): occupied column slots of the padded buffers — the append
    position of the next update.  Distinct from ``counts.sum()`` in the
    expert layouts (broadcast columns start at m*n_pad; PoE at n_pad) and
    after CRC demotions (demoted fit rows keep their padded slot).
    ``wire_bits`` / ``payload_bits`` / ``integrity_bits`` (): the Theorem-1
    ledger, the measured packed payload, and the CRC framing ledger.
    ``rows_demoted`` (): transmitted rows rejected by the receiver's CRC."""

    counts: jnp.ndarray
    cols: jnp.ndarray
    wire_bits: jnp.ndarray
    payload_bits: jnp.ndarray
    integrity_bits: jnp.ndarray
    rows_demoted: jnp.ndarray

    @classmethod
    def make(cls, counts, cols, wire_bits=0, payload_bits=0,
             integrity_bits=0, rows_demoted=0) -> "StreamState":
        i32 = lambda v: jnp.asarray(v, jnp.int32)
        return cls(
            counts=i32(counts), cols=i32(cols), wire_bits=i32(wire_bits),
            payload_bits=i32(payload_bits), integrity_bits=i32(integrity_bits),
            rows_demoted=i32(rows_demoted),
        )


@partial(
    jax.tree_util.register_dataclass,
    data_fields=["params", "y", "factors", "data", "wire", "stream"],
    meta_fields=[
        "protocol", "kernel", "gram_mode", "fuse", "gram_backend",
        "n_center", "fit_lengths", "block_order", "bits_per_sample",
        "max_bits", "impl", "scheme", "config",
    ],
)
@dataclasses.dataclass
class FittedProtocol:
    """The serving artifact of a communication-limited distributed GP.

    Produced by :func:`fit`, consumed by :func:`predict` (one jitted program;
    triangular solves only) and :func:`update` (rank-k factor growth).  It is
    a registered JAX pytree: array leaves checkpoint through
    ``repro.checkpoint`` (:func:`save_artifact` / :func:`load_artifact`,
    shardings respected on restore) and the static metadata rides in the
    treedef, so :func:`predict` retraces only when the protocol shape
    actually changes (e.g. after an :func:`update` grows the factors).

    Array fields (pytree leaves)
    ----------------------------
    params : trained :class:`~repro.core.gp.GPParams` (log-space hypers).
    y : targets in the artifact's column layout — center: (C,) flat
        [center block first]; broadcast: (C,) mask-zeroed; poe: (m, C)
        mask-zeroed — where C is the CAPACITY of the streaming buffers
        (``stream.cols`` columns occupied; a fresh fit is exact-size).
    factors : dict of cached solve factors, keyed per gram_mode —
        ``L_KK``/``W``/``L_M``/``alpha`` (Nyström woodbury form, see
        ``nystrom.nystrom_factors``) and/or ``L``/``alpha`` (dense
        ``gp.posterior_factors``).  Broadcast/PoE hold a leading machine
        axis (one batched factor set, NOT m objects).  The column-growable
        members live at capacity (padded exactly: zero columns / identity
        Cholesky slots — see :mod:`.streaming`).
    data : dict of query-time arrays — the Nyström bases (``Xc`` for center,
        ``Xs``+``mask`` for broadcast/poe), reconstructions (``X_recon``)
        with their column-validity mask (``valid``), squared norms
        (``sq_cols``/``sq_exact``/``sq_dec``), and scheme extras (the ``vq``
        test-channel state ``vq_A``/``vq_W_half``/``vq_rate_bits``).
    wire : :class:`WireState` — the frozen fit-once scheme state (codebooks,
        transforms, int codes).  :func:`update` re-encodes new symbols with
        it; the pallas backend decodes grams straight from its codes.  None
        for the zero-rate PoE baseline.
    stream : :class:`StreamState` — the device-resident row counts, occupied
        column counter, and §4 ledgers :func:`update` extends.  The legacy
        integer views (``lengths``/``wire_bits``/``payload_bits``/
        ``integrity_bits``/``rows_demoted``) are read-only properties that
        synchronize these leaves to host.

    Static metadata (treedef)
    -------------------------
    protocol / kernel / gram_mode / fuse / gram_backend / scheme — registry
    names (see :mod:`repro.core.registry`); n_center (center's exact-block
    size K), fit_lengths (per-machine FIT-TIME row counts — frozen, the
    streaming counts live in ``stream``), block_order (center's gram-row
    machine order), bits_per_sample, max_bits, impl (``"batched"``
    single-host or ``"mesh"`` machines-as-devices: factors live sharded
    along the mesh axis and :func:`predict` runs as one shard_map program
    with a psum/KL fusion epilogue), and config — the full
    :class:`~repro.core.config.DGPConfig` this artifact was fitted under
    (recorded in the checkpoint's ``meta.json``; ``None`` only on artifacts
    restored from pre-config checkpoints before defaults kick in).
    """

    params: GPParams
    y: jnp.ndarray
    factors: dict
    data: dict
    wire: WireState | None
    stream: StreamState
    protocol: str
    kernel: str
    gram_mode: str
    fuse: str
    gram_backend: str
    n_center: int
    fit_lengths: tuple
    block_order: tuple | None
    bits_per_sample: int
    max_bits: int
    impl: str = "batched"
    scheme: str = "per_symbol"
    config: object | None = None  # DGPConfig (opaque here: no import cycle)

    # -- legacy integer views (host sync of the StreamState leaves) ---------

    @property
    def lengths(self) -> tuple:
        """Per-machine true row counts (fit survivors + streamed rows)."""
        return tuple(
            int(v) for v in np.asarray(jax.device_get(self.stream.counts))
        )

    @property
    def wire_bits(self) -> int:
        """The paper's §4 Theorem-1 ledger, extended by every update."""
        return int(jax.device_get(self.stream.wire_bits))

    @property
    def payload_bits(self) -> int:
        """The packed payload PHYSICALLY moved (whole uint32 words per valid
        row + side info); exceeds the ledger only by per-word padding."""
        return int(jax.device_get(self.stream.payload_bits))

    @property
    def integrity_bits(self) -> int:
        """The CRC framing ledger (accounting.CRC_BITS per transmitted row)."""
        return int(jax.device_get(self.stream.integrity_bits))

    @property
    def rows_demoted(self) -> int:
        """Transmitted rows the receiver's CRC check demoted to masked rows."""
        return int(jax.device_get(self.stream.rows_demoted))

    # -- conveniences (the paper-facing entry points return artifacts) ------

    def predict(self, X_star, available=None):
        """Serve one query batch from the cached factors — see :func:`predict`."""
        return predict(self, X_star, available)

    def health(self, available=None) -> "ServeHealth":
        """Degradation status of this artifact — see :func:`serve_health`."""
        return serve_health(self, available)

    def update(self, X_new, y_new, machine: int = 0):
        """Stream in new points — see :func:`update`."""
        return update(self, X_new, y_new, machine)

    def save(self, directory: str, step: int = 0) -> str:
        """Checkpoint this artifact — see :func:`save_artifact`."""
        return save_artifact(self, directory, step)

    def _gram(self, params):
        """Rebuild the TRAIN-time gram at the given params (debug/inspection;
        the serve path never calls this — predictions run off cached
        factors).  Center protocol, xla assembly."""
        if self.protocol != "center":
            raise NotImplementedError("_gram inspection is center-protocol only")
        k = gram_fn(self.kernel)
        X = self.data["X_recon"]
        if self.gram_mode == "direct":
            return k(params, X)
        Xc = self.data["Xc"]
        G_KK = k(params, Xc)
        G_KN = k(params, Xc, X)
        if self.gram_mode == "nystrom_fitc":
            exact = prior_diag(self.kernel, params, self.data["sq_exact"])
            return nystrom_complete(G_KK, G_KN, exact_diag=exact)
        return nystrom_complete(G_KK, G_KN)


def _as_config(
    bits_per_sample, protocol, kernel, steps, lr, gram_mode, fuse, method,
    gram_backend, max_bits, train_impl, impl, scheme,
):
    """The loose legacy kwargs as one validated DGPConfig (``method`` wins
    over ``fuse`` for the PoE protocol, matching the old signatures)."""
    from ..config import DGPConfig

    return DGPConfig(
        protocol=protocol,
        scheme=scheme,
        kernel=kernel,
        fusion=method if protocol == "poe" else fuse,
        impl=impl,
        gram_backend=gram_backend,
        gram_mode=gram_mode,
        bits_per_sample=int(bits_per_sample),
        max_bits=int(max_bits),
        steps=int(steps),
        lr=float(lr),
        train_impl=train_impl,
    )


def _apply_fit_faults(parts, cfg):
    """Dataset-level fault injection at fit() entry (drop/NaN shards from
    ``cfg.faults``) plus the guards that make the remaining fleet trainable:
    the §5.1 center and the broadcast/PoE training machine (machine 0) must
    survive — predict-time availability masks are where arbitrary machine
    loss is served.  Returns ``(parts, rows_removed)``."""
    plan = getattr(cfg, "faults", None) if cfg is not None else None
    if plan is None:
        return parts, 0
    from ...faults import apply_to_parts

    new_parts, removed = apply_to_parts(parts, plan)
    lengths = [int(p[0].shape[0]) for p in new_parts]
    if not any(lengths):
        raise ValueError(
            "fault plan removed every row from every machine — nothing to fit"
        )
    if cfg.protocol == "center" and lengths[cfg.center] == 0:
        raise ValueError(
            f"fault plan emptied the center machine ({cfg.center}) — the "
            "§5.1 protocol cannot fit without its exact block; drop a "
            "non-center machine or serve an old artifact degraded instead"
        )
    if cfg.protocol in ("broadcast", "poe") and lengths[0] == 0:
        raise ValueError(
            "fault plan emptied machine 0, where broadcast/poe train their "
            "hyperparameters — drop a different machine (prediction-time "
            "availability masks handle arbitrary loss)"
        )
    return new_parts, removed


@fit_span()
@full_precision
def fit(
    parts,
    bits_per_sample: int = 0,
    protocol: str = "center",
    *,
    kernel: str = "se",
    steps: int = 150,
    lr: float = 0.05,
    params: GPParams | None = None,
    gram_mode: str = "nystrom",
    fuse: str = "kl",
    method: str = "rbcm",
    gram_backend: str = "xla",
    max_bits: int | None = None,
    train_impl: str = "scan",
    impl: str = "batched",
    scheme: str = "per_symbol",
) -> FittedProtocol:
    """Run a distributed-GP protocol ONCE and return the serving artifact.

    This is the fit half of the fit/predict split: wire protocol (scheme fit +
    encode + decode, one vmapped jit), hyperparameter training (one lax.scan
    program), and ONE factorization of every predictive the protocol needs.
    The returned :class:`FittedProtocol` then serves any number of
    :func:`predict` query batches with no scheme refit and no Cholesky
    refactorization, supports streaming :func:`update`, and checkpoints via
    :func:`save_artifact`.

    protocol="center" (§5.1): every machine quantizes toward the center's
    covariance; the center Nyström-completes and holds one factor set.
    protocol="broadcast" (§5.2): every machine broadcasts once; m local
    Nyström factor sets are built under one vmap and fused (``fuse``: a
    ``repro.core.registry.FUSIONS`` name — "kl" = eqs. 62-64 barycenter, or
    a PoE-family combiner).
    protocol="poe": the zero-rate baseline (``method``: poe/gpoe/bcm/rbcm);
    ``bits_per_sample`` is ignored and the wire ledger is 0.

    scheme="per_symbol" (§4.2, default) puts int codes on the wire;
    scheme="vq" simulates the §4.1 Theorem-2 optimal test channel at the
    matched bit budget (batched impl, xla backend).

    impl="batched" (default) simulates the machines under one vmapped jit;
    impl="mesh" puts machines on a real device mesh — the wire protocol,
    factor builds, and (broadcast/PoE) predict run as shard_map programs
    whose only inter-machine channel is ``repro.comm``, per-machine factors
    come out sharded along the mesh axis, and the wire ledger is computed
    from what the collectives actually move.

    This is the engine under :meth:`repro.core.api.DistributedGP.fit`; prefer
    the facade (one validated :class:`~repro.core.config.DGPConfig` instead
    of loose kwargs) in new code.
    """
    if impl not in ("batched", "mesh"):
        raise ValueError(f'fit() impl must be "batched" or "mesh", got {impl!r}')
    from .. import quantizers as Q

    cfg = _as_config(
        bits_per_sample, protocol, kernel, steps, lr, gram_mode, fuse, method,
        gram_backend, Q.DEFAULT_MAX_BITS if max_bits is None else max_bits,
        train_impl, impl, scheme,
    )
    return PROTOCOLS.get(cfg.protocol).fit(parts, cfg, params)


# --------------------------------------------------------------------------
# predict: one jitted program per artifact, cached factors only
# --------------------------------------------------------------------------

# Incremented INSIDE the traced function body, so it counts (re)traces, not
# calls: a warm serve loop must leave it flat (benchmarks/serve_bench.py and
# tests/test_serving.py assert exactly that).
_SERVE_TRACES: collections.Counter = collections.Counter()


def serve_trace_count(protocol: str = "center") -> int:
    """How many times :func:`predict` has been (re)traced for a protocol —
    a warm serve loop holds this constant (no refit, no recompile)."""
    return _SERVE_TRACES[protocol]


def _machine_index(j):
    """The update() machine index as a device scalar via an EXPLICIT
    device_put of a numpy scalar.  ``jnp.int32(j)`` would materialize the
    same buffer through an IMPLICIT host-to-device transfer, which the
    strict-mode runtime contract (``jax.transfer_guard("disallow")`` around
    the streaming-update tests) rejects."""
    return jax.device_put(np.int32(j))


def _predict_impl(art: FittedProtocol, X_star, avail=None):
    _SERVE_TRACES[art.protocol] += 1  # runs at trace time only
    p = art.params
    noise = jnp.exp(p.log_noise)
    # tripwire: non-finite query rows are sanitized before the kernel map
    # (one NaN row would otherwise poison the whole batch through the solve)
    # and answered with the prior predictive below.  For finite inputs every
    # select is an identity, so the healthy path is bitwise unchanged.
    finite_row = jnp.isfinite(X_star).all(axis=-1)
    Xq = jnp.where(finite_row[:, None], X_star, 0.0)
    sq_star = jnp.sum(Xq**2, -1)
    g_ss = prior_diag(art.kernel, p, sq_star)
    mu, var = PROTOCOLS.get(art.protocol).predict(
        art, Xq, sq_star, g_ss, noise, avail
    )
    ok = finite_row & jnp.isfinite(mu) & jnp.isfinite(var)
    mu = jnp.where(ok, mu, 0.0)
    var = jnp.where(ok, var, g_ss + noise)  # degrade to the prior, not NaN
    return mu, var


_predict_jit = jax.jit(_predict_impl)


def _uses_mesh_predict(art: FittedProtocol) -> bool:
    # §5.1 serving is center-local by construction (one factor set at the
    # center, nothing to fuse) — center artifacts serve on the host path
    return art.impl == "mesh" and art.protocol in ("broadcast", "poe")


def _availability(art: FittedProtocol, available):
    """Normalize a machine-availability mask to (m,) float32 — or ``None``
    for the all-alive fast path (statically identical to the pre-fault
    program).  ``None`` in means "derive from the artifact": machines whose
    shards were emptied by fit-time faults are marked down automatically."""
    # fit_lengths is the sync-free source of truth for the zero pattern:
    # update() refuses machines that transmitted nothing at fit time, so a
    # machine's row count is zero iff its FIT row count is zero
    m = len(art.fit_lengths)
    if available is None:
        if all(n > 0 for n in art.fit_lengths):
            return None
        return jnp.asarray([1.0 if n > 0 else 0.0 for n in art.fit_lengths],
                           jnp.float32)
    av = np.asarray(available, np.float32).reshape(-1)
    if av.shape[0] != m:
        raise ValueError(
            f"available mask has {av.shape[0]} entries for m={m} machines"
        )
    return jnp.asarray((av > 0).astype(np.float32))


@full_precision
def predict(art: FittedProtocol, X_star, available=None):
    """Serve one query batch from a fitted artifact: (mean, var) at X_star.

    ONE jitted program per artifact shape, O(t) per query batch: the cross
    inner products against the stored bases, the kernel map, and triangular
    solves against the cached factors.  No scheme refit, no Cholesky
    refactorization, no hyperparameter step happens here — verify with
    :func:`predict_op_counts` / :func:`serve_trace_count`.  Retraces only
    when the artifact's shapes change (a fresh :func:`fit`, an
    :func:`update`, a new query-batch size, or a new availability pattern).
    Mesh broadcast/PoE artifacts serve through one shard_map program with a
    psum/KL fusion epilogue instead (:func:`.mesh._predict_mesh_impl`).

    ``available``: optional (m,) machine-availability mask (1 = alive) for
    degraded-mode serving — broadcast/PoE fusions renormalize over the
    surviving experts (variance inflated accordingly, see
    docs/fault_model.md); the center protocol serves its last-good factor
    set regardless (the center holds everything), with the loss reported by
    :func:`serve_health`.  ``None`` derives the mask from the artifact
    (machines emptied by fit-time faults are already marked down)."""
    X_star = jnp.asarray(X_star, jnp.float32)
    avail = _availability(art, available)
    if _uses_mesh_predict(art):
        from . import mesh

        return mesh._predict_mesh_jit(art, X_star, avail)
    return _predict_jit(art, X_star, avail)


# --------------------------------------------------------------------------
# update: streaming append via rank-k factor updates (device-resident)
# --------------------------------------------------------------------------

# Incremented INSIDE each protocol's traced update body (the serve-trace
# idiom): consecutive in-bucket update() calls must leave it flat —
# tests/test_streaming.py and benchmarks/stream_bench.py assert exactly that.
_UPDATE_TRACES: collections.Counter = collections.Counter()


def update_trace_count(protocol: str = "center") -> int:
    """How many times the streaming :func:`update` program has been
    (re)traced for a protocol — consecutive in-bucket updates hold this
    constant (the retrace-free streaming contract; a bucket crossing costs
    exactly one retrace)."""
    return _UPDATE_TRACES[protocol]


@full_precision
def update(art: FittedProtocol, X_new, y_new, machine: int = 0) -> FittedProtocol:
    """Stream (X_new, y_new) arriving at ``machine`` into a fitted artifact.

    The fit-once economics in action: machine ``machine``'s FROZEN scheme
    state (codebooks + decorrelating transform fitted at :func:`fit` time;
    the test-channel parameters for ``scheme="vq"``) re-encodes only the new
    symbols, charging the frozen per-machine rate to the ledger — no scheme
    refit, no new side info.  The cached factors then grow by rank-k updates
    (``nystrom.chol_update_rank`` for the Nyström woodbury core,
    ``nystrom.chol_append_at`` for dense factors) written IN PLACE into the
    capacity-padded buffers (:mod:`.streaming`), so the whole append runs as
    ONE device-resident jitted program whose traced shapes never change
    within a bucket: consecutive updates hit the jit cache
    (:func:`update_trace_count` stays flat), and the warm :func:`predict`
    program reads the same buffers, so the first predict after an in-bucket
    update does not recompile either.  Per-symbol streams run the full wire
    plane (encode→pack→CRC→unpack→decode) INSIDE the traced program; the
    ``machine`` index is traced too, so every machine shares one cache
    entry.  Returns a NEW artifact (the input is unchanged).

    Center protocol: points landing on the center are exact and cost 0 wire
    bits; the rank-K Nyström basis stays fixed either way (appended points
    extend the columns, not the basis).  Broadcast: default "nystrom" mode
    only.  PoE: the new points extend ``machine``'s expert (zero-rate,
    exact).  A machine that transmitted no rows at fit time (dropped or
    fully demoted) has no frozen codebooks and is REFUSED.  Under a
    ``flip_rate`` fault plan the streamed batch is corrupted on the wire
    like a fit-time batch: CRC-failing rows are demoted (only the new rows
    are at risk), the full transmission is still charged to the ledgers.
    Within-tolerance agreement with a from-scratch refit on the concatenated
    data is locked by tests/test_serving.py and tests/test_streaming.py."""
    X_new = jnp.asarray(X_new, jnp.float32)
    y_new = jnp.asarray(y_new, jnp.float32)
    if X_new.ndim != 2 or y_new.ndim != 1 or y_new.shape[0] != X_new.shape[0]:
        raise ValueError("update expects X_new (n_new, d), y_new (n_new,)")
    m = len(art.fit_lengths)
    if not 0 <= machine < m:
        raise ValueError(f"machine {machine} out of range (m={m})")
    if art.fit_lengths[machine] == 0:
        raise ValueError(
            f"machine {machine} transmitted no rows at fit time (dropped or "
            "fully demoted) — it has no frozen codebooks to stream under; "
            "route the batch to a surviving machine or refit"
        )
    # tripwire: a NaN/Inf point would poison the rank-k factor growth (and
    # every subsequent predict) — drop hostile rows, loudly, instead
    finite = np.isfinite(np.asarray(X_new)).all(axis=1) & np.isfinite(
        np.asarray(y_new)
    )
    if not finite.all():
        import warnings

        warnings.warn(
            f"update(): dropping {int((~finite).sum())} non-finite point(s) "
            f"of {finite.size} (machine {machine})",
            stacklevel=2,
        )
        if not finite.any():
            return art  # nothing usable arrived; the artifact is unchanged
        keep = jnp.asarray(np.flatnonzero(finite))
        X_new, y_new = X_new[keep], y_new[keep]
    if X_new.shape[0] == 0:
        return art  # a (0, d) batch: nothing to append, nothing to charge
    pre = _prepare_update(art, X_new, y_new, machine)
    if isinstance(pre, FittedProtocol):
        return pre  # every transmitted row was demoted: ledger-only bump
    X_new, y_new, pre = pre
    from . import streaming

    art = streaming.ensure_capacity(art, X_new.shape[0])
    return PROTOCOLS.get(art.protocol).update(art, X_new, y_new, machine, pre)


def _prepare_update(art: FittedProtocol, X_new, y_new, machine: int):
    """Host-side update prep: decide which re-encode path the batch takes.

    Returns ``(X_new, y_new, pre)`` where ``pre`` is either ``None`` — the
    fully-traced path: the protocol's jitted update program re-encodes
    in-jit via ``SchemeSpec.reencode_traced`` (per-symbol transmitting
    machines; one cache entry shared by every machine) — or a 5-tuple
    ``(decoded, wire_add, payload_add, integrity_add, demoted_add)`` of
    precomputed arrays (the vq scheme's host-sampled channel, the center's
    own exact points, and fault-corrupted batches).  When a fault plan
    demotes EVERY row, returns the ledger-bumped artifact directly."""
    n_new = X_new.shape[0]
    spec = SCHEMES.get(art.scheme)
    center = art.block_order[0] if art.block_order else 0
    is_center_point = art.protocol == "center" and machine == center
    transmits = art.wire is not None and art.protocol != "poe" \
        and not is_center_point
    plan = getattr(art.config, "faults", None) if art.config is not None \
        else None
    fitc_side = 32 * n_new if (
        art.protocol == "center" and art.gram_mode == "nystrom_fitc"
    ) else 0  # exact |x|^2 side channel rides along with transmitted rows

    if transmits and plan is not None and \
            getattr(plan, "flip_rate", 0.0) > 0.0 and \
            spec.update_corrupt is not None:
        keep_idx, decoded, w_add, p_add, i_add, demoted = spec.update_corrupt(
            art, machine, X_new, plan
        )
        w_add, p_add = w_add + fitc_side, p_add + fitc_side
        if keep_idx.size == 0:
            # the receiver kept nothing, but the bits still moved: charge the
            # ledgers and the demotion count, leave factors/counts untouched
            s = art.stream
            return dataclasses.replace(art, stream=StreamState.make(
                s.counts, s.cols,
                s.wire_bits + w_add, s.payload_bits + p_add,
                s.integrity_bits + i_add, s.rows_demoted + demoted,
            ))
        idx = jnp.asarray(keep_idx)
        pre = (decoded, jnp.int32(w_add), jnp.int32(p_add), jnp.int32(i_add),
               jnp.int32(demoted))
        return X_new[idx], y_new[idx], pre
    if transmits and spec.reencode_traced is None:
        # host-side scheme (vq samples its simulated channel eagerly); its
        # test-channel stream carries no CRC framing (integrity delta 0)
        decoded, w_add, p_add = spec.reencode(art, machine, X_new)
        pre = (jnp.asarray(decoded, jnp.float32), jnp.int32(w_add + fitc_side),
               jnp.int32(p_add + fitc_side), jnp.int32(0), jnp.int32(0))
        return X_new, y_new, pre
    if is_center_point:
        # the center's own data is local: exact, zero wire cost
        pre = (X_new, jnp.int32(0), jnp.int32(0), jnp.int32(0), jnp.int32(0))
        return X_new, y_new, pre
    # per-symbol transmitting machines (and the zero-rate PoE experts, which
    # never re-encode): fully traced — the jitted program does the wire work
    return X_new, y_new, None


def _reencode(art: FittedProtocol, machine: int, X_new):
    """(X̂, wire_bits, payload_bits) for new symbols under ``machine``'s
    frozen scheme — dispatched on the artifact's wire scheme (registry
    lookup).  Per-symbol streams pass through the packed code plane (encode
    -> pack -> unpack -> decode), so the payload charge is whole uint32
    words per point while the ledger charge is the frozen allocated rate."""
    return SCHEMES.get(art.scheme).reencode(art, machine, X_new)


# --------------------------------------------------------------------------
# degraded-mode health reporting
# --------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class ServeHealth:
    """Degradation status of a serving artifact — what :func:`predict` is
    actually working with, instead of NaNs.

    status : ``"ok"`` (full fleet, nothing demoted) or ``"degraded"``.
    machines / machines_lost : fleet size and the indices serving no rows
        (dropped at fit time or masked out by the availability argument).
    rows_demoted : transmitted rows the receiver's CRC check rejected.
    variance_inflation : the factor applied to the fused predictive variance
        by the KL barycenter's survivor renormalization (``m / m_alive``);
        1.0 for precision-weighted PoE-family fusions (their variance widens
        intrinsically as experts leave) and for the center protocol."""

    status: str
    machines: int
    machines_lost: tuple
    rows_demoted: int
    variance_inflation: float


def serve_health(art: FittedProtocol, available=None) -> ServeHealth:
    """Report what :func:`predict` degrades to under the given availability
    (``None`` = derived from the artifact, as in :func:`predict`)."""
    m = len(art.fit_lengths)
    avail = _availability(art, available)
    if avail is None:
        alive = [True] * m
    else:
        alive = [bool(a) for a in np.asarray(avail) > 0]
    lost = tuple(
        j for j in range(m) if not alive[j] or art.fit_lengths[j] == 0
    )
    n_alive = m - len(lost)
    demoted = int(getattr(art, "rows_demoted", 0))
    inflation = 1.0
    if lost and art.protocol in ("broadcast", "poe") and art.fuse == "kl" \
            and n_alive > 0:
        inflation = m / n_alive
    status = "ok" if not lost and demoted == 0 else "degraded"
    return ServeHealth(
        status=status, machines=m, machines_lost=lost,
        rows_demoted=demoted, variance_inflation=inflation,
    )


# --------------------------------------------------------------------------
# artifact persistence (repro.checkpoint) + serve-path introspection
# --------------------------------------------------------------------------


def save_artifact(art: FittedProtocol, directory: str, step: int = 0) -> str:
    """Checkpoint a fitted artifact: array leaves through
    ``repro.checkpoint.save_checkpoint`` (atomic npz), static metadata to a
    sidecar json — including the full :class:`~repro.core.config.DGPConfig`
    and an artifact format version, so :func:`load_artifact` can rebuild the
    exact configuration years later.  Predictions from the restored artifact
    are bitwise identical (tests/test_serving.py)."""
    from ...checkpoint import save_artifact as _save
    from ..config import ARTIFACT_FORMAT_VERSION

    cfg = getattr(art, "config", None)
    meta = {
        "format_version": ARTIFACT_FORMAT_VERSION,
        "protocol": art.protocol, "kernel": art.kernel,
        "gram_mode": art.gram_mode, "fuse": art.fuse,
        "gram_backend": art.gram_backend, "n_center": art.n_center,
        "lengths": list(art.lengths),
        "fit_lengths": list(art.fit_lengths),  # v5: frozen fit-time counts
        "block_order": list(art.block_order) if art.block_order is not None else None,
        "bits_per_sample": art.bits_per_sample, "max_bits": art.max_bits,
        "wire_bits": art.wire_bits, "has_wire": art.wire is not None,
        "payload_bits": art.payload_bits,  # v3: measured packed payload
        "integrity_bits": art.integrity_bits,  # v4: CRC framing ledger
        "rows_demoted": art.rows_demoted,
        "impl": art.impl,  # provenance; restore is always single-host
        "scheme": art.scheme,
        "config": cfg.asdict() if cfg is not None else None,
    }
    return _save(directory, step, art, meta)


def _pack_legacy_wire(wire: WireState, meta: dict) -> WireState:
    """Pre-v3 wire state (unpacked int32 codes) -> the packed code plane."""
    from ...comm.accounting import row_bits
    from .. import jax_scheme

    m, n_pad, d = wire.codes.shape
    if meta.get("scheme", "per_symbol") == "vq":
        # vq never had codes (the stored plane was all -1 sentinels)
        return wire._replace(codes=jnp.zeros((m, n_pad, 0), jnp.uint32))
    rbits = row_bits(meta["bits_per_sample"], d, meta["max_bits"])
    words = jax.vmap(
        lambda c, r: jax_scheme.pack_codes(c, r, total_bits=rbits)
    )(jnp.asarray(wire.codes), jnp.asarray(wire.rates))
    return wire._replace(codes=words)


def load_artifact(directory: str, step: int | None = None, shardings=None) -> FittedProtocol:
    """Restore a :func:`save_artifact` checkpoint into a fresh artifact.

    Always restores as a SINGLE-HOST artifact (``impl="batched"``): a mesh
    fit's checkpoint round-trips to an equivalent host-serving artifact
    (sharded factors were gathered at save time).  Format version 3 stores
    the wire codes PACKED (uint32 words — 4-16x smaller than the old int32
    plane at b<=8); older checkpoints store unpacked int32 codes, which are
    packed on load so every restored artifact carries the same in-memory
    representation (predictions are bitwise identical either way —
    tests/test_ckpt_backcompat.py).  Pre-redesign checkpoints
    (format version 1: no ``config``/``scheme`` in ``meta.json``) load too —
    the scheme defaults to ``per_symbol`` and a
    :class:`~repro.core.config.DGPConfig` is reconstructed from the legacy
    metadata fields.  Format version 5 persists the streaming state
    (``stream/*`` leaves: per-machine counts, occupied-column counter, the
    ledgers) and capacity-padded factor buffers; v1-v4 checkpoints load at
    exact capacity with the state rebuilt from the json integers (their
    first :func:`update` pads up), and pre-v5 PoE streamed extras are folded
    into the shared capacity layout.  ``shardings``:
    optional — a single ``Sharding``/device applied to every leaf, or a
    ``{leaf_key: sharding}`` dict (keys as in the npz: ``factors/W``,
    ``data/Xc``, ``wire/codes``, ...) for per-leaf placement; leaves are
    ``jax.device_put`` into place on restore."""
    from ...checkpoint import load_artifact_arrays
    from ..config import ARTIFACT_FORMAT_VERSION, DGPConfig

    meta, arrays = load_artifact_arrays(directory, step)
    version = meta.get("format_version", 1)  # pre-redesign checkpoints: v1
    if version > ARTIFACT_FORMAT_VERSION:
        raise ValueError(
            f"artifact format version {version} is newer than this code "
            f"supports ({ARTIFACT_FORMAT_VERSION}) — upgrade the package to "
            "load this checkpoint"
        )

    def put(key):
        arr = arrays[key]
        sh = shardings.get(key) if isinstance(shardings, dict) else shardings
        return jax.device_put(arr, sh) if sh is not None else jnp.asarray(arr)

    params = GPParams(*(put(f"params/{f}") for f in GPParams._fields))
    factors = {
        k.split("/", 1)[1]: put(k) for k in arrays if k.startswith("factors/")
    }
    data = {k.split("/", 1)[1]: put(k) for k in arrays if k.startswith("data/")}
    wire = None
    if meta["has_wire"]:
        wire = WireState(*(put(f"wire/{f}") for f in WireState._fields))
        if version < 3 and wire.codes.dtype != jnp.uint32:
            # pre-v3 checkpoints stored the unpacked int32 code plane; pack
            # it into the uint32 wire representation every consumer (qgram
            # kernels, update(), re-save) now shares.  -1 sentinel rows pack
            # to all-zero words, matching a fresh fit's layout.
            wire = _pack_legacy_wire(wire, meta)
    cfg_dict = meta.get("config")
    config = (
        DGPConfig.from_dict(cfg_dict) if cfg_dict
        else DGPConfig.from_legacy_meta(meta)
    )
    # restored artifacts always serve single-host; the recorded config keeps
    # the fit-time impl as provenance, the reconstruction pins "batched"
    config = dataclasses.replace(config, impl="batched")
    protocol, y = meta["protocol"], put("y")
    stream_fields = [f.name for f in dataclasses.fields(StreamState)]
    if all(f"stream/{f}" in arrays for f in stream_fields):
        # v5 streaming checkpoints persist the StreamState leaves directly
        # (checked by presence, not version: re-stamped copies keep working)
        stream = StreamState(*(put(f"stream/{f}") for f in stream_fields))
    else:
        # v1-v4: derive the occupied-column count from the exact-size arrays
        # (pre-streaming artifacts ARE their own capacity) and lift the json
        # integer ledgers onto device
        if protocol == "poe":
            cols = int(y.shape[-1])
            if "X_extra" in data:  # legacy streamed extras: folded below
                cols += int(data["X_extra"].shape[0])
        else:
            cols = int(y.shape[0])
        stream = StreamState.make(
            meta["lengths"], cols, meta["wire_bits"],
            meta.get("payload_bits", 0),  # pre-v3: not recorded
            meta.get("integrity_bits", 0),  # pre-v4: not recorded
            meta.get("rows_demoted", 0),
        )
    if protocol == "center" and "valid" not in data:
        # pre-v5 center artifacts carried no column-validity mask (every
        # column was live); the padded predict path multiplies it in
        data["valid"] = jnp.ones_like(y)
    if protocol == "poe" and "X_extra" in data:
        # pre-v5 streamed PoE extras lived in side arrays (X_extra/extra_mask/
        # y_extra); fold them into the capacity layout every expert now
        # shares — the dense factors already carry the [n_pad | extras]
        # column order, so the fold appends in that same order
        Xe = data.pop("X_extra")
        em = data.pop("extra_mask")
        ye = data.pop("y_extra")
        mcnt = em.shape[0]
        y = jnp.concatenate([y, ye[None, :] * em], axis=1)
        data["Xs"] = jnp.concatenate(
            [data["Xs"], jnp.broadcast_to(Xe[None], (mcnt,) + Xe.shape)], axis=1
        )
        data["mask"] = jnp.concatenate([data["mask"], em], axis=1)
        sq_e = jnp.sum(Xe**2, -1)
        data["sq_exact"] = jnp.concatenate(
            [data["sq_exact"], jnp.broadcast_to(sq_e[None], em.shape)], axis=1
        )
    return FittedProtocol(
        params=params, y=y, factors=factors, data=data, wire=wire,
        stream=stream,
        protocol=protocol, kernel=meta["kernel"],
        gram_mode=meta["gram_mode"], fuse=meta["fuse"],
        gram_backend=meta["gram_backend"], n_center=meta["n_center"],
        fit_lengths=tuple(meta.get("fit_lengths", meta["lengths"])),
        block_order=tuple(meta["block_order"]) if meta["block_order"] is not None else None,
        bits_per_sample=meta["bits_per_sample"], max_bits=meta["max_bits"],
        impl="batched",
        scheme=meta.get("scheme", "per_symbol"), config=config,
    )


def predict_op_counts(art: FittedProtocol, X_star, ops=("cholesky", "eigh")) -> dict:
    """Count primitives in the :func:`predict` program for this artifact —
    the structural serve-path check: a warm predict must contain ZERO
    ``cholesky`` (no refactorization) and ZERO ``eigh`` (no scheme refit)
    equations.  Mesh artifacts are checked on their actual shard_map serve
    program (the walk descends into the shard_map body jaxpr).

    Thin wrapper over :mod:`repro.analysis` (which generalizes this into the
    declarative :func:`repro.analysis.check_contracts` rule system); kept for
    benchmarks/serve_bench.py's BENCH_serve.json and the existing test
    suites.  Trace-neutral: the abstract trace this performs is excluded from
    ``serve_trace_count``, so callers may order it freely around retrace
    assertions."""
    from ...analysis.contracts import predict_jaxpr

    jaxpr = predict_jaxpr(art, X_star)
    counts = {op: 0 for op in ops}
    for eqn in _walk_jaxpr(jaxpr.jaxpr):
        if eqn.primitive.name in counts:
            counts[eqn.primitive.name] += 1
    return counts


def _walk_jaxpr(jaxpr):
    from ...analysis.jaxpr_walk import walk_jaxpr

    return walk_jaxpr(jaxpr)
