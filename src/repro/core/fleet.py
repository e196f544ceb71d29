"""Multi-tenant fleet serving: stacked vmapped predict over homogeneous
:class:`~.protocols.base.FittedProtocol` artifacts.

The fit-once/serve-from-cached-factors story (§4/§5) scales to a FLEET of
independent posteriors by exploiting that artifacts fitted under the same
:class:`~.config.DGPConfig` at the same capacity bucket are pytrees with
IDENTICAL treedefs and leaf shapes.  Stacking T of them leaf-wise produces a
single tenant-major pytree, and ONE vmapped/jitted program — the per-tenant
serve path batched over the leading axis — answers a whole mixed-tenant
micro-batch in one dispatch:

* :func:`bucket_key` — the homogeneity class: (treedef, leaf shapes/dtypes).
  Same key <=> stackable.  :func:`pad_to_capacity` co-buckets artifacts with
  different update histories by padding to a common power-of-two capacity
  (the exact-padding rules of :mod:`.protocols.streaming`).
* :class:`FleetStack` — a device-resident stack with FIXED slot count and an
  LRU tenant->row map.  Tenant swaps write one row in place
  (``leaf.at[row].set``) and queries gather rows by a TRACED index vector
  (``leaf[idx]`` inside the jit), so neither admitting a tenant nor changing
  the tenant mix of a batch ever retraces: the jit cache is keyed on
  (treedef, avals) and both stay fixed (:func:`fleet_trace_count` proves it).
* broadcast artifacts on the fused serve path get a TENANT-BATCHED epilogue:
  the operand build of :func:`~.protocols.broadcast._fused_epilogue_operands`
  is vmapped and the whole (T, m)-expert moment reduction runs as one
  ``kernels.epilogue`` fleet launch (per-tenant accumulators — tenants never
  share a moment row).  Everything else serves through a plain vmap of the
  single-tenant ``_predict_impl`` (center/PoE predicts are matmul-shaped and
  batch cleanly).
* :class:`ArtifactCache` — LRU over loaded artifacts, capacity in artifacts
  or bytes, loader-on-miss (checkpoint-backed via :class:`ArtifactStore`).
* :class:`ArtifactStore` — a directory of per-tenant v6 packed checkpoints
  (:func:`~.protocols.base.save_artifact` format); restores are bitwise
  (tests/test_fleet.py locks cache-mediated == direct load).

The request-coalescing half (micro-batching under a latency budget) lives in
:mod:`repro.launch.fleet`; docs/fleet_serving.md has the design notes and
benchmarks/fleet_bench.py the ≥256-tenant zipf-traffic gates.
"""
from __future__ import annotations

import collections
import dataclasses
import os
import re

import numpy as np
import jax
import jax.numpy as jnp

from .linalg_safe import full_precision
from .registry import FUSIONS
from .protocols import base
from .protocols import broadcast as _broadcast
from .protocols import streaming
from .protocols.base import FittedProtocol

__all__ = [
    "bucket_key",
    "artifact_nbytes",
    "pad_to_capacity",
    "scale_targets",
    "stack_artifacts",
    "FleetStack",
    "ArtifactCache",
    "ArtifactStore",
    "fleet_trace_count",
]


# --------------------------------------------------------------------------
# homogeneity: when do artifacts co-batch?
# --------------------------------------------------------------------------


def bucket_key(art: FittedProtocol):
    """The stacking-compatibility class of an artifact: its pytree treedef
    (which carries ALL static metadata — protocol, kernel, fusion, config,
    fit_lengths ...) plus every leaf's (shape, dtype).  Two artifacts share
    a bucket iff their keys compare equal; then — and only then — their
    leaves stack into one tenant-major pytree that a single traced program
    serves.  Hashable, so it keys the server's stack table directly."""
    leaves, treedef = jax.tree_util.tree_flatten(art)
    sig = tuple(
        (tuple(np.shape(leaf)), jnp.asarray(leaf).dtype.name)
        for leaf in leaves
    )
    return (treedef, sig)


def artifact_nbytes(art: FittedProtocol) -> int:
    """Device bytes of an artifact's array leaves (the unit of the cache's
    byte-capacity accounting)."""
    return sum(
        int(np.prod(np.shape(leaf)) * jnp.asarray(leaf).dtype.itemsize)
        for leaf in jax.tree_util.tree_leaves(art)
    )


def pad_to_capacity(art: FittedProtocol, capacity: int | None = None
                    ) -> FittedProtocol:
    """Pad an artifact's column-growable buffers up to ``capacity`` (default:
    the next power of two of its occupied columns) using the EXACT padding
    rules of :mod:`.protocols.streaming` — zero columns, identity Cholesky
    slots, masked cross-columns — so the padded artifact predicts identically.

    This is the co-bucketing primitive: a freshly fitted artifact (exact-size
    buffers) and one that streamed a few updates (grown buffers) land in
    different buckets until both are padded to the same capacity.  Host-side
    by construction (one device round-trip per admitted artifact, never in
    the serve loop)."""
    cols = int(jax.device_get(art.stream.cols))
    cap_now = int(art.y.shape[-1])
    target = streaming.next_pow2(cols) if capacity is None else int(capacity)
    if target < cap_now:
        if cap_now == cols and streaming.next_pow2(cols) == cap_now:
            return art  # already exactly at a power-of-two capacity
        raise ValueError(
            f"pad_to_capacity: target {target} is below the artifact's "
            f"current capacity {cap_now} (buffers never shrink)"
        )
    if target == cap_now:
        return art
    return streaming._grow(art, target)


def scale_targets(art: FittedProtocol, c: float) -> FittedProtocol:
    """An EXACT artifact for the target vector ``c * y``: the posterior mean
    operands (``alpha = (G + s2 I)^{-1} y`` and the cached ``walpha``) are
    linear in y, so scaling those leaves yields exactly the artifact a
    protocol run on scaled targets (at the same hyperparameters) would
    produce — without paying the fit.  Per-expert GP variances are
    y-independent; a moment-matching fusion's combined variance shifts with
    the (scaled) expert means, as a real refit's would.  Benchmarks and
    tests use this to build large fleets of genuinely distinct posteriors
    from a handful of fits (same bucket by construction: only leaf VALUES
    change)."""
    c = float(c)
    factors = dict(art.factors)
    for k in ("alpha", "walpha"):
        if k in factors:
            factors[k] = c * factors[k]
    return dataclasses.replace(art, y=c * art.y, factors=factors)


def stack_artifacts(arts) -> FittedProtocol:
    """Stack homogeneous artifacts leaf-wise into one tenant-major pytree
    (every leaf gains a leading tenant axis; static metadata is shared).
    Raises ``ValueError`` naming the first mismatching tenant when the
    artifacts are not bucket-compatible."""
    arts = list(arts)
    if not arts:
        raise ValueError("stack_artifacts: need at least one artifact")
    key0 = bucket_key(arts[0])
    for i, a in enumerate(arts[1:], start=1):
        if bucket_key(a) != key0:
            raise ValueError(
                f"stack_artifacts: artifact {i} is not bucket-compatible "
                f"with artifact 0 (different config/protocol metadata or "
                f"leaf shapes — pad_to_capacity() aligns capacity buckets; "
                f"heterogeneous configs need separate stacks)"
            )
    return jax.tree.map(lambda *leaves: jnp.stack(leaves), *arts)


# --------------------------------------------------------------------------
# the one fleet predict program
# --------------------------------------------------------------------------

# Incremented INSIDE the traced fleet program (the serve-trace idiom of
# core/protocols/base.py): a steady-state fleet loop — tenants swapping
# in/out of stacks included — must leave it flat.  benchmarks/fleet_bench.py
# gates on exactly that.
_FLEET_TRACES: collections.Counter = collections.Counter()


def fleet_trace_count(protocol: str = "broadcast") -> int:
    """How many times the stacked fleet predict program has been (re)traced
    for a protocol — tenant swaps and batch-mix changes hold this constant
    (row writes and traced gather indices never change the jit key)."""
    return _FLEET_TRACES[protocol]


def _fleet_fused_operands(art, Xq, avail, proj):
    """Single-tenant slice of the fused-epilogue operand build (vmapped over
    the stacked tenant axis by :func:`_fleet_predict_fused`).  Mirrors the
    sanitize prologue of ``base._predict_impl`` term for term — the parity
    tests lock the two paths together.  ``proj`` is the tenant's
    PRECOMPUTED woodbury projector (built once at admit time and kept
    resident next to the stack), so the hot path skips the per-query
    ``cho_solve`` chain the single-tenant serve pays on every call."""
    from .gp import prior_diag

    p = art.params
    noise = jnp.exp(p.log_noise)
    finite_row = jnp.isfinite(Xq).all(axis=-1)
    Xqc = jnp.where(finite_row[:, None], Xq, 0.0)
    sq_star = jnp.sum(Xqc**2, -1)
    g_ss = prior_diag(art.kernel, p, sq_star)
    G, Ainv, P, walpha, prior, w = _broadcast._fused_epilogue_operands(
        art, Xqc, sq_star, g_ss, noise, avail, proj
    )
    return finite_row, noise, g_ss, G, Ainv, P, walpha, prior, w


def _fleet_predict_fused(art, Xq, avail, proj, block):
    """Tenant-batched fused serve: vmap the operand build, then ONE
    ``epilogue_moments_fleet`` launch reduces every tenant's experts into
    per-tenant moment rows, and a vmapped ``finalize`` finishes.  The
    non-finite tripwire of ``base._predict_impl`` is applied per tenant row
    (a hostile query row degrades ITS tenant's row to the prior and touches
    nothing else)."""
    from ..kernels.epilogue.ops import epilogue_moments_fleet

    spec = FUSIONS.get(art.fuse)
    m = len(art.fit_lengths)
    av_ax = None if avail is None else 0
    pr_ax = None if proj is None else 0
    finite, noise, g_ss, G, Ainv, P, walpha, prior, w = jax.vmap(
        _fleet_fused_operands, in_axes=(0, 0, av_ax, pr_ax)
    )(art, Xq, avail, proj)
    S = epilogue_moments_fleet(G, Ainv, P, walpha, g_ss, prior, w,
                               fuse=art.fuse, block=block)
    mu, var = jax.vmap(lambda Si, pri: spec.finalize(Si, m, pri))(S, prior)
    ok = finite & jnp.isfinite(mu) & jnp.isfinite(var)
    mu = jnp.where(ok, mu, 0.0)
    var = jnp.where(ok, var, g_ss + noise[:, None])
    return mu, var


def _fleet_predict_impl(stack, idx, Xq, avail=None, proj=None, *, block=None):
    """The fleet serve program: gather the batch's tenant rows from the
    resident stack BY TRACED INDEX (idx value changes never retrace), then
    answer every tenant in one batched pass.  ``stack`` is a stacked
    FittedProtocol (leading tenant axis on every leaf); ``Xq`` is
    (S, t, d); ``avail`` is None or (S, m); ``proj`` is the stack's
    slot-aligned precomputed projector buffer (or None off the fused path);
    ``block`` is the statically resolved fleet-epilogue t-tile."""
    _FLEET_TRACES[stack.protocol] += 1  # runs at trace time only
    art = jax.tree.map(lambda leaf: leaf[idx], stack)
    if art.protocol == "broadcast" and art.impl != "mesh" and \
            _broadcast._uses_fused_epilogue(art, FUSIONS.get(art.fuse)):
        P = None if proj is None else proj[idx]
        return _fleet_predict_fused(art, Xq, avail, P, block)
    av_ax = None if avail is None else 0
    return jax.vmap(base._predict_impl, in_axes=(0, 0, av_ax))(art, Xq, avail)


_fleet_predict_jit = jax.jit(_fleet_predict_impl, static_argnames=("block",))

# admit-time projector builds (one artifact / a whole stacked tree); jitted so
# repeated admits into the same bucket reuse one compiled program
_projector_jit = jax.jit(_broadcast._epilogue_projector)
_stack_projector_jit = jax.jit(jax.vmap(_broadcast._epilogue_projector))


# --------------------------------------------------------------------------
# FleetStack: fixed device-resident slots, LRU tenant->row map
# --------------------------------------------------------------------------


class FleetStack:
    """A device-resident capacity bucket of the fleet: ``slots`` stacked
    artifact rows, an LRU ``tenant -> row`` map, and the one jitted predict
    program over them.

    The slot count is FIXED at construction (padded up to a power of two),
    which is the whole retrace story: admitting a tenant writes one row in
    place (``leaf.at[row].set(...)`` — shapes unchanged), evicting is just
    forgetting a map entry, and a query batch gathers its rows through a
    traced index vector, so the steady-state loop compiles exactly once per
    (batch shape, availability pattern).  Admits run off the hot path (host
    work per CACHE miss, not per request)."""

    @full_precision
    def __init__(self, tenants, slots: int | None = None):
        items = list(tenants.items()) if isinstance(tenants, dict) \
            else list(tenants)
        if not items:
            raise ValueError("FleetStack: need at least one tenant artifact")
        self.key = bucket_key(items[0][1])
        n_slots = streaming.next_pow2(len(items)) if slots is None \
            else int(slots)
        if n_slots < len(items):
            raise ValueError(
                f"FleetStack: {len(items)} tenants exceed {n_slots} slots"
            )
        # unoccupied slots hold a copy of the first artifact: every row must
        # be a VALID artifact (the vmapped program computes all S gathered
        # rows), and unaddressed rows are never returned to a caller
        padded = [a for _, a in items]
        padded += [items[0][1]] * (n_slots - len(items))
        self.tree = stack_artifacts(padded)
        self.slots = n_slots
        self.protocol = items[0][1].protocol
        self._rows: "collections.OrderedDict[object, int]" = \
            collections.OrderedDict()
        self._free = list(range(len(items), n_slots))[::-1]
        self.swaps = 0  # admits that evicted a resident tenant
        self._block = None
        self._block_t = None
        for row, (tid, art) in enumerate(items):
            if tid in self._rows:
                raise ValueError(f"FleetStack: duplicate tenant id {tid!r}")
            self._rows[tid] = row
        # fused-path stacks keep the query-independent woodbury projector
        # resident per slot: built ONCE per admit (off the hot path), so the
        # stacked dispatch skips the per-query cho_solve chain the
        # single-tenant serve pays on every predict
        a0 = items[0][1]
        self._proj = None
        if self.protocol == "broadcast" and a0.impl != "mesh" and \
                _broadcast._uses_fused_epilogue(a0, FUSIONS.get(a0.fuse)):
            self._proj = _stack_projector_jit(self.tree)

    def __contains__(self, tenant) -> bool:
        return tenant in self._rows

    def __len__(self) -> int:
        return len(self._rows)

    def tenants(self) -> tuple:
        """Resident tenant ids, least-recently-used first."""
        return tuple(self._rows)

    @full_precision
    def admit(self, tenant, art: FittedProtocol) -> int:
        """Make ``tenant`` resident (write its leaves into one slot row) and
        return the row.  A re-admit refreshes the row in place; a full stack
        evicts the least-recently-used tenant.  Never retraces the predict
        program: only leaf VALUES change."""
        if bucket_key(art) != self.key:
            raise ValueError(
                f"FleetStack.admit({tenant!r}): artifact is not "
                "bucket-compatible with this stack (different config "
                "metadata or leaf shapes; pad_to_capacity() aligns capacity "
                "buckets, heterogeneous configs need their own stack)"
            )
        if tenant in self._rows:
            row = self._rows[tenant]
            self._rows.move_to_end(tenant)
        elif self._free:
            row = self._free.pop()
            self._rows[tenant] = row
        else:
            _, row = self._rows.popitem(last=False)  # evict the LRU tenant
            self._rows[tenant] = row
            self.swaps += 1
        self.tree = jax.tree.map(
            lambda leaf, new: leaf.at[row].set(new), self.tree, art
        )
        if self._proj is not None:
            self._proj = self._proj.at[row].set(_projector_jit(art))
        return row

    def touch(self, tenant) -> None:
        """Refresh a resident tenant's LRU recency without rewriting its row
        (raises ``KeyError`` when not resident).  The server touches every
        batch member during grouping so a same-batch admit can never evict a
        co-batched tenant."""
        self._rows.move_to_end(tenant)

    def rows(self, tenants) -> np.ndarray:
        """Slot rows for a tenant batch (touches their LRU recency).  Raises
        ``KeyError`` naming the non-resident tenants."""
        missing = [t for t in tenants if t not in self._rows]
        if missing:
            raise KeyError(
                f"FleetStack: tenants not resident: {missing!r} (admit() "
                "them first — FleetServer does this through its cache)"
            )
        for t in tenants:
            self._rows.move_to_end(t)
        return np.asarray([self._rows[t] for t in tenants], np.int32)

    def _epilogue_block(self, t: int):
        """Statically resolve (and memoize) the tuned fleet-epilogue t-tile
        for this stack's launch shape — outside the trace, so a cache miss
        can actually time candidates (satellite: the fleet shape family is
        swept and cached like the single-tenant ones)."""
        if self.protocol != "broadcast" or "Ainv" not in self.tree.factors:
            return None
        if self._block_t == t:
            return self._block
        from ..kernels.epilogue.ops import fleet_epilogue_block

        m = len(self.tree.fit_lengths)
        K = int(self.tree.factors["Ainv"].shape[-1])
        self._block = fleet_epilogue_block(self.slots, m, t, K,
                                           fuse=self.tree.fuse)
        self._block_t = t
        return self._block

    @full_precision
    def predict(self, tenants, Xq, avail=None):
        """Serve one mixed-tenant micro-batch in ONE dispatch.

        ``tenants``: length-S sequence of resident tenant ids (repeats
        allowed); ``Xq``: (S, t, d) per-tenant query batches; ``avail``:
        optional (S, m) per-tenant availability masks (rows of ones = that
        tenant healthy).  Returns (mu, var), each (S, t)."""
        idx = self.rows(tenants)
        Xq = jnp.asarray(Xq, jnp.float32)
        if Xq.ndim != 3 or Xq.shape[0] != idx.shape[0]:
            raise ValueError(
                f"FleetStack.predict: Xq must be (S, t, d) with "
                f"S == len(tenants) == {idx.shape[0]}, got {Xq.shape}"
            )
        if avail is not None:
            avail = jnp.asarray(
                (np.asarray(avail, np.float32) > 0).astype(np.float32)
            )
            m = len(self.tree.fit_lengths)
            if avail.shape != (idx.shape[0], m):
                raise ValueError(
                    f"FleetStack.predict: avail must be (S, m) = "
                    f"({idx.shape[0]}, {m}), got {tuple(avail.shape)}"
                )
        block = self._epilogue_block(int(Xq.shape[1]))
        return _fleet_predict_jit(self.tree, jnp.asarray(idx), Xq, avail,
                                  self._proj, block=block)


# --------------------------------------------------------------------------
# ArtifactCache: LRU over loaded artifacts, loader-on-miss
# --------------------------------------------------------------------------


class ArtifactCache:
    """LRU cache of loaded serving artifacts with checkpoint-backed
    load-on-miss.

    ``loader(tenant) -> FittedProtocol`` supplies misses (typically
    :meth:`ArtifactStore.load`); capacity is bounded in ARTIFACTS
    (``capacity``), BYTES (``capacity_bytes``, leaf nbytes via
    :func:`artifact_nbytes`), or both — eviction drops least-recently-used
    entries until both bounds hold.  A single artifact larger than the byte
    budget is kept (capacity bounds the cache, it does not refuse service).
    Hit/miss/eviction counters feed the bench's reported hit rate."""

    def __init__(self, loader, capacity: int | None = None,
                 capacity_bytes: int | None = None):
        self._loader = loader
        self.capacity = None if capacity is None else int(capacity)
        self.capacity_bytes = None if capacity_bytes is None \
            else int(capacity_bytes)
        if self.capacity is not None and self.capacity < 1:
            raise ValueError("ArtifactCache: capacity must be >= 1")
        self._items: "collections.OrderedDict[object, FittedProtocol]" = \
            collections.OrderedDict()
        self._nbytes: dict = {}
        self.total_bytes = 0
        self.hits = 0
        self.misses = 0
        self.evictions = 0

    def __contains__(self, tenant) -> bool:
        return tenant in self._items

    def __len__(self) -> int:
        return len(self._items)

    @property
    def hit_rate(self) -> float:
        n = self.hits + self.misses
        return self.hits / n if n else 0.0

    def get(self, tenant) -> FittedProtocol:
        """The cached artifact for ``tenant``; a miss pays one loader call
        (checkpoint restore) and may evict LRU entries."""
        art = self._items.get(tenant)
        if art is not None:
            self.hits += 1
            self._items.move_to_end(tenant)
            return art
        self.misses += 1
        art = self._loader(tenant)
        self.put(tenant, art)
        return art

    def put(self, tenant, art: FittedProtocol) -> None:
        """Insert/refresh an entry, then evict LRU entries until the
        artifact- and byte-capacity bounds both hold."""
        if tenant in self._items:
            self.total_bytes -= self._nbytes.pop(tenant)
            del self._items[tenant]
        nb = artifact_nbytes(art)
        self._items[tenant] = art
        self._nbytes[tenant] = nb
        self.total_bytes += nb
        while len(self._items) > 1 and (
            (self.capacity is not None and len(self._items) > self.capacity)
            or (self.capacity_bytes is not None
                and self.total_bytes > self.capacity_bytes)
        ):
            old, _ = self._items.popitem(last=False)
            self.total_bytes -= self._nbytes.pop(old)
            self.evictions += 1

    def stats(self) -> dict:
        return {
            "entries": len(self._items),
            "bytes": self.total_bytes,
            "hits": self.hits,
            "misses": self.misses,
            "evictions": self.evictions,
            "hit_rate": self.hit_rate,
        }


# --------------------------------------------------------------------------
# ArtifactStore: per-tenant v6 packed checkpoints on disk
# --------------------------------------------------------------------------


def _tenant_dirname(tenant) -> str:
    safe = re.sub(r"[^A-Za-z0-9_.-]", "_", str(tenant))
    return f"tenant_{safe}"


class ArtifactStore:
    """A directory of per-tenant artifact checkpoints
    (``root/tenant_<id>/``), each in the v6 packed format of
    :func:`~.protocols.base.save_artifact` — CRC-checksummed npz + metadata
    sidecar, so a bit-rotted tenant fails loud at load instead of serving
    garbage.  ``store.load`` is the canonical :class:`ArtifactCache` loader;
    restores are bitwise-identical to serving the original artifact."""

    def __init__(self, root: str):
        self.root = str(root)
        os.makedirs(self.root, exist_ok=True)

    def path(self, tenant) -> str:
        return os.path.join(self.root, _tenant_dirname(tenant))

    def save(self, tenant, art: FittedProtocol, step: int = 0) -> str:
        return base.save_artifact(art, self.path(tenant), step)

    def load(self, tenant, step: int | None = None) -> FittedProtocol:
        return base.load_artifact(self.path(tenant), step)

    def meta(self, tenant, step: int | None = None) -> dict:
        """The checkpoint's static metadata WITHOUT loading the arrays — a
        cheap bucket-compatibility screen (protocol/config/capacity) before
        paying a full restore."""
        from ..checkpoint import load_artifact_meta

        return load_artifact_meta(self.path(tenant), step)

    def tenants(self) -> list:
        pref = "tenant_"
        return sorted(
            d[len(pref):] for d in os.listdir(self.root)
            if d.startswith(pref)
            and os.path.isdir(os.path.join(self.root, d))
        )
