"""impl="mesh": machines are devices, the collectives are the wire.

The production SPMD substrate shared by every protocol: machines live along a
1-D ``("machines",)`` device mesh, the per-symbol wire protocol runs as ONE
``jax.shard_map`` program whose only inter-machine channel is
``repro.comm.q_all_gather`` (int codes + O(d²) fp32 side info; the ledger is
computed from what the collective actually moves), per-machine factors are
built device-local and live SHARDED along the mesh axis, and broadcast/PoE
serving is one shard_map program with a psum/KL fusion epilogue.  All of it
is locked to the host/batched impls by tests/test_conformance.py.
"""
from __future__ import annotations

import dataclasses
import functools

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from jax import shard_map
from .. import jax_scheme
from ..gp import (
    GPParams,
    gram_fn,
    kernel_from_inner,
    prior_diag,
    posterior_factors,
    posterior_apply,
    posterior_from_gram,
)
from ..nystrom import (
    nystrom_factors,
    nystrom_apply,
    nystrom_serve_cache,
    nystrom_apply_cached,
    nystrom_kinv,
    chol_update_rank,
    chol_append_at,
)
from ..linalg_safe import DEFAULT_JITTER
from ..fusion import kl_fuse_diag
from ..registry import FUSIONS, SCHEMES
from .base import StreamState, WireState, _mask_gram, _SERVE_TRACES, _UPDATE_TRACES

__all__ = [
    "MESH_AXIS",
    "machine_mesh",
    "broadcast_gp_mesh",
]

MESH_AXIS = "machines"


def machine_mesh(m: int) -> Mesh:
    """A 1-D ``("machines",)`` mesh over the first m local devices — the
    execution substrate of ``impl="mesh"``.  m may not exceed the number of
    attached devices (four on a v5e 2x2 host).  A CPU-only host can stand in
    with placeholder devices, ``XLA_FLAGS=--xla_force_host_platform_device_
    count=8`` (tests/conftest.py sets it)."""
    devs = jax.devices()
    if m > len(devs):
        raise ValueError(
            f'impl="mesh" needs one device per machine: m={m} > '
            f"{len(devs)} attached {devs[0].platform} devices — use at most "
            f"{len(devs)} machines, or impl=\"batched\" for more"
        )
    return Mesh(np.asarray(devs[:m]), (MESH_AXIS,))


@functools.lru_cache(maxsize=None)
def _mesh_wire_fn(m: int, total_bits: int, max_bits: int, mode: str, center: int):
    """One compiled SPMD wire program per (m, R, mode): every device fits its
    scheme, the int codes + O(d²) side info move through comm.q_all_gather,
    and everything the collective moved comes back replicated."""
    from ...comm import q_all_gather

    mesh = machine_mesh(m)

    def body(x_blk, mask_blk):
        _, st = q_all_gather(
            x_blk[0], MESH_AXIS, total_bits, max_bits, mask=mask_blk[0],
            mode=mode, center=center, return_state=True,
        )
        return st

    return jax.jit(shard_map(
        body, mesh=mesh, in_specs=(P(MESH_AXIS), P(MESH_AXIS)),
        out_specs=P(), check_vma=False,
    ))


def _run_wire_protocol_mesh(X, mask, total_bits: int, max_bits: int, mode: str, center: int):
    """The per-symbol wire protocol as a REAL device-mesh program (machines =
    devices along ``MESH_AXIS``; ``comm.q_all_gather`` is the only
    inter-machine channel, and what it gathers is the PACKED uint32 code
    plane).  Returns the same :class:`~.base.WireState` layout as the batched
    program (replicated arrays; ``codes`` are the gathered packed words),
    the Theorem-1 ledger, the payload bits MEASURED from the buffer the
    collective moved, and the CRC integrity bits — all integer-equal to the
    host oracle's §4 accounting / the shared formulas
    (tests/test_conformance.py)."""
    m, n_pad, d = X.shape
    st = _mesh_wire_fn(m, total_bits, max_bits, mode, center)(X, mask)
    # UNSHARD the replicated outputs.  shard_map's out_specs=P() leaves every
    # array COMMITTED to NamedSharding(mesh, P()) — replicated over all m
    # devices — and that sharding is sticky: any downstream jit that consumes
    # these arrays (the center protocol's host predict, train_gp's scan)
    # compiles as an m-way SPMD program with per-dispatch cross-device
    # synchronization, which is what collapsed mesh predict throughput as m
    # grew (23.2k -> 1.9k qps from m=2 to m=8).  One host pull here at fit
    # time erases the committed sharding (this function already host-syncs to
    # int() the ledger scalars); the mesh-served protocols explicitly
    # re-shard what they need via _shard_machine_axis.
    st = jax.tree.map(lambda a: jnp.asarray(jax.device_get(a)), st)
    tables = jax_scheme.scheme_tables(total_bits, max_bits)
    cents = jax_scheme.scaled_centroids_batched(st["rates"], st["sigma"], tables)
    ws = WireState(
        st["codes"], st["decoded"], st["T_inv"], st["rates"], st["sigma"],
        cents, st["T"],
    )
    return (
        ws, int(st["wire_bits"]), int(st["payload_bits"]),
        int(st["integrity_bits"]),
    )


def _shard_machine_axis(tree, mesh: Mesh):
    """device_put every leaf with its leading (machine) axis along the mesh."""
    sh = NamedSharding(mesh, P(MESH_AXIS))
    return jax.tree.map(lambda a: jax.device_put(a, sh), tree)


@functools.lru_cache(maxsize=None)
def _mesh_broadcast_factor_fn(m: int, kernel: str, fused_serve: bool = True):
    """Per-machine §5.2 Nyström factor build as ONE shard_map program: device i
    assembles ITS view (own block exact, peers from the wire reconstructions)
    and factorizes it locally; the factor set comes out SHARDED along the
    mesh axis (out_specs P(MESH_AXIS)).  ``fused_serve`` additionally builds
    the K-sized ``nystrom_serve_cache`` operands device-local, so mesh serving
    runs the fused matmul-only epilogue."""
    mesh = machine_mesh(m)

    def body(x_blk, mask_blk, dec, sq_dec, mask_flat, y_flat, p):
        i = jax.lax.axis_index(MESH_AXIS)
        x, mi = x_blk[0], mask_blk[0]
        n_pad = x.shape[0]
        noise = jnp.exp(p.log_noise)
        sqx = jnp.sum(x**2, -1)
        cols = dec.at[i].set(x)  # own (exact) block replaces its reconstruction
        sq_cols = sq_dec.at[i].set(sqx).reshape(-1)
        ip_KK = x @ x.T
        ip_KN = jnp.moveaxis(
            jnp.einsum("nd,jNd->jnN", x, cols), 0, 1
        ).reshape(n_pad, m * n_pad)
        G_KK = _mask_gram(kernel_from_inner(kernel, p, ip_KK, sqx, sqx), mi)
        G_KN = kernel_from_inner(kernel, p, ip_KN, sqx, sq_cols) * (
            mi[:, None] * mask_flat[None, :]
        )
        fac = nystrom_factors(G_KK, G_KN, y_flat, noise)
        if fused_serve:
            fac.update(nystrom_serve_cache(fac))
        return jax.tree.map(lambda a: a[None], fac)

    return jax.jit(shard_map(
        body, mesh=mesh,
        in_specs=(P(MESH_AXIS), P(MESH_AXIS), P(), P(), P(), P(), P()),
        out_specs=P(MESH_AXIS), check_vma=False,
    ))


@functools.lru_cache(maxsize=None)
def _mesh_poe_factor_fn(m: int, kernel: str):
    """Zero-rate expert factorization, one dense Cholesky per device (own
    shard only — no wire at all), factors sharded along the mesh axis."""
    mesh = machine_mesh(m)

    def body(x_blk, y_blk, mask_blk, p):
        x, yj, mj = x_blk[0], y_blk[0], mask_blk[0]
        noise = jnp.exp(p.log_noise)
        sqj = jnp.sum(x**2, -1)
        G = _mask_gram(kernel_from_inner(kernel, p, x @ x.T, sqj, sqj), mj)
        fac = posterior_factors(G, yj * mj, noise)
        return jax.tree.map(lambda a: a[None], fac)

    return jax.jit(shard_map(
        body, mesh=mesh,
        in_specs=(P(MESH_AXIS), P(MESH_AXIS), P(MESH_AXIS), P()),
        out_specs=P(MESH_AXIS), check_vma=False,
    ))


# --------------------------------------------------------------------------
# mesh serving: one shard_map program with a psum fusion epilogue
# --------------------------------------------------------------------------


def _predict_mesh_impl(art, X_star, avail=None):
    """Mesh serving: ONE shard_map program — each device applies ITS machine's
    cached factors to the query batch (triangular solves only, exactly like
    the batched path) and the predictives meet in a psum/KL fusion epilogue
    (eqs. 62-64 as two psums; the PoE combiners as precision-weighted psums;
    any registered fusion with a ``fuse_psum`` form plugs in).  Factors/data
    stay sharded along the mesh axis throughout.

    ``avail``: optional replicated (m,) float availability mask — degraded
    serving renormalizes the psum fusion over surviving machines (each device
    reads its own weight ``w_i = avail[axis_index]``).  ``None`` (the healthy
    fleet) keeps the unweighted epilogue; each distinct availability pattern
    costs one retrace, like any other static serve knob."""
    _SERVE_TRACES[art.protocol] += 1  # runs at trace time only
    m = len(art.fit_lengths)
    mesh = machine_mesh(m)
    weighted = avail is not None
    fusion = FUSIONS.get(art.fuse)
    fused_moments = fusion.moments is not None and fusion.finalize is not None
    if fusion.fuse_psum is None and not fused_moments:
        raise NotImplementedError(
            f"fusion {art.fuse!r} has no mesh (psum or moments) form — serve "
            "the checkpointed single-host artifact instead"
        )
    # static: key presence selects the fused matmul-only apply
    cached = art.protocol == "broadcast" and "Ainv" in art.factors

    def body(fac, Xs_blk, mask_blk, sq_blk, X_star, av, p):
        fac_i = jax.tree.map(lambda a: a[0], fac)
        Xi, mi, sqi = Xs_blk[0], mask_blk[0], sq_blk[0]
        noise = jnp.exp(p.log_noise)
        sq_star = jnp.sum(X_star**2, -1)
        g_ss = prior_diag(art.kernel, p, sq_star)
        w_i = av[jax.lax.axis_index(MESH_AXIS)] if weighted else None
        # streamed points live in the capacity-padded buffers (mask-zeroed
        # where invalid), so one uniform apply serves updated artifacts too
        G_sK = kernel_from_inner(
            art.kernel, p, X_star @ Xi.T, sq_star, sqi
        ) * mi[None, :]
        if art.protocol == "broadcast":
            if cached:
                mu_i, s2_i = nystrom_apply_cached(fac_i, G_sK, g_ss, noise)
            else:
                mu_i, s2_i = nystrom_apply(fac_i, G_sK, g_ss, noise)
        else:  # poe
            mu_i, s2_i = posterior_apply(fac_i, G_sK, g_ss)
        prior = g_ss + noise
        if fused_moments:
            # fused epilogue: ONE stacked psum carries the (3, t) moment rows
            # instead of the 2-3 collectives of fuse_psum — halves the
            # per-dispatch collective cost that dominates mesh serve latency
            # (m is static: no psum(1) just to count machines)
            S = jax.lax.psum(
                fusion.moments(mu_i, s2_i, prior, w_i), MESH_AXIS
            )
            return fusion.finalize(S, m, prior)
        if not weighted:  # legacy 4-arg fuse_psum keeps the healthy path
            return fusion.fuse_psum(mu_i, s2_i, prior, MESH_AXIS)
        return fusion.fuse_psum(mu_i, s2_i, prior, MESH_AXIS, w_i)

    fn = shard_map(
        body, mesh=mesh,
        in_specs=(
            P(MESH_AXIS), P(MESH_AXIS), P(MESH_AXIS), P(MESH_AXIS),
            P(), P(), P(),
        ),
        out_specs=(P(), P()), check_vma=False,
    )
    av = None if avail is None else jnp.asarray(avail, jnp.float32)
    return fn(
        art.factors, art.data["Xs"], art.data["mask"], art.data["sq_exact"],
        X_star, av, art.params,
    )


_predict_mesh_jit = jax.jit(_predict_mesh_impl)


# --------------------------------------------------------------------------
# mesh streaming: the update is a shard_map program too (no host pull)
# --------------------------------------------------------------------------


def _update_mesh_impl(art, X_new, y_new, j, pre):
    """Mesh streaming append: ONE jitted program in which the new batch is
    re-encoded through the frozen codebooks (encode→pack→CRC→unpack→decode,
    all on device via the scheme's traced reencode) and the SHARDED factors
    grow in place on their own devices under shard_map — the ledgers extend
    as device-resident int32 leaves and nothing is pulled to host.  The
    machine index ``j`` and append cursor are traced, so consecutive
    in-bucket updates hit one cache entry regardless of target machine."""
    _UPDATE_TRACES[art.protocol] += 1  # runs at trace time only
    m = len(art.fit_lengths)
    mesh = machine_mesh(m)
    kernel = art.kernel
    n_new = X_new.shape[0]
    pos = art.stream.cols
    zero = jnp.int32(0)

    if art.protocol == "broadcast":
        if pre is None:
            # the full wire plane runs inside this traced program; the
            # decoded batch is replicated to every device like fit time
            decoded, w_add, p_add, i_add = SCHEMES.get(
                art.scheme
            ).reencode_traced(art, j, X_new)
            d_add = jnp.int32(0)
        else:  # host-precomputed batch (faulted transmission)
            decoded, w_add, p_add, i_add, d_add = pre
        y2 = jax.lax.dynamic_update_slice(art.y, y_new, (pos,))

        def body(fac, Xs_blk, mask_blk, sq_blk, Xn, dec, y2r, pr, jj, ps):
            i = jax.lax.axis_index(MESH_AXIS)
            fac_i = jax.tree.map(lambda a: a[0], fac)
            Xi, mi, sqi = Xs_blk[0], mask_blk[0], sq_blk[0]
            s2 = jnp.exp(pr.log_noise) + DEFAULT_JITTER
            X_eff = jnp.where(i == jj, Xn, dec)  # own batch exact, peers X̂
            sqn = jnp.sum(X_eff**2, -1)
            G_KN_new = kernel_from_inner(
                kernel, pr, Xi @ X_eff.T, sqi, sqn
            ) * mi[:, None]
            W_new = jax.scipy.linalg.solve_triangular(
                fac_i["L_KK"], G_KN_new, lower=True
            )
            W2 = jax.lax.dynamic_update_slice(fac_i["W"], W_new, (0, ps))
            L_M2 = chol_update_rank(fac_i["L_M"], W_new)
            fac2 = {
                "L_KK": fac_i["L_KK"], "W": W2, "L_M": L_M2,
                "alpha": nystrom_kinv(W2, L_M2, s2, y2r),
            }
            if "Ainv" in fac_i:  # fused-serve cache rides along device-local
                fac2["Ainv"] = fac_i["Ainv"]
                fac2["walpha"] = W2 @ fac2["alpha"]
            return jax.tree.map(lambda a: a[None], fac2)

        factors = shard_map(
            body, mesh=mesh,
            in_specs=(
                P(MESH_AXIS), P(MESH_AXIS), P(MESH_AXIS), P(MESH_AXIS),
                P(), P(), P(), P(), P(), P(),
            ),
            out_specs=P(MESH_AXIS), check_vma=False,
        )(
            art.factors, art.data["Xs"], art.data["mask"],
            art.data["sq_exact"], X_new, decoded, y2, art.params, j, pos,
        )
        data = art.data
    else:  # poe: zero-rate, the batch is machine j's own exact data
        w_add = p_add = i_add = d_add = jnp.int32(0)
        valid = jnp.broadcast_to(
            (jnp.arange(m)[:, None] == j).astype(jnp.float32), (m, n_new)
        )
        y2 = jax.lax.dynamic_update_slice(
            art.y, valid * y_new[None, :], (zero, pos)
        )

        def body(fac, Xs_blk, mask_blk, sq_blk, Xn, y2r, pr, jj, ps):
            i = jax.lax.axis_index(MESH_AXIS)
            fac_i = jax.tree.map(lambda a: a[0], fac)
            Xi, mi, sqi = Xs_blk[0], mask_blk[0], sq_blk[0]
            s2 = jnp.exp(pr.log_noise) + DEFAULT_JITTER
            nn = Xn.shape[0]
            vi = jnp.where(i == jj, 1.0, 0.0) * jnp.ones((nn,), jnp.float32)
            Xi2 = jax.lax.dynamic_update_slice(Xi, Xn, (ps, 0))
            mi2 = jax.lax.dynamic_update_slice(mi, vi, (ps,))
            sqi2 = jax.lax.dynamic_update_slice(sqi, jnp.sum(Xn**2, -1), (ps,))
            kf = gram_fn(kernel)
            # OLD mask in the cross block: zero rows at/after the cursor keep
            # chol_append_at's contract; non-owners (vi=0) append decoupled
            # unit rows, masked out of their predict columns by mi2
            G_on = kf(pr, Xi2, Xn) * (mi[:, None] * vi[None, :])
            G_nn = _mask_gram(kf(pr, Xn), vi) + s2 * jnp.eye(nn)
            L2 = chol_append_at(fac_i["L"], G_on, G_nn, ps)
            fac2 = {
                "L": L2,
                "alpha": jax.scipy.linalg.cho_solve((L2, True), y2r[i]),
            }
            lift = lambda a: a[None]
            return jax.tree.map(lift, fac2), Xi2[None], mi2[None], sqi2[None]

        factors, Xs2, mask2, sq2 = shard_map(
            body, mesh=mesh,
            in_specs=(
                P(MESH_AXIS), P(MESH_AXIS), P(MESH_AXIS), P(MESH_AXIS),
                P(), P(), P(), P(), P(),
            ),
            out_specs=(P(MESH_AXIS),) * 4, check_vma=False,
        )(
            art.factors, art.data["Xs"], art.data["mask"],
            art.data["sq_exact"], X_new, y2, art.params, j, pos,
        )
        data = dict(art.data)
        data["Xs"], data["mask"], data["sq_exact"] = Xs2, mask2, sq2

    s = art.stream
    stream = StreamState(
        counts=s.counts.at[j].add(n_new), cols=s.cols + n_new,
        wire_bits=s.wire_bits + w_add, payload_bits=s.payload_bits + p_add,
        integrity_bits=s.integrity_bits + i_add,
        rows_demoted=s.rows_demoted + d_add,
    )
    return dataclasses.replace(art, y=y2, factors=factors, data=data,
                               stream=stream)


_update_mesh_jit_raw = jax.jit(_update_mesh_impl)

# Leaf path prefixes that are SUPPOSED to live sharded along the machine
# axis (that is the point of the substrate); every other artifact leaf is
# single-device, enforced by the mesh-update contract (repro.analysis:
# NoShardingLeak).
_MESH_SHARDED_LEAVES = ("factors/", "data/")


def _update_mesh_jit(art, X_new, y_new, j, pre):
    """In-bucket mesh update plus sharding hygiene on the outputs.

    The update program consumes mesh-sharded factors, so GSPMD commits ALL
    of its outputs to the mesh — the logically-replicated leaves (params,
    y, wire state, stream ledger) come back COMMITTED to a replicated
    NamedSharding over every device.  That is the PR-8 leak class: the
    commitment is sticky, so downstream host/batched consumers of those
    leaves compile as m-way SPMD with per-dispatch device sync, and the
    update program itself re-specializes between the first dispatch
    (uncommitted fit-time leaves) and every later one.  A single-device
    commitment is no fix — one jit cannot mix a leaf pinned to device 0
    with factors pinned to the mesh — so do exactly what the fit boundary
    does (see ``_mesh_wire_state``): host-sync the leaked leaves to erase
    the commitment.  Only the O(1)/O(rows) bookkeeping moves; the O(cols²)
    factor and data buffers stay device-resident and mesh-sharded, which is
    the streaming contract that matters.
    """
    out = _update_mesh_jit_raw(art, X_new, y_new, j, pre)
    leaves, treedef = jax.tree_util.tree_flatten_with_path(out)
    fixed = []
    for path, leaf in leaves:
        if (
            isinstance(leaf, jax.Array)
            and len(leaf.sharding.device_set) > 1
            and not _path_str(path).startswith(_MESH_SHARDED_LEAVES)
        ):
            leaf = jnp.asarray(jax.device_get(leaf))
        fixed.append(leaf)
    return jax.tree_util.tree_unflatten(treedef, fixed)


# --------------------------------------------------------------------------
# legacy one-shot mesh entry point (absorbed from the old core.mesh_gp)
# --------------------------------------------------------------------------


def broadcast_gp_mesh(
    mesh,
    axis: str,
    X,
    y,
    X_star,
    params: GPParams,
    *,
    kernel: str = "se",
    bits_per_sample: int = 32,
    max_bits: int = 8,
):
    """One-shot §5.2 broadcast on a caller-supplied mesh: devices along
    ``axis`` are machines, the wire is ``comm.q_all_gather`` (int codes),
    each device solves its dense local view, and the per-point predictives
    are KL-fused (eqs. 62-64) — all inside one jit/shard_map program.

    This is the original mesh prototype, kept for fixed-hyper one-shot runs
    (no training, no serving artifact).  The first-class mesh path is
    ``fit(..., impl="mesh")`` — it adds hyperparameter training, Nyström
    factor caching sharded along the mesh axis, streaming
    :func:`~.base.update`, and checkpointing.

    X: (n, d) globally, sharded over ``axis`` on dim 0 (n % n_devices == 0);
    y: (n,) likewise; X_star: (t, d) replicated.  Returns fused (mean, var).
    """
    from ...comm import q_all_gather

    k = gram_fn(kernel)

    def local_predict(X_all_blocks, y_all, own_idx, xs_l):
        """One device's §5.2 view: own block exact, peers reconstructed."""
        m, n_loc, d = X_all_blocks.shape
        # reorder so the exact (own) block is first — matches the Nyström layout
        order = jnp.argsort(
            jnp.where(jnp.arange(m) == own_idx, -1, jnp.arange(m))
        )
        Xv = X_all_blocks[order].reshape(m * n_loc, d)
        yv = y_all[order].reshape(m * n_loc)
        G = k(params, Xv)
        G_sn = k(params, xs_l, Xv)
        g_ss = jnp.diagonal(k(params, xs_l, xs_l))
        return posterior_from_gram(G, G_sn, g_ss, yv, jnp.exp(params.log_noise))

    def body(x_l, y_l, xs_l):
        idx = jax.lax.axis_index(axis)
        # the paper's wire: quantized codes, own block exact (repro.comm)
        x_blocks = q_all_gather(x_l, axis, bits_per_sample, max_bits)
        y_all = jax.lax.all_gather(y_l, axis)  # targets are scalars (unquantized)
        mu_i, s2_i = local_predict(x_blocks, y_all, idx, xs_l)
        # KL-barycenter fusion (eqs. 62-64) across the machine axis
        mus = jax.lax.all_gather(mu_i, axis)
        s2s = jax.lax.all_gather(s2_i, axis)
        return kl_fuse_diag(mus, s2s)

    fn = shard_map(
        body,
        mesh=mesh,
        in_specs=(P(axis, None), P(axis), P(None, None)),
        out_specs=(P(), P()),
        check_vma=False,
    )
    return jax.jit(fn)(X, y, X_star)


# --------------------------------------------------------------------------
# the impl="mesh" program contracts: overrides for the protocols whose serve
# program actually runs on the machine mesh (broadcast/PoE; center unshards
# at the fit boundary and keeps the batched contract)
# --------------------------------------------------------------------------
from ...analysis.contracts import (
    CollectiveBudget,
    Contract,
    LedgerAccounting,
    NoHostCallbacks,
    NoShardingLeak,
    _path_str,
    forbid_primitives,
    register_contract,
)

# _MESH_SHARDED_LEAVES (defined next to _update_mesh_jit above): factor and
# data leaves are deliberately mesh-sharded; anything else committed to more
# than one device is the PR-8 leak class (replicated-committed shard_map
# outputs turning every downstream jit m-way SPMD).

# The fused serve epilogue is ONE stacked psum of the (mu, s2-moment, weight)
# rows — the single collective the §4 wire model licenses at serve time.
# More than one means the legacy 2-3 psum epilogue (or an unaccounted
# channel) regressed in.
_MESH_SERVE_CONTRACT = Contract(
    name="mesh-serve",
    rules=(
        forbid_primitives(),
        NoHostCallbacks(),
        CollectiveBudget(max_count=1),
        NoShardingLeak(max_devices=1, allow_prefixes=_MESH_SHARDED_LEAVES),
        LedgerAccounting(),
    ),
)
_MESH_UPDATE_CONTRACT = Contract(
    name="mesh-update",
    rules=(
        NoShardingLeak(max_devices=1, allow_prefixes=_MESH_SHARDED_LEAVES),
        LedgerAccounting(),
    ),
)
for _protocol in ("broadcast", "poe"):
    register_contract(_protocol, "predict", _MESH_SERVE_CONTRACT, impl="mesh")
    register_contract(_protocol, "update", _MESH_UPDATE_CONTRACT, impl="mesh")
