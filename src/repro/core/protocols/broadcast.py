"""§5.2 broadcast protocol.

Every machine broadcasts codes fitted against Qy = sum of the *other*
machines' covariances; each machine builds its own Nyström gram (own block
exact), forms a local predictive, and the per-point predictives are fused
with a registered fusion rule (default: the KL barycenter, eqs. 62-64).
"""
from __future__ import annotations

import dataclasses
from functools import partial

import numpy as np
import jax
import jax.numpy as jnp

from .. import quantizers as Q
from ..distortion import second_moment
from ..schemes import PerSymbolScheme
from ..gp import (
    GPParams,
    gram_fn,
    kernel_from_inner,
    nystrom_from_inner,
    posterior_factors,
    posterior_apply,
    posterior_from_gram,
    train_gp,
)
from ..nystrom import (
    nystrom_posterior,
    nystrom_factors,
    nystrom_apply,
    nystrom_serve_cache,
    nystrom_projector,
    nystrom_apply_cached,
    nystrom_kinv,
    chol_update_rank,
)
from ..linalg_safe import DEFAULT_JITTER
from ..registry import FUSIONS, SCHEMES, ProtocolSpec, register_protocol
from ...spans import span
from . import base, mesh
from .base import (
    FittedProtocol,
    PaddedShards,
    StreamState,
    WireState,
    pad_parts,
    _mask_gram,
    _UPDATE_TRACES,
)

__all__ = ["broadcast_gp", "HostBroadcastGP", "fit_broadcast_host"]


# --------------------------------------------------------------------------
# the serial host oracle
# --------------------------------------------------------------------------


@dataclasses.dataclass
class HostBroadcastGP:
    """The ``impl="host"`` oracle's fitted state: one scipy scheme fit per
    machine, shared hypers trained at machine 0.  ``predict`` runs one dense
    solve per machine view and fuses — m serial host dispatches, kept as the
    reference the batched/mesh artifacts are locked against."""

    kernel: str
    params: GPParams
    parts: list
    decoded: list
    wire_bits: int
    gram_mode: str
    fuse: str
    payload_bits: int = 0  # packed-payload formula (accounting), for parity
    integrity_bits: int = 0  # CRC framing formula (accounting), for parity

    def predict(self, X_star, available=None):
        m = len(self.parts)
        k = gram_fn(self.kernel)
        p = self.params
        X_star = jnp.asarray(X_star, jnp.float32)
        y_parts = [yj for _, yj in self.parts]

        def machine_view(i):
            blocks = [
                self.parts[j][0] if j == i else self.decoded[j] for j in range(m)
            ]
            order = [i] + [j for j in range(m) if j != i]
            Xv = jnp.concatenate([blocks[j] for j in order], axis=0)
            yv = jnp.concatenate([y_parts[j] for j in order], axis=0)
            return Xv, yv, self.parts[i][0].shape[0]

        gram_mode = self.gram_mode

        @partial(jax.jit, static_argnums=(2,))
        def local_predict(Xv, yv, nc):
            Xc = Xv[:nc]
            g_ss = jnp.diagonal(k(p, X_star, X_star))
            if gram_mode == "nystrom":
                # consistent low-rank predictive (see CenterGP.predict)
                return nystrom_posterior(
                    k(p, Xc), k(p, Xc, Xv), yv, jnp.exp(p.log_noise),
                    k(p, X_star, Xc), g_ss,
                )
            G = k(p, Xv)  # "direct": all blocks from reconstructed points
            G_sn = k(p, X_star, Xv)
            return posterior_from_gram(G, G_sn, g_ss, yv, jnp.exp(p.log_noise))

        mus, s2s = [], []
        for i in range(m):
            Xv, yv, nc = machine_view(i)
            mu_i, s2_i = local_predict(Xv, yv, nc)
            mus.append(mu_i)
            s2s.append(s2_i)
        mus = jnp.stack(mus)
        s2s = jnp.stack(s2s)
        prior = jnp.diagonal(k(p, X_star, X_star)) + jnp.exp(p.log_noise)
        spec = FUSIONS.get(self.fuse)
        if available is None:  # legacy 3-arg fusions keep the healthy path
            return spec.fuse(mus, s2s, prior)
        w = (jnp.asarray(available, jnp.float32) > 0).astype(jnp.float32)
        return spec.fuse(mus, s2s, prior, w)


def fit_broadcast_host(parts, cfg, params=None) -> HostBroadcastGP:
    """Serial reference §5.2 fit: one scipy scheme fit per machine and shared
    hypers trained at machine 0 on its Nyström view (warm-started from
    ``params`` when given)."""
    plan = getattr(cfg, "faults", None)
    if plan is not None and plan.flip_rate > 0:
        raise NotImplementedError(
            "the host oracle has no packed wire plane to corrupt: inject "
            'flip faults with impl="batched" or impl="mesh"'
        )
    parts, _ = base._apply_fit_faults(parts, cfg)
    m = len(parts)
    S = [
        second_moment(Xj) if np.asarray(Xj).shape[0]
        else np.zeros((np.asarray(Xj).shape[1],) * 2, np.float32)
        for Xj, _ in parts
    ]
    S_tot = sum(S)
    # every machine encodes ONCE against the sum of the others' covariances
    # (a machine emptied by faults transmits nothing and is charged nothing)
    wire = 0
    decoded = []
    for j, (Xj, yj) in enumerate(parts):
        if np.asarray(Xj).shape[0] == 0:
            decoded.append(jnp.asarray(Xj, jnp.float32))
            continue
        sch = PerSymbolScheme(cfg.bits_per_sample, cfg.max_bits).fit(
            np.asarray(S[j]), np.asarray(S_tot - S[j])
        )
        decoded.append(sch.decode(sch.encode(Xj)))
        wire += sch.wire_bits(Xj.shape[0]) + sch.side_info_bits(Xj.shape[1])

    # train shared hypers at machine 0 on its own completed gram
    Xc = jnp.asarray(parts[0][0], jnp.float32)
    X0 = jnp.concatenate([Xc] + [decoded[j] for j in range(1, m)], axis=0)
    y0 = jnp.concatenate([yj for _, yj in parts], axis=0)
    sq_N = jnp.sum(X0**2, -1)
    operands = {"ip_KK": Xc @ Xc.T, "ip_KN": Xc @ X0.T,
                "sq_K": sq_N[: Xc.shape[0]], "sq_N": sq_N}
    trained = train_gp(
        None, y0, kernel=cfg.kernel, params=params, steps=cfg.steps, lr=cfg.lr,
        gram=nystrom_from_inner, operands=operands, impl=cfg.train_impl,
    )
    from ...comm.accounting import integrity_bits_formula, payload_bits_formula

    payload = payload_bits_formula(
        [p[0].shape[0] for p in parts], parts[0][0].shape[1],
        cfg.bits_per_sample, cfg.max_bits,
    )
    integrity = integrity_bits_formula([p[0].shape[0] for p in parts])
    return HostBroadcastGP(
        kernel=cfg.kernel, params=trained.params, parts=list(parts),
        decoded=decoded, wire_bits=wire, gram_mode=cfg.gram_mode,
        fuse=cfg.fusion, payload_bits=payload, integrity_bits=integrity,
    )


# --------------------------------------------------------------------------
# fit-time inner-product tensors (batched impl)
# --------------------------------------------------------------------------


def _receiver_products(X_recv, mask, wire: WireState, backend: str,
                       pack_bits: int = 0):
    """(m, k, n_pad, n_pad): out[j, r] = X̂_j Xs_r^T, every sender j's
    reconstruction against the exact points of each of the k receivers
    ``X_recv`` (k, n_pad, d) — the receivers' columns of the wire's inner
    products.  backend="pallas" computes them straight from the PACKED wire
    words with the fused unpack+dequantize+gram kernel (``pack_bits``: the
    static row bit budget of the packed plane; ``mask`` (m, n_pad) zeroes the
    senders' padded rows)."""
    if backend == "pallas":
        from ...kernels.qgram.ops import qgram_packed

        proj = jnp.einsum("ind,jde->jine", X_recv, wire.T_inv)  # (m_j, k, n, d)
        return jax.vmap(
            lambda w, r, t, mk, ys: jax.vmap(
                lambda yy: qgram_packed(
                    w, r, t, yy, total_bits=pack_bits, mask=mk
                )
            )(ys)
        )(wire.codes, wire.rates, wire.scaled_cents, mask, proj)
    return jnp.einsum("jnd,imd->jinm", wire.decoded, X_recv)


def _receiver_decodes(wire: WireState, receivers: int, backend: str) -> int:
    """Row-tile decodes of the ``qgram_packed`` kernel in one
    :func:`_receiver_products` call for ``receivers`` receivers: one kernel
    call per sender and receiver, 0 where no kernel runs."""
    if backend != "pallas":
        return 0
    from ...kernels.qgram.ops import qgram_packed_decodes

    m = wire.codes.shape[0]
    return m * receivers * qgram_packed_decodes(wire.codes.shape[1:])


@partial(jax.jit, static_argnames=("backend", "pack_bits"))
def _train_inner_products(X, mask, wire: WireState, backend: str,
                          pack_bits: int = 0):
    """The inner products a fit needs before its factor build, as one
    program per shard layout:

    A (m, n_pad, n_pad): exact own-block products Xs_i Xs_i^T
    B0 (m, n_pad, n_pad): B0[j] = X̂_j Xs_0^T, machine 0's column of the
      wire's inner products (:func:`_receiver_products`), all its training
      needs

    backend="pallas" computes A with the tiled gram kernel.  The other
    receivers' columns are computed group by group inside the factor build
    (:func:`broadcast_factor_group`), so no (m, m, n_pad, n_pad) tensor is
    ever held."""
    if backend == "pallas":
        from ...kernels.gram.ops import gram as gram_kernel

        A = jax.vmap(lambda a: gram_kernel(a, a))(X)
    else:
        A = jnp.einsum("ind,imd->inm", X, X)
    return A, _receiver_products(X[:1], mask, wire, backend, pack_bits)[:, 0]


def _operands0(ip_own, ip_peers, sq_own, sq_dec, y, lengths):
    """Machine 0's training operands, unpadded: its exact block as the
    Nyström centers (``ip_KK``, ``sq_K``), its columns every machine's block
    in machine order, its own exact and the others' reconstructions
    (``ip_KN``, ``sq_N``), and every machine's targets (``y``).  ``ip_own``
    (n_pad, n_pad) holds machine 0's products with itself, ``ip_peers``
    (m, n_pad, n_pad) each machine's reconstruction against machine 0's
    exact points, ``lengths`` the machines' row counts."""
    n0, m = lengths[0], len(lengths)
    ip_KK = ip_own[:n0, :n0]
    ip_KN = jnp.concatenate(
        [ip_KK] + [ip_peers[j, : lengths[j], :n0].T for j in range(1, m)], axis=1
    )
    sq_K = sq_own[:n0]
    sq_N = jnp.concatenate([sq_K] + [sq_dec[j, : lengths[j]] for j in range(1, m)])
    y0 = jnp.concatenate([y[j, : lengths[j]] for j in range(m)])
    return {"ip_KK": ip_KK, "ip_KN": ip_KN, "sq_K": sq_K, "sq_N": sq_N, "y": y0}


@partial(jax.jit, static_argnames="lengths")
def _train_operands0(A, B0, sq_exact, sq_dec, y, lengths):
    """:func:`_operands0` from the products of :func:`_train_inner_products`
    (the own-block products A (m, n_pad, n_pad), of which it reads machine
    0's, and machine 0's column B0), as one program per shard layout."""
    return _operands0(A[0], B0, sq_exact[0], sq_dec, y, lengths)


@partial(jax.jit, static_argnames="lengths")
def _mesh_train_operands0(X, decoded, sq_exact, sq_dec, y, lengths):
    """:func:`_operands0` from the points: machine 0's exact block against
    itself and every machine's reconstruction."""
    X0 = X[0]
    return _operands0(X0 @ X0.T, jnp.einsum("jnd,md->jnm", decoded, X0),
                      sq_exact[0], sq_dec, y, lengths)


# --------------------------------------------------------------------------
# the factor build (batched impl): receivers in groups sized to the device
# --------------------------------------------------------------------------

# float32 copies of one receiver's (n_pad, m*n_pad) view that the build
# holds at once: its column of wire products (padded to the kernel's blocks,
# then relaid out), its kernel columns G_KN, the blocked triangular solve's
# W = L_KK^{-1} G_KN and W relaid out for the artifact.  The TPU v5e
# compiler's own count at m=40, n_pad=1,113: 1.01-1.15 GB of temporaries per
# receiver for groups of 1 to 5, 5.1-5.8 views.
_VIEW_COPIES = 6
# share of the device's memory left to what the process holds besides the
# artifact and the build (the dataset, the wire state, the allocator's slack:
# a program's temporaries are reserved in one piece)
_HEADROOM = 0.15


def _device_memory_limit():
    """Bytes the default device may allocate, None where the backend does
    not say (the CPU)."""
    stats = jax.local_devices()[0].memory_stats()
    return None if not stats else stats.get("bytes_limit")


def factor_group_size(m: int, n_pad: int, bytes_limit=None) -> int:
    """Receivers per group of the factor build: as many as fit in
    ``bytes_limit`` beside the artifact, balanced so that the groups are as
    even as the count allows (the last one may overlap the one before it).

    The artifact holds, per receiver, ``W`` (n_pad, N = m*n_pad), three
    (n_pad, n_pad) factors (``L_KK``, ``L_M``, ``Ainv``), ``alpha`` (N,) and
    ``walpha`` (n_pad,); the own-block products A stay alive through the
    build.  Each
    receiver built at once adds ``_VIEW_COPIES`` float32 views of (n_pad, N).
    No limit (None) gives one group of all m receivers."""
    if bytes_limit is None:
        return m
    N = m * n_pad
    resident = 4 * m * (n_pad * N + 4 * n_pad * n_pad + N + n_pad)
    per_receiver = 4 * _VIEW_COPIES * n_pad * N
    free = int(bytes_limit * (1.0 - _HEADROOM)) - resident
    k = max(1, min(m, free // per_receiver))
    groups = -(-m // k)
    return -(-m // groups)


def _build_group(start, p, A, X, mask, wire: WireState, sq_exact, sq_dec,
                 y_flat, *, kernel: str, group: int, backend: str,
                 pack_bits: int, serve_cache: bool):
    """The Nyström factor sets of receivers ``start`` .. ``start+group-1``,
    stacked: ``L_KK``, ``W``, ``L_M``, ``alpha`` and, with ``serve_cache``,
    the fused serve operands.

    Receiver i's view: its exact block as the Nyström centers, its columns
    every machine's block in machine order, its own exact and the others'
    reconstructions.  The group's columns of the wire's inner products are
    computed here from the wire state (:func:`_receiver_products`)."""
    m, n_pad, _ = X.shape
    noise = jnp.exp(p.log_noise)
    mask_flat = mask.reshape(-1)  # column layout is block j at slot j

    def build(i, cols, ip_KK):
        # cols (m, n_pad, n_pad): block j is X̂_j Xs_i^T; own block exact
        mask_i = mask[i]
        blocks = cols.transpose(0, 2, 1).at[i].set(ip_KK)
        ip_KN = jnp.moveaxis(blocks, 0, 1).reshape(n_pad, m * n_pad)
        sq_cols = sq_dec.at[i].set(sq_exact[i]).reshape(-1)
        G_KK = _mask_gram(
            kernel_from_inner(kernel, p, ip_KK, sq_exact[i], sq_exact[i]), mask_i
        )
        G_KN = kernel_from_inner(kernel, p, ip_KN, sq_exact[i], sq_cols) * (
            mask_i[:, None] * mask_flat[None, :]
        )
        fac = nystrom_factors(G_KK, G_KN, y_flat, noise)
        if serve_cache:
            fac.update(nystrom_serve_cache(fac))
        return fac

    X_g = jax.lax.dynamic_slice_in_dim(X, start, group)
    cols = _receiver_products(X_g, mask, wire, backend, pack_bits)
    return jax.vmap(build, in_axes=(0, 1, 0))(
        start + jnp.arange(group), cols,
        jax.lax.dynamic_slice_in_dim(A, start, group),
    )


_BUILD_STATIC = ("kernel", "group", "backend", "pack_bits", "serve_cache")


@partial(jax.jit, static_argnames=_BUILD_STATIC)
def broadcast_factor_buffers(p, A, X, mask, wire: WireState, sq_exact, sq_dec,
                             y_flat, *, kernel: str, group: int, backend: str,
                             pack_bits: int, serve_cache: bool):
    """The artifact's factor buffers for :func:`broadcast_factor_group` to
    fill: zeros shaped as every receiver's factor set, stacked over the m
    receivers."""
    build = partial(_build_group, kernel=kernel, group=group, backend=backend,
                    pack_bits=pack_bits, serve_cache=serve_cache)
    shapes = jax.eval_shape(build, 0, p, A, X, mask, wire, sq_exact, sq_dec,
                            y_flat)
    return jax.tree_util.tree_map(
        lambda s: jnp.zeros((X.shape[0],) + s.shape[1:], s.dtype), shapes
    )


@partial(jax.jit, static_argnames=_BUILD_STATIC, donate_argnums=0)
def broadcast_factor_group(factors, start, p, A, X, mask, wire: WireState,
                           sq_exact, sq_dec, y_flat, *, kernel: str,
                           group: int, backend: str, pack_bits: int,
                           serve_cache: bool):
    """One group's factor sets (:func:`_build_group`), written into the
    donated artifact ``factors`` at receiver ``start``, in place.  One
    program per shard layout and group size: ``start`` is an argument."""
    fresh = _build_group(start, p, A, X, mask, wire, sq_exact, sq_dec, y_flat,
                         kernel=kernel, group=group, backend=backend,
                         pack_bits=pack_bits, serve_cache=serve_cache)
    return jax.tree_util.tree_map(
        lambda F, f: jax.lax.dynamic_update_slice_in_dim(F, f, start, 0),
        factors, fresh,
    )


def _build_factors(p, A, X, mask, wire: WireState, sq_exact, sq_dec, y_flat,
                   *, group: int, **static):
    """Every receiver's factor set, ``group`` receivers a call: the groups
    start at 0, group, 2*group, ..., the last one shifted back to end at
    receiver m-1 (it recomputes rows of the one before, identically).  So the
    device holds the artifact and one group's views, never m² blocks or m
    views at once."""
    m = X.shape[0]
    args = (p, A, X, mask, wire, sq_exact, sq_dec, y_flat)
    factors = broadcast_factor_buffers(*args, group=group, **static)
    decodes = _receiver_decodes(wire, group, static["backend"])
    stats = {"qgram_decodes": decodes} if decodes else {}
    for g in range(-(-m // group)):
        with span("fit.factors.group", **stats):
            factors = broadcast_factor_group(
                factors, min(g * group, m - group), *args, group=group, **static
            )
    return factors


def _star_exact_products(Xs, X_star, backend: str):
    """C (m, t, n): X_star Xs_i^T — the query-time products against every
    machine's EXACT shard (the Nyström bases)."""
    if backend == "pallas":
        from ...kernels.gram.ops import gram as gram_kernel

        return jax.vmap(lambda a: gram_kernel(X_star, a))(Xs)
    return jnp.einsum("td,ind->itn", X_star, Xs)


def _decoded_inner_products(
    shards: PaddedShards, wire: WireState, backend: str, pack_bits: int = 0
):
    """D (m, n_pad, m*n_pad): D[j] = X̂_j [X̂_0..X̂_m]^T (decoded-vs-decoded) —
    only the gram_mode="direct" views consume this, so it is computed only for
    them (fit time)."""
    m, n_pad, d = shards.X.shape
    dec_flat = wire.decoded.reshape(m * n_pad, d)
    if backend == "pallas":
        from ...kernels.qgram.ops import qgram_packed_batched

        proj = jnp.einsum("nd,jde->jne", dec_flat, wire.T_inv)
        return qgram_packed_batched(
            wire.codes, wire.rates, wire.scaled_cents, proj,
            total_bits=pack_bits, mask=shards.mask,
        )
    return jnp.einsum("jnd,Nd->jnN", wire.decoded, dec_flat)


def _star_decoded_products(wire: WireState, X_star, backend: str,
                           pack_bits: int = 0, mask=None):
    """E (m, t, n_pad): E[j] = X_star X̂_j^T — query-time products against the
    reconstructions (gram_mode="direct" views only); straight from the packed
    wire words under the pallas backend."""
    if backend == "pallas":
        from ...kernels.qgram.ops import qgram_packed_batched

        proj_star = jnp.einsum("td,jde->jte", X_star, wire.T_inv)
        return qgram_packed_batched(
            wire.codes, wire.rates, wire.scaled_cents, proj_star,
            total_bits=pack_bits, mask=mask,
        ).transpose(0, 2, 1)
    return jnp.einsum("td,jnd->jtn", X_star, wire.decoded)


def broadcast_gp(
    parts,
    bits_per_sample: int,
    X_star,
    kernel: str = "se",
    steps: int = 150,
    lr: float = 0.05,
    fuse: str = "kl",
    gram_mode: str = "nystrom",
    impl: str = "batched",
    gram_backend: str = "xla",
    max_bits: int = Q.DEFAULT_MAX_BITS,
    train_impl: str = "scan",
):
    """Full §5.2 protocol.  Hyperparameters are trained once (at machine 0, on
    its Nyström view) and shared — a cheap O(#hypers) extra broadcast; the
    paper trains per-machine, which is embarrassingly parallel on a real
    cluster but m-times serial here.  Returns fused (mean, var) at X_star plus
    total wire bits.

    The default ``impl="batched"`` is a thin serving composition:
    ``fit(parts, R, protocol="broadcast", ...)`` builds the
    :class:`~.base.FittedProtocol` artifact (every machine's scheme fit and
    decode under jax.vmap on padded shards, and its Nyström factorization
    in groups of receivers under one vmap each — batched Choleskys instead
    of m serial ones),
    and :func:`~.base.predict` serves X_star from the cached factors.  Call
    ``fit`` directly (or the ``DistributedGP`` facade) to keep the artifact
    and amortize the protocol over many query batches."""
    if impl == "host":
        if gram_backend == "pallas":
            raise ValueError('gram_backend="pallas" requires impl="batched"')
        from ..config import DGPConfig

        cfg = DGPConfig(
            protocol="broadcast", kernel=kernel, fusion=fuse, impl="host",
            gram_mode=gram_mode, bits_per_sample=int(bits_per_sample),
            max_bits=int(max_bits), steps=int(steps), lr=float(lr),
            train_impl=train_impl,
        )
        model = fit_broadcast_host(parts, cfg)
        mu, s2 = model.predict(X_star)
        return mu, s2, model.wire_bits, model.params
    art = base.fit(
        parts, bits_per_sample, protocol="broadcast", kernel=kernel, steps=steps,
        lr=lr, gram_mode=gram_mode, fuse=fuse, gram_backend=gram_backend,
        max_bits=max_bits, train_impl=train_impl, impl=impl,
    )
    mu, s2 = base.predict(art, X_star)
    return mu, s2, art.wire_bits, art.params


# --------------------------------------------------------------------------
# fit / predict / update (the registered protocol triple)
# --------------------------------------------------------------------------


def _fit_broadcast(parts, cfg, params=None) -> FittedProtocol:
    from ...comm.accounting import row_bits

    with span("fit.wire") as wire_span:
        parts, _ = base._apply_fit_faults(parts, cfg)
        m = len(parts)
        shards = pad_parts(parts)
        _, n_pad, d = shards.X.shape
        bits, kernel, gram_mode = cfg.bits_per_sample, cfg.kernel, cfg.gram_mode
        gram_backend, fuse = cfg.gram_backend, cfg.fusion
        pack_bits = row_bits(bits, d, cfg.max_bits)
        if cfg.impl == "mesh":
            if gram_mode != "nystrom":
                raise NotImplementedError(
                    'impl="mesh" broadcast supports gram_mode="nystrom" only'
                )
            if gram_backend != "xla":
                raise NotImplementedError(
                    'impl="mesh" assembles grams device-local (gram_backend="xla")'
                )
        run = SCHEMES.get(cfg.scheme).run(
            shards, bits, cfg.max_bits, "broadcast", 0, cfg.impl,
            getattr(cfg, "faults", None),
        )
        # CRC demotion may have compacted rows out of the shard table: every
        # assembly below reads the (possibly shrunk) post-wire shards
        wire_state, shards = run.state, run.shards
        wire, payload = run.wire_bits, run.payload_bits
        extras = run.extras

        sq_exact = jnp.sum(shards.X**2, -1)  # (m, n)
        sq_dec = jnp.sum(wire_state.decoded**2, -1)
        if cfg.impl != "mesh":  # own blocks and machine 0's wire column
            A, B0 = _train_inner_products(
                shards.X, shards.mask, wire_state, gram_backend, pack_bits
            )
            decodes = _receiver_decodes(wire_state, 1, gram_backend)
            if decodes:
                wire_span.set_metadata(qgram_decodes=decodes)

    with span("fit.train"):
        # ---- train shared hypers at machine 0 on its completed Nyström gram ----
        # (the inner products are param-independent arguments of the training
        # program, so its steps only re-do the cheap kernel map + Cholesky)
        if cfg.impl == "mesh":
            # machine-0-local products, straight from the wire output (the
            # batched A/B tensors exist only to vmap the m simulated views)
            operands = _mesh_train_operands0(
                shards.X, wire_state.decoded, sq_exact, sq_dec, shards.y,
                lengths=shards.lengths,
            )
        else:
            operands = _train_operands0(
                A, B0, sq_exact, sq_dec, shards.y, lengths=shards.lengths
            )
            del B0
        trained = train_gp(
            None, operands["y"], kernel=kernel, params=params, steps=cfg.steps, lr=cfg.lr,
            gram=nystrom_from_inner, operands=operands, impl=cfg.train_impl,
        )
        p = trained.params
        noise = jnp.exp(p.log_noise)
        del operands

    stats = {}
    if cfg.impl != "mesh" and gram_mode == "nystrom":
        group = factor_group_size(m, shards.X.shape[1], _device_memory_limit())
        stats = {"groups": -(-m // group), "receivers": group}
    with span("fit.factors", **stats):
        # ---- factorize every machine's local predictive ----
        mask_flat = shards.mask.reshape(-1)  # column layout is block j at slot j
        y_flat = (shards.y * shards.mask).reshape(-1)

        fused_serve = getattr(cfg, "serve_epilogue", "fused") == "fused"
        if cfg.impl == "mesh":
            # one shard_map program: device i assembles & factorizes ITS view;
            # the factor set lives sharded along the mesh axis
            msh = mesh.machine_mesh(m)
            factors = mesh._mesh_broadcast_factor_fn(m, kernel, fused_serve)(
                shards.X, shards.mask, wire_state.decoded, sq_dec, mask_flat,
                y_flat, p,
            )
            data = mesh._shard_machine_axis(
                {"Xs": shards.X, "mask": shards.mask,
                 "sq_exact": sq_exact, "sq_dec": sq_dec},
                msh,
            )
            return FittedProtocol(
                params=p, y=y_flat, factors=factors, data=data, wire=wire_state,
                stream=StreamState.make(
                    shards.lengths, y_flat.shape[0], int(wire), int(payload),
                    int(run.integrity_bits), int(run.rows_demoted),
                ),
                protocol="broadcast", kernel=kernel, gram_mode=gram_mode,
                fuse=fuse, gram_backend=gram_backend, n_center=0,
                fit_lengths=shards.lengths, block_order=None,
                bits_per_sample=bits, max_bits=cfg.max_bits, impl="mesh",
                scheme=cfg.scheme, config=cfg,
            )

        if gram_mode == "nystrom":
            factors = _build_factors(
                p, A, shards.X, shards.mask, wire_state, sq_exact, sq_dec,
                y_flat, kernel=kernel, group=group, backend=gram_backend,
                pack_bits=pack_bits, serve_cache=fused_serve,
            )
        elif gram_mode == "direct":
            D = _decoded_inner_products(shards, wire_state, gram_backend, pack_bits)
            B = _receiver_products(
                shards.X, shards.mask, wire_state, gram_backend, pack_bits
            )

            def build(i):
                mask_i = shards.mask[i]
                own_cols = B[:, i].transpose(0, 2, 1)  # block j: Xs_i X̂_j^T
                own_cols = own_cols.at[i].set(A[i])
                row_i = jnp.moveaxis(own_cols, 0, 1).reshape(n_pad, m * n_pad)
                # non-own rows: decoded-vs-decoded, with column block i swapped to
                # decoded-vs-exact (B[r, i])
                rows = D.reshape(m, n_pad, m, n_pad).at[:, :, i, :].set(B[:, i])
                rows = rows.reshape(m, n_pad, m * n_pad).at[i].set(row_i)
                ip_NN = rows.reshape(m * n_pad, m * n_pad)
                sq_cols = sq_dec.at[i].set(sq_exact[i]).reshape(-1)
                G = _mask_gram(
                    kernel_from_inner(kernel, p, ip_NN, sq_cols, sq_cols), mask_flat
                )
                return posterior_factors(G, y_flat, noise)

            factors = jax.vmap(build)(jnp.arange(m))
        else:
            raise ValueError(f"unknown broadcast gram mode {gram_mode!r}")

        data = {
            "Xs": shards.X, "mask": shards.mask,
            "sq_exact": sq_exact, "sq_dec": sq_dec,
        }
        data.update(extras)
        return FittedProtocol(
            params=p,
            y=y_flat,
            factors=factors,
            data=data,
            wire=wire_state,
            stream=StreamState.make(
                shards.lengths, y_flat.shape[0], int(wire), int(payload),
                int(run.integrity_bits), int(run.rows_demoted),
            ),
            protocol="broadcast",
            kernel=kernel,
            gram_mode=gram_mode,
            fuse=fuse,
            gram_backend=gram_backend,
            n_center=0,
            fit_lengths=shards.lengths,
            block_order=None,
            bits_per_sample=bits,
            max_bits=cfg.max_bits,
            impl=cfg.impl,
            scheme=cfg.scheme,
            config=cfg,
        )


def _predict_broadcast_experts(art, X_star, sq_star, g_ss, noise):
    p = art.params
    Xs, mask = art.data["Xs"], art.data["mask"]
    sq_exact = art.data["sq_exact"]
    m, n_pad, _ = Xs.shape
    C = _star_exact_products(Xs, X_star, art.gram_backend)
    if art.gram_mode == "nystrom":

        cached = "Ainv" in art.factors  # static: key presence decides the path

        def apply_i(fac, Ci, sqi, mi):
            G_sK = kernel_from_inner(art.kernel, p, Ci, sq_star, sqi) * mi[None, :]
            if cached:
                return nystrom_apply_cached(fac, G_sK, g_ss, noise)
            return nystrom_apply(fac, G_sK, g_ss, noise)

        return jax.vmap(apply_i)(art.factors, C, sq_exact, mask)
    # direct views
    from ...comm.accounting import row_bits

    sq_dec = art.data["sq_dec"]
    mask_flat = mask.reshape(-1)
    E = _star_decoded_products(
        art.wire, X_star, art.gram_backend,
        row_bits(art.bits_per_sample, Xs.shape[-1], art.max_bits), mask,
    )

    def apply_i(i, fac):
        star_cols = E.at[i].set(C[i])  # (m, t, n_pad); block i exact
        ip_sN = jnp.moveaxis(star_cols, 0, 1).reshape(-1, m * n_pad)
        sq_cols = sq_dec.at[i].set(sq_exact[i]).reshape(-1)
        G_sn = kernel_from_inner(art.kernel, p, ip_sN, sq_star, sq_cols) * (
            mask_flat[None, :]
        )
        return posterior_apply(fac, G_sn, g_ss)

    return jax.vmap(apply_i)(jnp.arange(m), art.factors)


def _uses_fused_epilogue(art, spec) -> bool:
    """Static predicate: this artifact serves through the one-launch fused
    epilogue (pallas backend, cached Nyström serve operands, a fusion that
    exposes moment rows).  Shared with :mod:`repro.core.fleet`, which batches
    the same path over a leading tenant axis."""
    return (
        art.gram_backend == "pallas"
        and art.gram_mode == "nystrom"
        and "Ainv" in art.factors
        and spec.moments is not None
        and spec.finalize is not None
    )


def _epilogue_projector(art, noise=None):
    """The woodbury quad-form projector ``P = (U - U M^{-1} U)/s2`` per
    expert (:func:`~repro.core.nystrom.nystrom_projector`) — the
    QUERY-INDEPENDENT half of the fused serve epilogue's
    operand set (it depends only on the artifact's cached factors and
    noise).  The single-tenant serve path rebuilds it inside each predict;
    the fleet stack (:mod:`repro.core.fleet`) precomputes it ONCE per
    admitted tenant and keeps it device-resident, amortizing the per-expert
    ``cho_solve`` chain across every query the tenant serves."""
    if noise is None:
        noise = jnp.exp(art.params.log_noise)
    f = art.factors
    s2 = noise + DEFAULT_JITTER
    return jax.vmap(lambda Lm: nystrom_projector(Lm, s2))(f["L_M"])


def _fused_epilogue_operands(art, X_star, sq_star, g_ss, noise, avail,
                             P=None):
    """Build the ``kernels.epilogue`` operand set ``(G, Ainv, P, walpha,
    prior, w)`` for one artifact's fused serve: the masked cross-gram tiles,
    the cached inverse, the woodbury quad-form projector, and the
    availability weights.  Split out of :func:`_predict_broadcast_fused` so
    the fleet path (:mod:`repro.core.fleet`) can vmap THIS over a stacked
    tenant axis and hand the batch to the tenant-batched epilogue kernel;
    ``P`` accepts that path's precomputed :func:`_epilogue_projector` (None
    = build it here, as the single-tenant serve does)."""
    p = art.params
    f = art.factors
    Xs, mask = art.data["Xs"], art.data["mask"]
    sq_exact = art.data["sq_exact"]
    m = Xs.shape[0]
    C = _star_exact_products(Xs, X_star, art.gram_backend)
    G = jax.vmap(
        lambda Ci, sqi, mi: kernel_from_inner(art.kernel, p, Ci, sq_star, sqi)
        * mi[None, :]
    )(C, sq_exact, mask)
    if P is None:
        P = _epilogue_projector(art, noise)
    w = jnp.ones((m,), jnp.float32) if avail is None else jnp.asarray(
        avail, jnp.float32
    )
    prior = g_ss + noise
    return G, f["Ainv"], P, f["walpha"], prior, w


def _predict_broadcast_fused(art, spec, X_star, sq_star, g_ss, noise, avail):
    """One-launch serve epilogue (pallas backend + cached Nyström factors):
    the per-expert cached apply AND the fusion moment rows run as a single
    ``kernels.epilogue`` call; only the method's cheap ``finalize`` remains
    outside.  Algebraically equal to experts + ``spec.fuse`` (asserted by
    tests/test_kernel_runtime.py for every fusion method)."""
    from ...kernels.epilogue.ops import epilogue_moments

    m = art.data["Xs"].shape[0]
    G, Ainv, P, walpha, prior, w = _fused_epilogue_operands(
        art, X_star, sq_star, g_ss, noise, avail
    )
    S = epilogue_moments(G, Ainv, P, walpha, g_ss, prior, w, fuse=art.fuse)
    return spec.finalize(S, m, prior)


def _predict_broadcast(art: FittedProtocol, X_star, sq_star, g_ss, noise,
                       avail=None):
    spec = FUSIONS.get(art.fuse)
    if _uses_fused_epilogue(art, spec):
        return _predict_broadcast_fused(art, spec, X_star, sq_star, g_ss,
                                        noise, avail)
    mus, s2s = _predict_broadcast_experts(art, X_star, sq_star, g_ss, noise)
    if avail is None:  # healthy fast path; legacy 3-arg fusions still plug in
        return spec.fuse(mus, s2s, g_ss + noise)
    # degraded serving: the fusion renormalizes over surviving machines
    return spec.fuse(mus, s2s, g_ss + noise, avail)


@jax.jit
def _update_broadcast_jit(art, X_new, y_new, j, pre):
    """Device-resident §5.2 streaming append (batched impl): machine ``j``
    broadcast its codes once — every peer i sees X̂_new, machine j itself
    keeps the exact points — and the new points extend every view's COLUMNS
    in place at the occupied-column cursor (the rank-n_pad Nyström bases
    stay fixed).  ``j`` is traced: one cache entry serves every machine."""
    _UPDATE_TRACES["broadcast"] += 1  # runs at trace time only
    p = art.params
    noise = jnp.exp(p.log_noise)
    m = len(art.fit_lengths)
    n_new = X_new.shape[0]
    if pre is None:
        decoded, w_add, p_add, i_add = SCHEMES.get(art.scheme).reencode_traced(
            art, j, X_new
        )
        d_add = jnp.int32(0)
    else:  # host-precomputed batch (vq channel or faulted transmission)
        decoded, w_add, p_add, i_add, d_add = pre
    reps = jnp.broadcast_to(decoded, (m, n_new, decoded.shape[1]))
    own = jnp.arange(m)[:, None, None] == j  # traced j: where, not .at[j]
    reps = jnp.where(own, X_new[None], reps)
    sq_new = jnp.sum(reps**2, -1)  # (m, n_new)
    ip_new = jnp.einsum("ind,ied->ine", art.data["Xs"], reps)  # (m, n_pad, n_new)
    pos = art.stream.cols
    y2 = jax.lax.dynamic_update_slice(art.y, y_new, (pos,))
    s2 = noise + DEFAULT_JITTER

    def upd(fac, ipn, sqi, sqn, mi):
        G_KN_new = kernel_from_inner(art.kernel, p, ipn, sqi, sqn) * mi[:, None]
        W_new = jax.scipy.linalg.solve_triangular(fac["L_KK"], G_KN_new, lower=True)
        W2 = jax.lax.dynamic_update_slice(fac["W"], W_new, (0, pos))
        L_M2 = chol_update_rank(fac["L_M"], W_new)
        out = {
            "L_KK": fac["L_KK"], "W": W2, "L_M": L_M2,
            "alpha": nystrom_kinv(W2, L_M2, s2, y2),
        }
        if "Ainv" in fac:  # fused-serve cache rides along: walpha
            # re-contracts against the updated alpha, Ainv never changes
            out["Ainv"] = fac["Ainv"]
            out["walpha"] = W2 @ out["alpha"]
        return out

    factors = jax.vmap(upd)(
        art.factors, ip_new, art.data["sq_exact"], sq_new, art.data["mask"]
    )
    s = art.stream
    stream = StreamState(
        counts=s.counts.at[j].add(n_new), cols=s.cols + n_new,
        wire_bits=s.wire_bits + w_add, payload_bits=s.payload_bits + p_add,
        integrity_bits=s.integrity_bits + i_add,
        rows_demoted=s.rows_demoted + d_add,
    )
    return dataclasses.replace(art, y=y2, factors=factors, stream=stream)


def _update_broadcast(art: FittedProtocol, X_new, y_new, j, pre=None):
    if art.gram_mode != "nystrom":
        raise NotImplementedError(
            'streaming update of broadcast artifacts supports gram_mode='
            '"nystrom" only'
        )
    if art.impl == "mesh":
        # the sharded factors grow IN PLACE on their devices: re-encode and
        # rank-k growth run as one shard_map program, no host pull
        return mesh._update_mesh_jit(art, X_new, y_new, base._machine_index(j), pre)
    return _update_broadcast_jit(art, X_new, y_new, base._machine_index(j), pre)


register_protocol(ProtocolSpec(
    name="broadcast",
    fit=_fit_broadcast,
    predict=_predict_broadcast,
    update=_update_broadcast,
    fit_host=fit_broadcast_host,
))


# --------------------------------------------------------------------------
# the program contract (repro.analysis.check_contracts enforces it); the
# impl="mesh" substrate registers its own override in mesh.py
# --------------------------------------------------------------------------
from ...analysis.contracts import (
    CollectiveBudget,
    Contract,
    LedgerAccounting,
    NoHostCallbacks,
    NoShardingLeak,
    forbid_primitives,
    register_contract,
)

# §5.2 batched serving: m machines are a vmap axis inside one program —
# nothing may factorize, synchronize, or stay sharded.
register_contract("broadcast", "predict", Contract(
    name="broadcast-serve",
    rules=(
        forbid_primitives(),
        NoHostCallbacks(),
        CollectiveBudget(max_count=0),
        NoShardingLeak(max_devices=1),
        LedgerAccounting(),
    ),
))
register_contract("broadcast", "update", Contract(
    name="broadcast-update",
    rules=(NoShardingLeak(max_devices=1), LedgerAccounting()),
))
