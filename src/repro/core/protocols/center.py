"""§5.1 single-center protocol.

Machine ``center`` is the center: it ships its local second-moment S_c to
every machine; machine j fits the wire scheme to (Qx=S_j, Qy=S_c) and
transmits; the center decodes X̂_j, forms the first-block rows of the gram
matrix (its own block exact), Nyström-completes (eq. 61), trains
hyperparameters on the completed gram, and serves predictions from one
cached factor set.
"""
from __future__ import annotations

import dataclasses

import numpy as np
import jax
import jax.numpy as jnp

from .. import quantizers as Q
from ..distortion import second_moment
from ..schemes import PerSymbolScheme
from ..gp import (
    GPParams,
    init_params,
    gram_fn,
    kernel_from_inner,
    nystrom_from_inner,
    prior_diag,
    posterior_factors,
    posterior_apply,
    posterior_from_gram,
    train_gp,
)
from ..nystrom import (
    nystrom_complete,
    nystrom_cross,
    nystrom_posterior,
    nystrom_factors,
    nystrom_apply,
    nystrom_serve_cache,
    nystrom_apply_cached,
    nystrom_kinv,
    chol_update_rank,
    chol_append_at,
)
from ..linalg_safe import DEFAULT_JITTER, chol_jittered
from ..registry import SCHEMES, ProtocolSpec, register_protocol
from ...spans import span
from . import base
from .base import (
    FittedProtocol,
    StreamState,
    WireState,
    pad_parts,
    _UPDATE_TRACES,
)

__all__ = ["quantize_to_center", "CenterGP", "single_center_gp"]


def _quantize_to_center_host(
    parts, bits_per_sample: int, center: int = 0, max_bits: int = Q.DEFAULT_MAX_BITS
):
    """Serial reference protocol: host-side scipy PerSymbolScheme per machine."""
    S_c = second_moment(parts[center][0])
    Xs, ys, sqs, wire = [], [], [], 0
    for j, (Xj, yj) in enumerate(parts):
        if j == center or np.asarray(Xj).shape[0] == 0:
            Xs.append(Xj)  # empty (dropped) machines transmit nothing
        else:
            S_j = second_moment(Xj)
            sch = PerSymbolScheme(bits_per_sample, max_bits).fit(
                np.asarray(S_j), np.asarray(S_c)
            )
            Xs.append(sch.decode(sch.encode(Xj)))
            wire += sch.wire_bits(Xj.shape[0]) + sch.side_info_bits(Xj.shape[1])
            # (the optional FITC diagonal costs an extra 32 bits/point of
            #  exact |x|^2 — accounted by the caller when gram_mode uses it)
        ys.append(yj)
        sqs.append(jnp.sum(jnp.asarray(Xj) ** 2, axis=-1))
    order = [center] + [j for j in range(len(parts)) if j != center]
    X_recon = jnp.concatenate([Xs[j] for j in order], axis=0)
    y_all = jnp.concatenate([ys[j] for j in order], axis=0)
    sq_norms = jnp.concatenate([sqs[j] for j in order], axis=0)
    n_center = parts[center][0].shape[0]
    return X_recon, y_all, wire, n_center, sq_norms


def _quantize_to_center_batched(
    parts, bits_per_sample: int, center: int, max_bits: int,
    impl: str = "batched", scheme: str = "per_symbol", faults=None,
):
    """Batched §5.1 wire: run the registered wire scheme for every machine at
    once, then assemble the center's gram-row layout (exact center block
    first).  ``impl="mesh"`` runs the per-symbol wire as one shard_map
    program on a machines-as-devices mesh (comm.q_all_gather is the channel,
    moving the packed code plane; payload measured from the buffer).

    Assembly reads the scheme run's RETURNED shards (not ``parts``): under a
    ``faults`` plan with wire corruption the run demotes CRC-flagged rows and
    compacts the survivors, so the shards are the receiver's honest view —
    for a clean run they are bitwise what ``pad_parts(parts)`` produced."""
    shards = pad_parts(parts)
    m, _, d = shards.X.shape
    run = SCHEMES.get(scheme).run(
        shards, bits_per_sample, max_bits, "center", center, impl, faults
    )
    wire_state, shards = run.state, run.shards
    order = [center] + [j for j in range(m) if j != center]
    blocks = [shards.X[center, : shards.lengths[center]]] + [
        wire_state.decoded[j, : shards.lengths[j]] for j in order[1:]
    ]
    X_recon = jnp.concatenate(blocks, axis=0)
    y_all = jnp.concatenate(
        [shards.y[j, : shards.lengths[j]] for j in order], axis=0
    )
    sq_norms = jnp.concatenate(
        [jnp.sum(shards.X[j, : shards.lengths[j]] ** 2, axis=-1) for j in order],
        axis=0,
    )
    return (
        X_recon, y_all, run.wire_bits, shards.lengths[center], sq_norms,
        shards, wire_state, order, run.extras, run.payload_bits,
        run.integrity_bits, run.rows_demoted,
    )


def quantize_to_center(
    parts, bits_per_sample: int, center: int = 0, impl: str = "batched",
    max_bits: int = Q.DEFAULT_MAX_BITS,
):
    """Run the single-center wire protocol; returns
    (X_recon, y_all, wire_bits, n_center, sq_norms).

    X_recon stacks the center's exact block first, then every machine's decoded
    points, matching the paper's gram-row layout.  ``sq_norms`` carries each
    point's EXACT |x|² (an O(32 n)-bit extra the Snelson–Ghahramani/FITC
    diagonal correction needs; included in the wire accounting).

    impl: "host" (serial scipy oracle), "batched" (one vmapped jit), or
    "mesh" (machines are devices; the wire is comm.q_all_gather inside one
    shard_map program) — all three produce integer-identical wire ledgers and
    matching reconstructions (tests/test_conformance.py)."""
    if impl == "host":
        return _quantize_to_center_host(parts, bits_per_sample, center, max_bits)
    if impl not in ("batched", "mesh"):
        raise ValueError(f"unknown impl {impl!r}")
    out = _quantize_to_center_batched(parts, bits_per_sample, center, max_bits, impl)
    return out[:5]


def _pallas_ip_rows(wire: WireState, block_order, lengths, Xc, Y, pack_bits: int):
    """⟨x_i, y_j⟩ for every x in the center gram-row layout (N, p): center rows
    via the Pallas tiled gram on exact points; reconstructed rows straight
    from the PACKED wire words via the fused unpack+dequantize+gram kernel —
    X̂ = dequant(unpack(words)) @ T_inv^T, so ⟨x̂, y⟩ =
    qgram_packed(words, Y @ T_inv).  ``pack_bits`` is the static row bit
    budget the words were packed under (``accounting.row_bits``).  Shared by
    the CenterGP fit-time builder and the FittedProtocol serve path."""
    from ...kernels.gram.ops import gram as gram_kernel
    from ...kernels.qgram.ops import qgram_packed_batched

    idx = list(block_order[1:])
    n_pad = wire.codes.shape[1]
    words = wire.codes[jnp.asarray(idx)]
    rates = wire.rates[jnp.asarray(idx)]
    cents = wire.scaled_cents[jnp.asarray(idx)]
    T_inv = wire.T_inv[jnp.asarray(idx)]
    mask = jnp.asarray(
        np.arange(n_pad)[None, :] < np.asarray([lengths[j] for j in idx])[:, None],
        jnp.float32,
    )
    top = gram_kernel(Xc, Y)  # (n_c, p)
    proj = jnp.einsum("pd,mde->mpe", Y, T_inv)  # Y in each decorrelated basis
    blocks = qgram_packed_batched(
        words, rates, cents, proj, total_bits=pack_bits, mask=mask
    )  # (m-1, n_pad, p)
    rows = [top] + [blocks[i, : lengths[j]] for i, j in enumerate(idx)]
    return jnp.concatenate(rows, axis=0)


@dataclasses.dataclass
class CenterGP:
    kernel: str
    params: GPParams
    X_recon: jnp.ndarray  # center block exact, rest reconstructed
    y: jnp.ndarray
    n_center: int
    wire_bits: int
    gram_mode: str = "nystrom"
    sq_norms: jnp.ndarray | None = None  # exact |x|^2 for the FITC diagonal
    gram_backend: str = "xla"
    wire: WireState | None = None  # packed words + tables (pallas/qgram path)
    block_order: tuple | None = None  # non-center machine ids, X_recon order
    block_lengths: tuple | None = None  # their true row counts
    pack_bits: int = 0  # static row bit budget of the packed wire codes
    payload_bits: int = 0  # measured packed payload (accounting formula)
    integrity_bits: int = 0  # CRC framing ledger (accounting.CRC_BITS/row)
    _ip_cache: dict = dataclasses.field(default_factory=dict, repr=False)

    def __post_init__(self):
        if self.gram_backend == "pallas" and self.wire is None:
            raise ValueError(
                'gram_backend="pallas" requires the batched wire protocol '
                "(int codes) — use impl=\"batched\""
            )

    def _exact_diag(self, params):
        """k(x_i, x_i) from the EXACT squared norms the machines shipped."""
        return prior_diag(self.kernel, params, self.sq_norms)

    # -- pallas/qgram inner-product assembly --------------------------------

    def _ip_rows(self, Y):
        """⟨x_i, y_j⟩ for every x in X_recon layout — see :func:`_pallas_ip_rows`."""
        return _pallas_ip_rows(
            self.wire, self.block_order, self.block_lengths,
            self.X_recon[: self.n_center], Y, self.pack_bits,
        )

    def _ip(self, key: str):
        """Cached param-independent inner products, computed once (with the
        kernels under the pallas backend), then passed to every training step
        and prediction."""
        if key not in self._ip_cache:
            X, Xc = self.X_recon, self.X_recon[: self.n_center]
            if key == "sq":
                ip = jnp.sum(X**2, axis=-1)
            elif self.gram_backend == "pallas":
                ip = self._ip_rows(Xc).T if key == "KN" else self._ip_rows(X)
            else:
                ip = (Xc if key == "KN" else X) @ X.T
            self._ip_cache[key] = ip  # KN (n_c, N), NN (N, N), sq (N,)
        return self._ip_cache[key]

    def train_operands(self) -> dict:
        """What :data:`_TRAIN_GRAMS`' hook for this gram mode reads: the
        cached inner products and squared norms, and the exact squared norms
        of the FITC diagonal."""
        sq = self._ip("sq")
        if self.gram_mode == "direct":
            return {"ip": self._ip("NN"), "sq": sq}
        K, ip_KN = self.n_center, self._ip("KN")
        ops = {"ip_KK": ip_KN[:, :K], "ip_KN": ip_KN, "sq_K": sq[:K], "sq_N": sq}
        if self.gram_mode == "nystrom_fitc" and self.sq_norms is not None:
            ops["sq_exact"] = self.sq_norms
        return ops

    def _nystrom_blocks(self, params):
        """The completed gram's first K rows, (G_KK, G_KN)."""
        if self.gram_backend == "pallas":
            return nystrom_from_inner(params, self.train_operands(), self.kernel)
        k = gram_fn(self.kernel)
        Xc = self.X_recon[: self.n_center]
        return k(params, Xc), k(params, Xc, self.X_recon)

    def _gram(self, params):
        if self.gram_mode == "direct":
            # beyond-paper: all blocks straight from the reconstructed points;
            # converges to the full GP as R -> inf (Nyström caps at rank K)
            if self.gram_backend == "pallas":
                sq = self._ip("sq")
                return kernel_from_inner(self.kernel, params, self._ip("NN"),
                                         sq, sq)
            return gram_fn(self.kernel)(params, self.X_recon)
        G_KK, G_KN = self._nystrom_blocks(params)
        if self.gram_mode == "nystrom_fitc" and self.sq_norms is not None:
            # Snelson & Ghahramani: make the Nyström diagonal exact (the
            # correction acts like per-point noise, taming the rank-K inverse)
            return nystrom_complete(G_KK, G_KN, exact_diag=self._exact_diag(params))
        return nystrom_complete(G_KK, G_KN)

    def predict(self, X_star, available=None):
        # ``available`` is accepted for surface parity with the fused-family
        # models but ignored: the center already holds every decoded shard
        # locally, so serve-time machine loss does not change the predictive
        if self.gram_backend == "pallas":
            return self._predict_pallas(X_star)
        k = gram_fn(self.kernel)
        g_ss = jnp.diagonal(k(self.params, X_star, X_star))
        noise = jnp.exp(self.params.log_noise)
        if self.gram_mode == "nystrom_fitc":
            # dense path: the FITC-corrected gram is full-rank (the exact
            # diagonal acts as per-point noise), so the direct predictive is
            # well-conditioned.  The test cross-covariance must still pass
            # through the Nyström map — the raw k(x*, x) against a
            # Nyström-structured train gram badly mis-weights y-components
            # outside the rank-K span (was the out-of-range seed bug).
            Xc = self.X_recon[: self.n_center]
            G_KK = k(self.params, Xc)
            G_KN = k(self.params, Xc, self.X_recon)
            G = nystrom_complete(G_KK, G_KN, exact_diag=self._exact_diag(self.params))
            G_sn = nystrom_cross(G_KK, G_KN, k(self.params, X_star, Xc))
            return posterior_from_gram(G, G_sn, g_ss, self.y, noise)
        if self.gram_mode == "nystrom":
            # consistent low-rank predictive: the test cross-covariances must
            # pass through the same Nyström map (G_*N = G_*K G_KK^{-1} G_KN),
            # else y-components outside the rank-K span are amplified by 1/s^2
            Xc = self.X_recon[: self.n_center]
            return nystrom_posterior(
                k(self.params, Xc), k(self.params, Xc, self.X_recon),
                self.y, noise, k(self.params, X_star, Xc), g_ss,
            )
        G = self._gram(self.params)
        G_sn = k(self.params, X_star, self.X_recon)
        return posterior_from_gram(G, G_sn, g_ss, self.y, noise)

    def _predict_pallas(self, X_star):
        from ...kernels.gram.ops import gram as gram_kernel

        X_star = jnp.asarray(X_star, jnp.float32)
        p = self.params
        sq = self._ip("sq")
        sq_star = jnp.sum(X_star**2, -1)
        K = self.n_center
        Xc = self.X_recon[:K]
        g_ss = prior_diag(self.kernel, p, sq_star)
        noise = jnp.exp(p.log_noise)
        ip_KN = self._ip("KN")
        G_KK = kernel_from_inner(self.kernel, p, ip_KN[:, :K], sq[:K], sq[:K])
        if self.gram_mode == "nystrom":
            ip_sK = gram_kernel(X_star, Xc)
            G_sK = kernel_from_inner(self.kernel, p, ip_sK, sq_star, sq[:K])
            G_KN = kernel_from_inner(self.kernel, p, ip_KN, sq[:K], sq)
            return nystrom_posterior(G_KK, G_KN, self.y, noise, G_sK, g_ss)
        G = self._gram(p)
        if self.gram_mode == "nystrom_fitc":
            # FITC-consistent test covariance (see the xla path)
            ip_sK = gram_kernel(X_star, Xc)
            G_sK = kernel_from_inner(self.kernel, p, ip_sK, sq_star, sq[:K])
            G_KN = kernel_from_inner(self.kernel, p, ip_KN, sq[:K], sq)
            G_sn = nystrom_cross(G_KK, G_KN, G_sK)
        else:
            ip_sN = self._ip_rows(X_star).T  # (t, N)
            G_sn = kernel_from_inner(self.kernel, p, ip_sN, sq_star, sq)
        return posterior_from_gram(G, G_sn, g_ss, self.y, noise)


def _fitc_train_gram(p: GPParams, operands, kernel: str):
    """The Nyström-completed gram, its diagonal pinned to the exact one
    where the machines shipped their exact squared norms."""
    G_KK, G_KN = nystrom_from_inner(p, operands, kernel)
    exact = operands.get("sq_exact")
    return nystrom_complete(
        G_KK, G_KN,
        exact_diag=None if exact is None else prior_diag(kernel, p, exact),
    )


def _direct_train_gram(p: GPParams, operands, kernel: str):
    """Every block straight from the reconstructed points' inner products."""
    sq = operands["sq"]
    return kernel_from_inner(kernel, p, operands["ip"], sq, sq)


# what ``train_gp`` trains a center fit on, by gram mode: the Nyström pair
# itself in ``nystrom`` mode (woodbury NLML, no N x N matrix), else the dense
# gram; each reads :meth:`CenterGP.train_operands`
_TRAIN_GRAMS = {
    "nystrom": nystrom_from_inner,
    "nystrom_fitc": _fitc_train_gram,
    "direct": _direct_train_gram,
}


def _check_center(cfg, parts):
    if not cfg.center < len(parts):
        raise ValueError(
            f"center={cfg.center} out of range for m={len(parts)} machines"
        )


def fit_center_host(parts, cfg, params: GPParams | None = None) -> CenterGP:
    """The serial scipy oracle (``impl="host"``): one host-side scheme fit and
    one dense Cholesky per machine.  Returns the legacy :class:`CenterGP`
    model (protocol semantics identical to the batched artifact; locked by
    tests/test_batched_protocol.py / test_conformance.py)."""
    from ...comm.accounting import integrity_bits_formula, payload_bits_formula

    _check_center(cfg, parts)
    plan = getattr(cfg, "faults", None)
    if plan is not None and plan.flip_rate > 0.0:
        raise NotImplementedError(
            "wire corruption (flip_rate) needs the packed code plane — the "
            'host oracle has none; use impl="batched" or "mesh"'
        )
    parts, _ = base._apply_fit_faults(parts, cfg)
    X_recon, y_all, wire, n_c, sq_norms = _quantize_to_center_host(
        parts, cfg.bits_per_sample, cfg.center, cfg.max_bits
    )
    d = X_recon.shape[1]
    lengths = [p[0].shape[0] for p in parts]
    payload = payload_bits_formula(
        lengths, d, cfg.bits_per_sample, cfg.max_bits, skip=cfg.center,
    )
    integrity = integrity_bits_formula(lengths, skip=cfg.center)
    if cfg.gram_mode == "nystrom_fitc":  # exact |x|^2 side-channel (32 bits/pt)
        wire += 32 * (X_recon.shape[0] - n_c)
        payload += 32 * (X_recon.shape[0] - n_c)
    model = CenterGP(
        kernel=cfg.kernel,
        params=params or init_params(),
        X_recon=X_recon,
        y=y_all,
        n_center=n_c,
        wire_bits=wire,
        gram_mode=cfg.gram_mode,
        sq_norms=sq_norms,
        gram_backend=cfg.gram_backend,
        payload_bits=payload,
        integrity_bits=integrity,
    )
    model.params = train_gp(
        None, y_all, kernel=cfg.kernel, params=model.params, steps=cfg.steps,
        lr=cfg.lr, gram=_TRAIN_GRAMS[cfg.gram_mode],
        operands=model.train_operands(), impl=cfg.train_impl,
    ).params
    return model


def single_center_gp(
    parts,
    bits_per_sample: int,
    kernel: str = "se",
    steps: int = 150,
    lr: float = 0.05,
    params: GPParams | None = None,
    gram_mode: str = "nystrom",
    impl: str = "batched",
    gram_backend: str = "xla",
    max_bits: int = Q.DEFAULT_MAX_BITS,
    train_impl: str = "scan",
):
    """Full §5.1 protocol: quantize-in, Nyström-complete (eq. 61), train hypers
    on the completed gram by marginal likelihood, return a predictor.

    This is a thin composition over the serving API: the default
    ``impl="batched"`` simply returns ``fit(parts, R, protocol="center", ...)``
    — a :class:`~.base.FittedProtocol` artifact whose ``.predict(X_star)``
    serves queries from cached factors.  ``impl="host"`` is the serial scipy
    reference/oracle (returns the legacy :class:`CenterGP`).  New code should
    prefer ``DistributedGP(DGPConfig(protocol="center", ...))``.
    """
    if impl == "host":
        from ..config import DGPConfig

        cfg = DGPConfig(
            protocol="center", kernel=kernel, impl="host",
            gram_backend=gram_backend, gram_mode=gram_mode,
            bits_per_sample=int(bits_per_sample), max_bits=int(max_bits),
            steps=int(steps), lr=float(lr), train_impl=train_impl,
        )
        return fit_center_host(parts, cfg, params)
    return base.fit(
        parts, bits_per_sample, protocol="center", kernel=kernel, steps=steps,
        lr=lr, params=params, gram_mode=gram_mode, gram_backend=gram_backend,
        max_bits=max_bits, train_impl=train_impl, impl=impl,
    )


# --------------------------------------------------------------------------
# fit / predict / update (the registered protocol triple)
# --------------------------------------------------------------------------


def _fit_center(parts, cfg, params: GPParams | None = None) -> FittedProtocol:
    from ...comm.accounting import row_bits

    _check_center(cfg, parts)
    with span("fit.wire"):
        parts, _ = base._apply_fit_faults(parts, cfg)
        (X_recon, y_all, wire, n_c, sq_norms, shards, wire_state, order, extras,
         payload, integrity, rows_demoted) = (
            _quantize_to_center_batched(
                parts, cfg.bits_per_sample, cfg.center, cfg.max_bits, cfg.impl,
                cfg.scheme, getattr(cfg, "faults", None),
            )
        )
        kernel, gram_mode, gram_backend = cfg.kernel, cfg.gram_mode, cfg.gram_backend
        d = X_recon.shape[1]
        if gram_mode == "nystrom_fitc":  # exact |x|^2 side-channel (32 bits/point)
            wire += 32 * (X_recon.shape[0] - n_c)
            payload += 32 * (X_recon.shape[0] - n_c)
        builder = CenterGP(
            kernel=kernel,
            params=params or init_params(),
            X_recon=X_recon,
            y=y_all,
            n_center=n_c,
            wire_bits=wire,
            gram_mode=gram_mode,
            sq_norms=sq_norms,
            gram_backend=gram_backend,
            wire=wire_state,
            block_order=tuple(order),
            block_lengths=shards.lengths,
            pack_bits=row_bits(cfg.bits_per_sample, d, cfg.max_bits),
            payload_bits=payload,
            integrity_bits=integrity,
        )
        operands = builder.train_operands()  # the inner products the wire gives
    with span("fit.train"):
        builder.params = p = train_gp(
            None, y_all, kernel=kernel, params=builder.params, steps=cfg.steps,
            lr=cfg.lr, gram=_TRAIN_GRAMS[gram_mode], operands=operands,
            impl=cfg.train_impl,
        ).params
        noise = jnp.exp(p.log_noise)
    with span("fit.factors"):
        K = n_c
        Xc = X_recon[:K]
        # ---- the one-time factorization, from the training's inner products ----
        sq_cols = builder._ip("sq")
        if gram_mode != "direct":
            G_KK, G_KN = nystrom_from_inner(p, operands, kernel)
        if gram_mode == "nystrom":
            factors = nystrom_factors(G_KK, G_KN, y_all, noise)
            if getattr(cfg, "serve_epilogue", "fused") == "fused":
                factors.update(nystrom_serve_cache(factors))
        elif gram_mode == "nystrom_fitc":
            G = nystrom_complete(G_KK, G_KN, exact_diag=builder._exact_diag(p))
            factors = posterior_factors(G, y_all, noise)
            # FITC-consistent test map Q_*N = G_*K G_KK^{-1} G_KN needs (L_KK, W)
            L_KK = chol_jittered(G_KK, DEFAULT_JITTER * jnp.trace(G_KK) / K)
            factors["L_KK"] = L_KK
            factors["W"] = jax.scipy.linalg.solve_triangular(L_KK, G_KN, lower=True)
        elif gram_mode == "direct":
            factors = posterior_factors(builder._gram(p), y_all, noise)
        else:
            raise ValueError(f"unknown gram mode {gram_mode!r}")

        data = {
            "Xc": Xc, "X_recon": X_recon, "sq_cols": sq_cols,
            "sq_exact": sq_norms,
            # column-validity mask of the streaming buffers: all-live at fit time
            # (SE kernels do not vanish at padded zero points, so the padded
            # predict/update programs multiply this in)
            "valid": jnp.ones_like(y_all),
        }
        data.update(extras)
        return FittedProtocol(
            params=p,
            y=y_all,
            factors=factors,
            data=data,
            wire=wire_state,
            stream=StreamState.make(
                shards.lengths, y_all.shape[0], int(wire), int(payload),
                int(integrity), int(rows_demoted),
            ),
            protocol="center",
            kernel=kernel,
            gram_mode=gram_mode,
            fuse="",
            gram_backend=gram_backend,
            n_center=K,
            fit_lengths=shards.lengths,
            block_order=tuple(order),
            bits_per_sample=cfg.bits_per_sample,
            max_bits=cfg.max_bits,
            impl=cfg.impl,
            scheme=cfg.scheme,
            config=cfg,
        )


def _predict_center(art: FittedProtocol, X_star, sq_star, g_ss, noise, avail=None):
    # the center holds every factor locally, so machine availability cannot
    # change what it serves: the artifact IS the last-good decoded state
    # (losses are surfaced through base.serve_health instead)
    p = art.params
    Xc = art.data["Xc"]
    K = art.n_center
    sq_cols = art.data["sq_cols"]
    if art.gram_backend == "pallas":
        from ...kernels.gram.ops import gram as gram_kernel

        ip_sK = gram_kernel(X_star, Xc)
        G_sK = kernel_from_inner(art.kernel, p, ip_sK, sq_star, sq_cols[:K])
    else:
        G_sK = gram_fn(art.kernel)(p, X_star, Xc)
    if art.gram_mode == "nystrom":
        if "Ainv" in art.factors:  # fused serve epilogue: K-sized matmuls only
            return nystrom_apply_cached(art.factors, G_sK, g_ss, noise)
        return nystrom_apply(art.factors, G_sK, g_ss, noise)
    if art.gram_mode == "nystrom_fitc":
        # FITC-consistent test covariance: Q_*N = G_*K G_KK^{-1} G_KN from the
        # cached (L_KK, W) — raw k(x*, x) against a Nyström-structured train
        # gram badly mis-weights y-components outside the rank-K span
        B = jax.scipy.linalg.solve_triangular(
            art.factors["L_KK"], G_sK.T, lower=True
        )
        return posterior_apply(art.factors, B.T @ art.factors["W"], g_ss)
    # direct
    if art.gram_backend == "pallas":
        ip_sN = _artifact_ip_rows(art, X_star).T  # (t, N)
        G_sn = kernel_from_inner(art.kernel, p, ip_sN, sq_star, sq_cols)
    else:
        # padded capacity slots hold the zero point, where SE kernels do NOT
        # vanish — the validity mask zeroes those cross-columns exactly
        G_sn = gram_fn(art.kernel)(p, X_star, art.data["X_recon"]) \
            * art.data["valid"][None, :]
    return posterior_apply(art.factors, G_sn, g_ss)


def _artifact_ip_rows(art, Y):
    """⟨x_i, y_j⟩ in the artifact's X_recon layout — see :func:`_pallas_ip_rows`."""
    from ...comm.accounting import row_bits

    pack_bits = row_bits(art.bits_per_sample, art.data["Xc"].shape[1], art.max_bits)
    # fit_lengths, not the live counts: this path reads the fit-time wire
    # codes (pallas direct artifacts refuse streaming updates), and the
    # static tuple keeps the block layout out of the traced program
    return _pallas_ip_rows(
        art.wire, art.block_order, art.fit_lengths, art.data["Xc"], Y, pack_bits
    )


@jax.jit
def _update_center_jit(art, X_new, y_new, j, pre):
    """The device-resident streaming append: one traced program per
    (capacity, n_new, pre-treedef) — the machine index ``j`` is traced, so
    every machine shares the cache entry, and all state (factors, buffers,
    ledgers) moves as pytree leaves with fixed shapes."""
    _UPDATE_TRACES["center"] += 1  # runs at trace time only
    p = art.params
    noise = jnp.exp(p.log_noise)
    n_new = X_new.shape[0]
    s2 = noise + DEFAULT_JITTER
    if pre is None:
        # transmitting machine, jit-safe scheme: the full wire plane
        # (encode→pack→CRC→unpack→decode) runs inside this program
        decoded, w_add, p_add, i_add = SCHEMES.get(art.scheme).reencode_traced(
            art, j, X_new
        )
        d_add = jnp.int32(0)
        if art.gram_mode == "nystrom_fitc":
            w_add = w_add + 32 * n_new  # exact |x|^2 side channel
            p_add = p_add + 32 * n_new
    else:  # host-precomputed batch (center-local, vq channel, or faulted)
        decoded, w_add, p_add, i_add, d_add = pre
    pos = art.stream.cols
    sq_new = jnp.sum(decoded**2, -1)
    sq_new_exact = jnp.sum(X_new**2, -1)
    k = gram_fn(art.kernel)
    Xc = art.data["Xc"]
    valid = art.data["valid"]
    y2 = jax.lax.dynamic_update_slice(art.y, y_new, (pos,))
    f = dict(art.factors)

    if art.gram_mode == "nystrom":
        # columns append on the woodbury form: W gains L_KK^{-1} G_K,new IN
        # PLACE at the occupied-column cursor, and L_M = chol(s2 I + W W^T)
        # takes a rank-n_new update (zero padded W columns contribute nothing)
        W_new = jax.scipy.linalg.solve_triangular(
            f["L_KK"], k(p, Xc, decoded), lower=True
        )
        f["W"] = jax.lax.dynamic_update_slice(f["W"], W_new, (0, pos))
        f["L_M"] = chol_update_rank(f["L_M"], W_new)
        f["alpha"] = nystrom_kinv(f["W"], f["L_M"], s2, y2)
        if "Ainv" in f:
            # fused-epilogue cache maintenance: walpha is an O(K C)
            # recompute; Ainv is fixed
            f["walpha"] = f["W"] @ f["alpha"]
    elif art.gram_mode == "direct":
        # the validity mask zeroes cross-covariances against padded slots
        # (k(x, 0) != 0 for SE), keeping chol_append_at's zero-row contract
        G_on = k(p, art.data["X_recon"], decoded) * valid[:, None]
        G_nn = k(p, decoded) + s2 * jnp.eye(n_new, dtype=G_on.dtype)
        f["L"] = chol_append_at(f["L"], G_on, G_nn, pos)
        f["alpha"] = jax.scipy.linalg.cho_solve((f["L"], True), y2)
    else:  # nystrom_fitc: bordered dense factor through the Nyström map
        W_new = jax.scipy.linalg.solve_triangular(
            f["L_KK"], k(p, Xc, decoded), lower=True
        )
        G_on = f["W"].T @ W_new  # padded W columns are zero: zero rows, exact
        corr = jnp.maximum(
            prior_diag(art.kernel, p, sq_new_exact) - jnp.sum(W_new**2, 0), 0.0
        )
        G_nn = W_new.T @ W_new + jnp.diag(corr) + s2 * jnp.eye(n_new)
        f["L"] = chol_append_at(f["L"], G_on, G_nn, pos)
        f["alpha"] = jax.scipy.linalg.cho_solve((f["L"], True), y2)
        f["W"] = jax.lax.dynamic_update_slice(f["W"], W_new, (0, pos))

    data = dict(art.data)
    zero = jnp.int32(0)
    data["X_recon"] = jax.lax.dynamic_update_slice(
        data["X_recon"], decoded, (pos, zero)
    )
    data["sq_cols"] = jax.lax.dynamic_update_slice(data["sq_cols"], sq_new, (pos,))
    data["sq_exact"] = jax.lax.dynamic_update_slice(
        data["sq_exact"], sq_new_exact, (pos,)
    )
    data["valid"] = jax.lax.dynamic_update_slice(
        valid, jnp.ones((n_new,), valid.dtype), (pos,)
    )
    s = art.stream
    stream = StreamState(
        counts=s.counts.at[j].add(n_new), cols=s.cols + n_new,
        wire_bits=s.wire_bits + w_add, payload_bits=s.payload_bits + p_add,
        integrity_bits=s.integrity_bits + i_add,
        rows_demoted=s.rows_demoted + d_add,
    )
    return dataclasses.replace(art, y=y2, factors=f, data=data, stream=stream)


def _update_center(art: FittedProtocol, X_new, y_new, j, pre=None):
    if art.gram_backend == "pallas" and art.gram_mode != "nystrom":
        raise NotImplementedError(
            "streaming update of pallas-backed center artifacts supports "
            'gram_mode="nystrom" only (direct/fitc query paths read the '
            "fit-time wire codes, which update does not extend)"
        )
    return _update_center_jit(art, X_new, y_new, base._machine_index(j), pre)


register_protocol(ProtocolSpec(
    name="center",
    fit=_fit_center,
    predict=_predict_center,
    update=_update_center,
    fit_host=fit_center_host,
))


# --------------------------------------------------------------------------
# the program contract (repro.analysis.check_contracts enforces it)
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

# §5.1 serving: the center holds ONE factor set, so a warm predict is pure
# triangular algebra — zero factorizations, zero host round-trips, zero
# collectives (machines were a fit-time construct), and nothing committed to
# more than one device (impl="mesh" unshards at the fit boundary).
register_contract("center", "predict", Contract(
    name="center-serve",
    rules=(
        forbid_primitives(),
        NoHostCallbacks(),
        CollectiveBudget(max_count=0),
        NoShardingLeak(max_devices=1),
        LedgerAccounting(),
    ),
))
register_contract("center", "update", Contract(
    name="center-update",
    rules=(NoShardingLeak(max_devices=1), LedgerAccounting()),
))
