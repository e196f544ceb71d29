"""Pallas kernel microbenchmarks (interpret mode on CPU; on-TPU the same
entry points compile natively).  Reports us/call and achieved element rates,
plus the fused-vs-unfused HBM-traffic ratio that motivates kernels/qgram,
and a FlagGems-style shape sweep of every registered backend of the key ops
through the unified kernel runtime (``kernel_sweep/<op>/<case>/<backend>``
rows — the honest table of when the XLA fallback beats the interpreter).
"""
from __future__ import annotations

import numpy as np
import jax

from repro.core import quantizers as Q
from repro.kernels import runtime
from repro.kernels.gram.ops import gram
from repro.kernels.gram.ref import gram_ref
from repro.kernels.quant.ops import encode, decode, build_scaled_tables
from repro.kernels.qgram.ops import qgram
from repro.kernels.epilogue.ops import epilogue_moments
from .common import timed, emit


def main(quick: bool = True):
    rng = np.random.default_rng(0)
    n, d, p = (256, 64, 256) if quick else (1024, 128, 1024)
    x = rng.normal(size=(n, d)).astype(np.float32)
    y = rng.normal(size=(p, d)).astype(np.float32)

    _, us = timed(lambda: jax.block_until_ready(gram(x, y, interpret=True)), repeats=2)
    _, us_ref = timed(lambda: jax.block_until_ready(gram_ref(x, y)), repeats=2)
    emit("kernel_gram", us, flops=2 * n * d * p, ref_us=us_ref)

    var = rng.uniform(0.1, 2.0, size=d)
    rates = Q.allocate_bits_greedy(var, 4 * d, 8)
    sigma = np.sqrt(var).astype(np.float32)
    edges, cents = build_scaled_tables(sigma, rates)
    xs = (rng.normal(size=(n, d)) * sigma).astype(np.float32)
    codes, us = timed(lambda: jax.block_until_ready(encode(xs, edges, interpret=True)), repeats=2)
    emit("kernel_quant_encode", us, elems=n * d)
    _, us = timed(lambda: jax.block_until_ready(decode(codes, cents, interpret=True)), repeats=2)
    emit("kernel_quant_decode", us, elems=n * d)

    _, us = timed(lambda: jax.block_until_ready(qgram(codes, cents, y, interpret=True)), repeats=2)
    # HBM traffic: unfused writes+reads the (n, d) fp32 reconstruction
    unfused_bytes = n * d * 4 * 2 + (n * d * 1 + p * d * 4 + n * p * 4)
    fused_bytes = n * d * 1 + p * d * 4 + n * p * 4
    emit("kernel_qgram_fused", us, traffic_ratio=unfused_bytes / fused_bytes)

    # fused serve epilogue: m experts' cached apply + fusion moments, 1 launch
    m_e, t_e, K_e = (8, 128, 64) if quick else (16, 512, 128)
    ep_args = _epilogue_args(rng, m_e, t_e, K_e)
    _, us = timed(lambda: jax.block_until_ready(
        epilogue_moments(*ep_args, fuse="kl", interpret=True)), repeats=2)
    _, us_x = timed(lambda: jax.block_until_ready(
        epilogue_moments(*ep_args, fuse="kl")), repeats=2)
    emit("kernel_epilogue", us, experts=m_e, t=t_e, K=K_e, xla_us=us_x)

    # ---- unified-runtime shape sweep: every backend of every swept op ----
    sweeps = {
        "gram": [
            (f"{n_}x{d_}x{p_}",
             (lambda n_=n_, d_=d_, p_=p_: (
                 rng.normal(size=(n_, d_)).astype(np.float32),
                 rng.normal(size=(p_, d_)).astype(np.float32))),
             None)
            for n_, d_, p_ in ([(64, 16, 64), (256, 64, 256)] if quick
                               else [(64, 16, 64), (256, 64, 256),
                                     (1024, 128, 1024)])
        ],
        "epilogue": [
            (f"m{mm}t{tt}K{kk}",
             (lambda mm=mm, tt=tt, kk=kk: _epilogue_args(rng, mm, tt, kk)),
             {"fuse": "kl"})
            for mm, tt, kk in ([(4, 128, 64)] if quick
                               else [(4, 128, 64), (16, 512, 128)])
        ],
    }
    for op, cases in sweeps.items():
        for label, backend, us in runtime.shape_sweep(op, cases, reps=2):
            emit(f"kernel_sweep/{op}/{label}/{backend}", us,
                 sweeps_run=runtime.sweep_count())


def _epilogue_args(rng, m, t, K):
    G = rng.normal(size=(m, t, K)).astype(np.float32)
    Ainv = np.broadcast_to(np.eye(K, dtype=np.float32), (m, K, K)).copy()
    P = 0.01 * np.broadcast_to(np.eye(K, dtype=np.float32), (m, K, K)).copy()
    walpha = rng.normal(size=(m, K)).astype(np.float32)
    gss = rng.uniform(1.0, 2.0, size=(t,)).astype(np.float32)
    w = np.ones((m,), np.float32)
    return G, Ainv, P, walpha, gss, gss + 0.1, w


if __name__ == "__main__":
    main()
