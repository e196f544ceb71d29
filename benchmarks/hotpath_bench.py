"""Perf-regression harness for the distributed-GP hot path (EXPERIMENTS.md §Perf).

Times old-vs-new on three axes so the speedups are recorded numbers:

* ``train_gp``: legacy per-step jit dispatch loop vs the single lax.scan
  program (dispatch counts are structural: ``steps`` host dispatches vs 1);
* ``broadcast_gp`` with m=8: serial host protocol (scipy scheme fit + one
  dense solve per machine) vs the vmapped padded-shard protocol;
* quantized gram assembly: unfused (decode X̂ to HBM, then matmul — two
  dispatches) vs the fused unpack+dequantize+gram path consuming the PACKED
  wire words (``kernels.qgram.qgram_packed``: the Pallas kernel on TPU, the
  single-jit XLA program elsewhere).  A fused speedup below 1.0x is a
  regression: the row gets a nonzero ``note`` in BENCH_hotpath.json so CI
  artifacts surface it.

Run standalone to write BENCH_hotpath.json:
  PYTHONPATH=src python -m benchmarks.hotpath_bench [--full]
or through the driver: PYTHONPATH=src python -m benchmarks.run --json --only hotpath
"""
from __future__ import annotations

import json

import numpy as np
import jax
import jax.numpy as jnp

from .common import timed, emit


def _problem(n, d, m, seed=0):
    from repro.core import split_machines

    rng = np.random.default_rng(seed)
    W = rng.normal(size=(d, 2))
    X = rng.normal(size=(n, d)).astype(np.float32)
    y = (np.sin(X @ W[:, 0]) + 0.4 * (X @ W[:, 1]) + 0.05 * rng.normal(size=n)).astype(
        np.float32
    )
    Xt = rng.normal(size=(max(n // 6, 16), d)).astype(np.float32)
    parts = split_machines(X, y, m, jax.random.PRNGKey(seed))
    return X, y, jnp.asarray(Xt), parts


def main(quick: bool = True):
    from repro.core import train_gp, broadcast_gp
    from repro.core.distributed_gp import pad_parts, _run_wire_protocol
    from repro.kernels.gram.ops import gram as gram_kernel
    from repro.kernels.qgram.ops import qgram_packed
    from repro.kernels.quant.ops import decode as quant_decode

    n, d, m = (240, 6, 8) if quick else (1000, 21, 40)
    steps = 30 if quick else 150
    X, y, Xt, parts = _problem(n, d, m)

    # ---- train_gp: per-step dispatch loop vs one scanned program ----
    # Cold rows: the in-memory caches cleared before each call, so it
    # re-traces + re-compiles (what a fresh process pays); warm rows reuse
    # the programs train_gp keeps per shape.  Block on the returned params
    # so async device execution is inside the measured window.
    def train(impl, cold):
        if cold:
            jax.clear_caches()
        return jax.block_until_ready(
            train_gp(X, y, steps=steps, impl=impl).params)

    _, us_loop = timed(train, "loop", True, repeats=1)
    _, us_scan = timed(train, "scan", True, repeats=1)
    emit("hotpath/train_gp_loop", us_loop, host_dispatches=steps, includes_compile=1)
    emit(
        "hotpath/train_gp_scan",
        us_scan,
        host_dispatches=1,
        dispatch_ratio=steps,  # structural: loop issues `steps` jit calls, scan 1
        speedup=us_loop / us_scan,
        includes_compile=1,
    )
    _, us_loop_w = timed(train, "loop", False)  # timed() warms once
    _, us_scan_w = timed(train, "scan", False)
    emit("hotpath/train_gp_loop_warm", us_loop_w, host_dispatches=steps)
    emit(
        "hotpath/train_gp_scan_warm",
        us_scan_w,
        host_dispatches=1,
        speedup=us_loop_w / us_scan_w,
    )

    # ---- broadcast_gp m=8: serial host protocol vs vmapped shards ----
    _, us_host = timed(
        lambda: jax.block_until_ready(
            broadcast_gp(parts, 24, Xt, steps=steps, impl="host", train_impl="loop")[0]
        ),
        repeats=1,
    )
    _, us_bat = timed(
        lambda: jax.block_until_ready(broadcast_gp(parts, 24, Xt, steps=steps)[0]),
        repeats=1,
    )
    emit(f"hotpath/broadcast_gp_m{m}_host", us_host)
    emit(f"hotpath/broadcast_gp_m{m}_batched", us_bat, speedup=us_host / us_bat)

    # ---- quantized gram: unfused decode->HBM->matmul vs fused packed qgram ----
    from repro.core import jax_scheme

    bits = 24
    shards = pad_parts(parts)
    ws = _run_wire_protocol(shards.X, shards.mask, bits, 12, "broadcast", 0)
    words = ws.codes[1]  # the packed wire plane, straight off the protocol
    rates = ws.rates[1]
    cents = ws.scaled_cents[1]
    codes = jax_scheme.unpack_codes(words, rates, total_bits=bits)
    Y = jnp.asarray(np.random.default_rng(1).normal(size=(n, d)).astype(np.float32))

    def unfused():
        xhat = quant_decode(codes, cents)  # X̂ materialized (the HBM round-trip)
        return gram_kernel(xhat, Y)

    def fused():
        return qgram_packed(words, rates, cents, Y, total_bits=bits)

    ref, us_unfused = timed(lambda: jax.block_until_ready(unfused()))
    out, us_fused = timed(lambda: jax.block_until_ready(fused()))
    err = float(jnp.max(jnp.abs(ref - out)))
    speedup = us_unfused / us_fused
    derived = dict(speedup=speedup, max_abs_err=err)
    if speedup < 1.0:
        # visible in the uploaded BENCH artifact: the fusion is LOSING
        derived["note"] = (
            f"REGRESSION: fused qgram {speedup:.2f}x slower than unfused"
        )
    emit("hotpath/qgram_unfused", us_unfused)
    emit("hotpath/qgram_fused", us_fused, **derived)


if __name__ == "__main__":
    import argparse

    from . import common

    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--out", default="BENCH_hotpath.json")
    args = ap.parse_args()
    print("name,us_per_call,derived")
    main(quick=not args.full)
    with open(args.out, "w") as f:
        json.dump(common.RESULTS, f, indent=1)
    print(f"# wrote {args.out}")
