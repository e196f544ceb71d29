"""Machines-as-devices scaling benchmark (EXPERIMENTS.md §Mesh): the
impl="mesh" execution path on 2 -> 8 devices.

Rows (written to BENCH_mesh.json via benchmarks/run.py --json, or standalone):

* ``mesh/fit_<protocol>_m<k>`` — one full fit(impl="mesh") wall clock
  (wire collectives + training + sharded factor build, includes
  trace/compile) with the wire-bit ledger and its fp32 all-gather baseline;
* ``mesh/predict_<protocol>_m<k>`` — the warm shard_map serve loop
  (per-query-batch latency; psum/KL fusion epilogue on the mesh);
* ``mesh/conformance_m<k>`` — max |mesh - batched| prediction deviation on
  the shared problem, asserted small (the in-benchmark cross-impl check).

The machine mesh needs one device per machine, so the bench runs in the
calling process on the devices that exist: m in {2, 4, 8} up to the device
count (four on a v5e 2x2 host).  The scaling gate compares the smallest and
largest m, so it refuses to run on fewer than four devices.  A CPU-only host stands in with placeholder devices, set before the
process starts: ``XLA_FLAGS=--xla_force_host_platform_device_count=8``.

Run standalone:  PYTHONPATH=src python -m benchmarks.mesh_bench [--full]
or through the driver: PYTHONPATH=src python -m benchmarks.run --json --only mesh
"""
from __future__ import annotations

import time

import numpy as np

from .common import emit

# center gets the strict 2x gate (that's where the PR-8 collapse lived);
# broadcast runs one more collective per call and, with 8 placeholder host
# devices oversubscribing a small container's cores, measured ~2.2x — gated
# at that threshold + headroom, still far below the 12x failure mode.
GATE_MAX_RATIO = {"center": 2.0, "broadcast": 3.0}


def _machine_counts():
    import jax

    n_dev = len(jax.devices())
    ms = [m for m in (2, 4, 8) if m <= n_dev]
    if len(ms) < 2:
        raise SystemExit(
            f"mesh_bench: impl='mesh' puts one machine on each device and the "
            f"scaling gate compares two machine counts, so it needs at least 4 "
            f"devices; this process sees {n_dev} {jax.devices()[0].platform} "
            f"device(s).  On a CPU-only host start it with "
            f"XLA_FLAGS=--xla_force_host_platform_device_count=8"
        )
    return ms


def _bench(quick: bool) -> list:
    import jax
    import jax.numpy as jnp
    from repro.core import split_machines, fit, predict

    ms = _machine_counts()
    rng = np.random.default_rng(0)
    d = 8
    n_per = 40 if quick else 250
    rows = []
    qps = {}
    for m in ms:
        n = m * n_per
        W = rng.normal(size=(d, 2))
        f = lambda Z: np.sin(Z @ W[:, 0]) + 0.4 * (Z @ W[:, 1])
        X = rng.normal(size=(n, d)).astype(np.float32)
        y = (f(X) + 0.05 * rng.normal(size=n)).astype(np.float32)
        Xt = rng.normal(size=(64, d)).astype(np.float32)
        parts = split_machines(X, y, m, jax.random.PRNGKey(0))
        steps = 10 if quick else 60
        for protocol, bits in (("broadcast", 24), ("center", 24)):
            t0 = time.perf_counter()
            art = fit(parts, bits, protocol, steps=steps, impl="mesh")
            mu, _ = predict(art, Xt)
            jax.block_until_ready(mu)
            t_fit = time.perf_counter() - t0
            # fp32 baseline: every transmitting machine ships raw floats
            tx = art.lengths[1:] if protocol == "center" else art.lengths
            fp32_bits = sum(32 * d * n_j for n_j in tx)
            rows.append({
                "name": f"mesh/fit_{protocol}_m{m}",
                "us_per_call": t_fit * 1e6,
                "derived": {"m": m, "n": n, "d": d, "bits": bits,
                            "wire_kbits": art.wire_bits / 1e3,
                            "payload_kbits": art.payload_bits / 1e3,
                            "fp32_baseline_kbits": fp32_bits / 1e3,
                            "wire_vs_fp32": art.wire_bits / fp32_bits},
            })
            # warm serve loop (trace once, then measure)
            predict(art, Xt)
            reps = 5 if quick else 20
            t0 = time.perf_counter()
            for _ in range(reps):
                mu, s2 = predict(art, Xt)
            jax.block_until_ready(mu)
            t_warm = (time.perf_counter() - t0) / reps
            qps[(protocol, m)] = 64 / t_warm
            rows.append({
                "name": f"mesh/predict_{protocol}_m{m}",
                "us_per_call": t_warm * 1e6,
                "derived": {"m": m, "batch": 64, "qps": 64 / t_warm},
            })
        # cross-impl conformance on the shared problem
        art_b = fit(parts, 24, "broadcast", steps=steps)
        art_m = fit(parts, 24, "broadcast", steps=steps, impl="mesh")
        mu_b, _ = predict(art_b, Xt)
        mu_m, _ = predict(art_m, Xt)
        dev = float(jnp.max(jnp.abs(mu_b - mu_m)))
        assert dev < 1e-2, f"mesh/batched divergence {dev}"
        assert art_b.wire_bits == art_m.wire_bits
        rows.append({
            "name": f"mesh/conformance_m{m}",
            "us_per_call": 0.0,
            "derived": {"m": m, "max_abs_mu_dev": dev, "wire_bits_equal": 1},
        })

    # the scaling gate: predict throughput must stay near-constant in m (the
    # PR-8 regression was a 12x center-protocol collapse from m=2 to m=8,
    # caused by the wire program's committed replicated sharding leaking into
    # the serve-time jit)
    lo, hi = ms[0], ms[-1]
    for protocol in ("broadcast", "center"):
        q_lo, q_hi = qps[(protocol, lo)], qps[(protocol, hi)]
        ratio = q_lo / q_hi
        gate = GATE_MAX_RATIO[protocol]
        assert ratio < gate, (
            f"mesh predict scaling collapse ({protocol}): m={lo} {q_lo:.0f} "
            f"qps vs m={hi} {q_hi:.0f} qps ({ratio:.2f}x > {gate}x gate)"
        )
        rows.append({
            "name": f"mesh/predict_scaling_{protocol}",
            "us_per_call": 0.0,
            "derived": {f"qps_m{lo}": q_lo, f"qps_m{hi}": q_hi,
                        "ratio": ratio, "gate_max_ratio": gate,
                        "gate_ok": 1},
        })
    return rows


def main(quick: bool = True) -> None:
    for row in _bench(quick):
        emit(row["name"], row["us_per_call"], **row["derived"])


if __name__ == "__main__":
    import argparse
    import json

    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true")
    args = ap.parse_args()
    main(quick=not args.full)
    from .common import RESULTS

    with open("BENCH_mesh.json", "w") as f:
        json.dump(RESULTS, f, indent=1)
    print("# wrote BENCH_mesh.json")
