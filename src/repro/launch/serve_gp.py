"""Distributed-GP serving driver: fit the communication-limited protocol ONCE,
checkpoint the artifact, then serve query batches (and optionally stream new
points) from the cached factors.

  python -m repro.launch.serve_gp --protocol center --m 40 \
      --bits 24 --n 2000 --d 8 --steps 60 --queries 50 --batch 128 \
      --artifact-dir /tmp/gp_artifact [--stream-every 20 --stream-size 16]

The driver builds ONE validated ``DGPConfig`` from the CLI flags and drives
everything through the ``DistributedGP`` facade — protocol, wire scheme
(``--scheme per_symbol|vq``), impl, and backend are all config fields, so the
command line is a 1:1 mirror of the API.  The serve loop deliberately
round-trips through the checkpoint (save -> load) so what is timed is exactly
the production story: a server process that never refits — it loads factors
and answers.  Warm-path structure is printed at the end (retraces,
cholesky/eigh equation counts) alongside latency/throughput.

The loop is hardened for unattended runs: fit and checkpoint-load retry with
exponential backoff, ``--timeout-ms`` tracks per-request latency against a
budget, ``--chaos`` injects a :class:`repro.faults.FaultPlan` (drops, NaN
shards, packed-word bit flips, stragglers) and periodically serves under a
degraded availability mask with a health report, and a mesh reload-parity
failure exits nonzero instead of serving a diverged artifact.
"""
from __future__ import annotations

import argparse
import sys
import time


def _retry(label: str, fn, attempts: int = 3, backoff: float = 0.5,
           sleep=time.sleep):
    """Run ``fn()`` with exponential-backoff retries; re-raise after the last
    attempt (transient load/fit failures should not kill an unattended
    server, persistent ones should).  ``sleep`` is injectable so tests
    exercise the backoff schedule without waiting it out."""
    for k in range(attempts):
        try:
            return fn()
        except Exception as e:  # noqa: BLE001 - last attempt re-raises
            if k == attempts - 1:
                raise
            wait = backoff * (2 ** k)
            print(f"  [{label}] attempt {k + 1}/{attempts} failed "
                  f"({type(e).__name__}: {e}); retrying in {wait:.1f}s",
                  file=sys.stderr)
            sleep(wait)


def _parse_chaos(spec: str):
    """``--chaos`` spec -> FaultPlan: comma-joined ``drop:J``, ``nan:J``,
    ``flip:RATE``, ``straggle:J@SECONDS`` clauses, e.g.
    ``drop:1,flip:0.01,straggle:3@0.2``."""
    from repro.faults import FaultPlan, corrupt_words, drop_machine, nan_shard, straggler

    plan = FaultPlan()
    for clause in spec.split(","):
        clause = clause.strip()
        if not clause:
            continue
        kind, _, val = clause.partition(":")
        if kind == "drop":
            plan = plan | drop_machine(int(val))
        elif kind == "nan":
            plan = plan | nan_shard(int(val))
        elif kind == "flip":
            plan = plan | corrupt_words(float(val))
        elif kind == "straggle":
            j, _, delay = val.partition("@")
            plan = plan | straggler(int(j), float(delay or 0.1))
        else:
            raise ValueError(
                f"unknown chaos clause {clause!r} (known: drop:J, nan:J, "
                "flip:RATE, straggle:J@SECONDS)"
            )
    return plan


def _run_fleet(args, art, degraded_avail, rng):
    """``--fleet`` mode: serve a multi-tenant fleet derived from the fitted
    artifact through the launch.fleet server (LRU artifact cache,
    latency-budgeted micro-batching, one stacked dispatch per flush).  The
    chaos/degraded machinery applies PER TENANT: every 7th flush-width block
    tags one tenant's request with the degraded availability mask, and only
    that tenant's answers renormalize over survivors."""
    import tempfile

    import numpy as np
    from repro.core.fleet import fleet_trace_count
    from repro.core.protocols import serve_trace_count

    from .fleet import FleetServer, build_fleet, serve_loop, zipf_tenants

    n_requests = max(args.queries, 4 * args.fleet_slots)
    with tempfile.TemporaryDirectory() as td:
        store_dir = args.artifact_dir or td
        store, tids = build_fleet([art], args.fleet_tenants, store_dir)
        print(f"fleet: {len(tids)} tenants (y-scaled variants of the fit) "
              f"stored under {store_dir}")
        server = FleetServer(
            store,
            cache_artifacts=args.fleet_cache,
            cache_bytes=args.fleet_cache_bytes or None,
            slots=args.fleet_slots,
            budget_ms=args.fleet_budget_ms,
        )
        stream = zipf_tenants(tids, n_requests, a=args.fleet_zipf)
        make_query = lambda i: rng.normal(
            size=(args.batch, args.d)
        ).astype(np.float32)
        degraded_every = 7 if degraded_avail is not None else 0
        # warm pass traces the per-bucket programs (healthy + degraded
        # shapes); the measured loop must then hold every counter flat
        serve_loop(server, stream[: 4 * args.fleet_slots], make_query,
                   degraded_every=degraded_every,
                   degraded_avail=degraded_avail)
        server.reset_stats()
        c0 = fleet_trace_count(args.protocol)
        s0 = serve_trace_count(args.protocol)
        t0 = time.perf_counter()
        stats = serve_loop(server, stream, make_query,
                           degraded_every=degraded_every,
                           degraded_avail=degraded_avail)
        wall = time.perf_counter() - t0
        retraces = (fleet_trace_count(args.protocol) - c0) + \
            (serve_trace_count(args.protocol) - s0)
        qps = stats["completed"] * args.batch / wall
        c = stats["cache"]
        print(f"fleet serve: {stats['completed']} requests x {args.batch} "
              f"pts in {wall:.2f}s -> {qps:.0f} q/s | p50 "
              f"{stats['p50_ms']:.2f} ms p99 {stats['p99_ms']:.2f} ms "
              f"(budget {args.fleet_budget_ms} ms, flush width "
              f"{args.fleet_slots})")
        print(f"fleet cache: hit rate {c['hit_rate']:.2f} "
              f"({c['hits']}h/{c['misses']}m, {c['evictions']} evictions) | "
              f"{stats['stacks']} stack(s), {stats['stack_swaps']} tenant "
              f"swaps | steady-state retraces={retraces}")
        if retraces:
            print("FATAL: steady-state fleet loop retraced", file=sys.stderr)
            sys.exit(1)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--protocol", default="center",
                    choices=["center", "broadcast", "poe"])
    ap.add_argument("--scheme", default="per_symbol",
                    choices=["per_symbol", "vq"],
                    help="wire scheme: §4.2 per-symbol int codes or the §4.1 "
                         "Theorem-2 optimal test channel (batched impl only)")
    ap.add_argument("--m", type=int, default=40, help="machines (paper §6: 40)")
    ap.add_argument("--bits", type=int, default=24, help="R bits/sample")
    ap.add_argument("--n", type=int, default=2000)
    ap.add_argument("--d", type=int, default=8)
    ap.add_argument("--steps", type=int, default=60, help="hyperparameter steps")
    ap.add_argument("--gram-mode", default="nystrom")
    ap.add_argument("--gram-backend", default="xla", choices=["xla", "pallas"])
    ap.add_argument("--fusion", default=None,
                    help="broadcast fusion / poe combiner (registry name); "
                         "default: kl for broadcast, rbcm for poe")
    ap.add_argument("--queries", type=int, default=50, help="warm query batches")
    ap.add_argument("--batch", type=int, default=128, help="points per query batch")
    ap.add_argument("--artifact-dir", default=None,
                    help="checkpoint the artifact here and serve from the "
                         "loaded copy (omit to serve the in-memory artifact)")
    ap.add_argument("--stream-every", type=int, default=0,
                    help="every k query batches, stream new points in via "
                         "update() (0 = never)")
    ap.add_argument("--stream-size", type=int, default=16,
                    help="points per streaming update")
    ap.add_argument("--mesh", action="store_true",
                    help="machines-as-devices: one attached device per "
                         "machine, so --m may not exceed the device count; "
                         "runs the wire protocol, factor builds, and serving "
                         "as shard_map programs (impl='mesh').  A CPU-only "
                         "host gets --m placeholder host devices")
    ap.add_argument("--chaos", default=None,
                    help="fault-injection spec, e.g. 'drop:1,flip:0.01,"
                         "straggle:3@0.2' (see docs/fault_model.md); every "
                         "7th serve batch also runs under a degraded "
                         "availability mask with a health report")
    ap.add_argument("--timeout-ms", type=float, default=0.0,
                    help="per-request latency budget; over-budget requests "
                         "are counted and reported (0 = no budget)")
    ap.add_argument("--retries", type=int, default=3,
                    help="fit/load attempts before giving up")
    ap.add_argument("--fleet", action="store_true",
                    help="multi-tenant mode: derive --fleet-tenants y-scaled "
                         "tenants from the fit and serve them through the "
                         "launch.fleet server (LRU artifact cache + "
                         "latency-budgeted micro-batching); chaos/degraded "
                         "masks apply per tenant")
    ap.add_argument("--fleet-tenants", type=int, default=16)
    ap.add_argument("--fleet-cache", type=int, default=8,
                    help="artifact cache capacity (count)")
    ap.add_argument("--fleet-cache-bytes", type=int, default=0,
                    help="artifact cache capacity in bytes (0 = unbounded)")
    ap.add_argument("--fleet-budget-ms", type=float, default=2.0,
                    help="micro-batch latency budget")
    ap.add_argument("--fleet-slots", type=int, default=4,
                    help="micro-batch flush width")
    ap.add_argument("--fleet-zipf", type=float, default=1.1,
                    help="zipf exponent of the tenant traffic mix")
    args = ap.parse_args()

    if args.mesh:
        # must happen before the jax backend initializes; only the host
        # platform reads it, so a chip host keeps its attached devices
        from repro.compat import force_host_device_count

        force_host_device_count(args.m)

    import numpy as np
    import jax
    from repro.compat import setup_compilation_cache
    from repro.core import DGPConfig, DistributedGP
    from repro.analysis import check_contracts
    from repro.core.protocols import serve_trace_count

    setup_compilation_cache()
    fusion = args.fusion
    if fusion is None:
        fusion = "rbcm" if args.protocol == "poe" else "kl"
    chaos = _parse_chaos(args.chaos) if args.chaos else None
    cfg = DGPConfig(
        protocol=args.protocol,
        scheme=args.scheme,
        fusion=fusion,
        impl="mesh" if args.mesh else "batched",
        gram_backend=args.gram_backend,
        gram_mode="dense" if args.protocol == "poe" else args.gram_mode,
        bits_per_sample=0 if args.protocol == "poe" else args.bits,
        steps=args.steps,
        faults=chaos,
    )
    est = DistributedGP(cfg)
    if chaos is not None:
        print(f"chaos: {chaos}")

    rng = np.random.default_rng(0)
    W = rng.normal(size=(args.d, 2))
    f = lambda Z: np.sin(Z @ W[:, 0]) + 0.4 * (Z @ W[:, 1])
    X = rng.normal(size=(args.n, args.d)).astype(np.float32)
    y = (f(X) + 0.05 * rng.normal(size=args.n)).astype(np.float32)

    t0 = time.perf_counter()
    art = _retry("fit", lambda: est.fit(X, y, args.m, key=jax.random.PRNGKey(0)),
                 attempts=args.retries)
    t_fit = time.perf_counter() - t0
    print(f"fit: protocol={cfg.protocol} scheme={cfg.scheme} impl={art.impl} "
          f"m={args.m} n={args.n} d={args.d} "
          f"R={cfg.bits_per_sample} -> {t_fit:.2f}s, "
          f"wire {art.wire_bits/1e3:.1f} kbit "
          f"(packed payload {art.payload_bits/1e3:.1f} kbit, "
          f"crc {art.integrity_bits/1e3:.1f} kbit, "
          f"{art.rows_demoted} rows demoted)")

    if args.artifact_dir:
        path = est.save(art, args.artifact_dir)
        if args.mesh:
            # the checkpoint round-trips to a single-host artifact; keep
            # serving the sharded mesh copy, but verify the round trip
            loaded = _retry("load", lambda: est.load(args.artifact_dir),
                            attempts=args.retries)
            Xv = rng.normal(size=(8, args.d)).astype(np.float32)
            dmu = float(np.max(np.abs(np.asarray(est.predict(art, Xv)[0])
                                      - np.asarray(est.predict(loaded, Xv)[0]))))
            if not np.isfinite(dmu) or dmu > 1e-4:
                print(f"FATAL: single-host reload of {path} diverges from the "
                      f"mesh artifact (max |dmu| = {dmu:.3e} > 1e-4) — "
                      "refusing to serve", file=sys.stderr)
                sys.exit(1)
            print(f"artifact: saved {path}; single-host reload agrees to "
                  f"{dmu:.1e} (serving the sharded mesh copy); recorded "
                  f"config: {loaded.config.protocol}/{loaded.config.scheme}")
        else:
            art = _retry("load", lambda: est.load(args.artifact_dir),
                         attempts=args.retries)
            print(f"artifact: saved+reloaded {path} (serving the loaded copy)")

    # degraded-mode serving under chaos: every 7th batch drops the chaos
    # plan's machines (or the last machine when the plan names none) and the
    # fusion renormalizes over survivors
    degraded_avail = None
    if chaos is not None and args.protocol in ("broadcast", "poe"):
        lost = set(chaos.drop) or {args.m - 1}
        degraded_avail = np.asarray(
            [0.0 if j in lost else 1.0 for j in range(args.m)], np.float32
        )
        h = est.health(art, degraded_avail)
        print(f"health (degraded mask): status={h.status} "
              f"lost={list(h.machines_lost)} demoted={h.rows_demoted} "
              f"var_inflation={h.variance_inflation:.2f}")
    stragglers = dict(chaos.straggle) if chaos is not None else {}

    if args.fleet:
        _run_fleet(args, art, degraded_avail, rng)
        return

    lat, machine, n_updates = [], 1 % args.m, 0
    n_over = 0  # requests over the --timeout-ms budget
    c0 = None  # trace-count snapshot taken after the first (tracing) batch
    for q in range(args.queries):
        Xq = rng.normal(size=(args.batch, args.d)).astype(np.float32)
        if stragglers and (q % args.m) in stragglers:
            # a straggler holds up its slot of the serve rotation
            time.sleep(stragglers[q % args.m])
        t0 = time.perf_counter()
        if degraded_avail is not None and (q + 1) % 7 == 0:
            mu, var = est.predict(art, Xq, available=degraded_avail)
        else:
            mu, var = est.predict(art, Xq)
        jax.block_until_ready(mu)
        dt = time.perf_counter() - t0
        lat.append(dt)
        if args.timeout_ms and dt * 1e3 > args.timeout_ms and q > 0:
            n_over += 1
        if c0 is None:
            c0 = serve_trace_count(args.protocol)
        if args.stream_every and (q + 1) % args.stream_every == 0:
            Xn = rng.normal(size=(args.stream_size, args.d)).astype(np.float32)
            yn = (f(Xn) + 0.05 * rng.normal(size=args.stream_size)).astype(np.float32)
            t0 = time.perf_counter()
            art = est.update(art, Xn, yn, machine=machine)
            # a growth only retraces the NEXT predict; the last batch's
            # update is never served in this loop
            n_updates += 1 if q + 1 < args.queries else 0
            print(f"  [q{q+1}] streamed {args.stream_size} pts -> machine "
                  f"{machine} in {time.perf_counter()-t0:.3f}s "
                  f"(ledger {art.wire_bits/1e3:.1f} kbit)")

    # contract check is trace-neutral (repro.analysis), so it can run before
    # the retrace delta is read — no snapshot-ordering fragility to maintain
    report = check_contracts(
        art, rng.normal(size=(args.batch, args.d)).astype(np.float32),
        raise_on_violation=False,
    )
    retraces = serve_trace_count(args.protocol) - c0
    lat_ms = np.asarray(lat[1:]) * 1e3  # drop the first (trace) batch
    print(f"serve: {args.queries} batches x {args.batch} pts | warm p50 "
          f"{np.percentile(lat_ms, 50):.2f} ms, p99 {np.percentile(lat_ms, 99):.2f} ms"
          f" | {args.batch/ (np.median(lat_ms)/1e3):.0f} queries/s")
    if args.timeout_ms:
        print(f"timeout budget: {n_over}/{args.queries - 1} warm requests over "
              f"{args.timeout_ms:.0f} ms")
    ops = report.op_counts
    n_coll = sum(v["count"] for v in report.collectives.values())
    print(f"warm path: retraces={retraces} (expected {n_updates}, one per "
          f"streamed growth) cholesky_eqns={ops.get('cholesky', 0)} "
          f"eigh_eqns={ops.get('eigh', 0)} collectives={n_coll} "
          f"contract={report.contract}:{'ok' if report.ok else 'VIOLATED'}")
    if not report.ok:
        for finding in report.findings:
            print(f"contract violation: {finding}", file=sys.stderr)
        sys.exit(1)


if __name__ == "__main__":
    main()
