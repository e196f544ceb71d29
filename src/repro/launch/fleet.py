"""Fleet serving driver: many tenants, one stacked predict program.

  python -m repro.launch.fleet --tenants 64 --protocol broadcast \
      --gram-backend pallas --cache 32 --budget-ms 2 --slots 8 \
      --requests 400 --batch 16 --zipf 1.1 [--store-dir /tmp/fleet_store]

The pieces (design notes in docs/fleet_serving.md):

* :class:`MicroBatcher` — coalesces per-tenant queries into stacked
  micro-batches under a latency budget: a batch flushes when its ``slots``
  fill OR when the oldest queued request has waited ``budget_ms`` (whichever
  first).  The clock is injectable so tests drive deadlines without
  sleeping.
* :class:`FleetServer` — the serving loop's state: an
  :class:`~repro.core.fleet.ArtifactCache` (LRU, checkpoint-backed
  load-on-miss), one :class:`~repro.core.fleet.FleetStack` per homogeneity
  bucket, and the batcher.  ``submit()`` enqueues; a flush groups the batch
  by bucket, pads each group to the fixed flush width (repeating the first
  row, results sliced off — so the jitted program sees ONE batch shape and
  the steady state never retraces), and answers every tenant in one
  dispatch per bucket.
* :func:`build_fleet` / :func:`serve_loop` — shared by this CLI, the
  ``serve_gp.py --fleet`` passthrough, and benchmarks/fleet_bench.py: build
  a tenant store from a handful of base fits (exact y-scaled variants, see
  :func:`~repro.core.fleet.scale_targets`) and drive zipf-mixed traffic
  against the server, reporting qps / p50 / p99 / hit rate / retraces.
"""
from __future__ import annotations

import argparse
import dataclasses
import time

import numpy as np


@dataclasses.dataclass
class _Pending:
    tenant: object
    X: object
    avail: object
    enqueued_at: float


class MicroBatcher:
    """Coalesce per-tenant requests into fixed-width micro-batches under a
    deadline: flush on ``slots`` full or on the oldest request aging past
    ``budget_ms``.  ``clock`` is injectable (seconds, monotonic) so tests
    exercise the deadline without sleeping."""

    def __init__(self, slots: int = 8, budget_ms: float = 2.0,
                 clock=time.monotonic):
        if slots < 1:
            raise ValueError("MicroBatcher: slots must be >= 1")
        self.slots = int(slots)
        self.budget_ms = float(budget_ms)
        self.clock = clock
        self._queue: list[_Pending] = []

    def __len__(self) -> int:
        return len(self._queue)

    def add(self, tenant, X, avail=None):
        """Enqueue one request; returns the flushed batch when this request
        fills the last slot, else None."""
        self._queue.append(_Pending(tenant, X, avail, self.clock()))
        if len(self._queue) >= self.slots:
            return self.flush()
        return None

    def due(self) -> bool:
        """True when the oldest queued request has exhausted the budget."""
        if not self._queue:
            return False
        age_ms = (self.clock() - self._queue[0].enqueued_at) * 1e3
        return age_ms >= self.budget_ms

    def flush(self) -> list:
        """Drain the queue (flush on budget: callers poll :meth:`due`)."""
        batch, self._queue = self._queue, []
        return batch


class FleetServer:
    """Multi-tenant GP serving: LRU artifact cache over a checkpoint store,
    device-resident :class:`~repro.core.fleet.FleetStack` per bucket, and
    latency-budgeted micro-batching in front.

    ``store`` is an :class:`~repro.core.fleet.ArtifactStore` (or any object
    with ``load(tenant)``); ``stack_slots`` fixes each stack's resident rows
    (default 2x the flush width, so a working set larger than one batch
    stays resident)."""

    def __init__(self, store, cache_artifacts: int | None = 64,
                 cache_bytes: int | None = None, slots: int = 8,
                 budget_ms: float = 2.0, stack_slots: int | None = None,
                 clock=time.monotonic):
        from repro.core.fleet import ArtifactCache

        self.store = store
        self.cache = ArtifactCache(store.load, capacity=cache_artifacts,
                                   capacity_bytes=cache_bytes)
        self.batcher = MicroBatcher(slots=slots, budget_ms=budget_ms,
                                    clock=clock)
        self.stack_slots = int(stack_slots) if stack_slots else 2 * int(slots)
        if self.stack_slots < int(slots):
            raise ValueError(
                f"FleetServer: stack_slots ({self.stack_slots}) must cover a "
                f"full flush width ({slots}) or a batch could evict its own "
                "members"
            )
        self.clock = clock
        self._stacks: dict = {}
        self.flushes = 0
        self.latencies_ms: list[float] = []

    # -- residency ---------------------------------------------------------

    def _resident(self, tenant):
        """(stack, art) with ``tenant`` resident — cache hit/miss and stack
        admit happen here, off the per-request hot path."""
        from repro.core.fleet import FleetStack, bucket_key

        art = self.cache.get(tenant)
        key = bucket_key(art)
        stack = self._stacks.get(key)
        if stack is None:
            stack = FleetStack({tenant: art}, slots=self.stack_slots)
            self._stacks[key] = stack
        elif tenant not in stack:
            stack.admit(tenant, art)
        else:
            # refresh recency so a later admit in this SAME batch can never
            # evict a tenant that is about to be co-batched
            stack.touch(tenant)
        return stack

    def stacks(self) -> list:
        return list(self._stacks.values())

    # -- request plane -----------------------------------------------------

    def submit(self, tenant, X, avail=None) -> list:
        """Enqueue one request; returns completed ``(tenant, mu, var,
        latency_ms)`` tuples when this submit triggered a flush (slots
        full), else []."""
        batch = self.batcher.add(tenant, X, avail)
        return self._serve(batch) if batch else []

    def poll(self) -> list:
        """Flush on deadline: serve the queue iff the oldest request has
        exhausted the latency budget."""
        if self.batcher.due():
            return self._serve(self.batcher.flush())
        return []

    def drain(self) -> list:
        """Serve whatever is queued regardless of deadline (shutdown)."""
        if len(self.batcher):
            return self._serve(self.batcher.flush())
        return []

    def _serve(self, batch) -> list:
        """Answer one flushed micro-batch: group by bucket, pad each group
        to the fixed flush width, ONE stacked dispatch per bucket."""
        import jax

        self.flushes += 1
        groups: dict = {}
        for req in batch:
            stack = self._resident(req.tenant)
            groups.setdefault(id(stack), (stack, []))[1].append(req)
        out = []
        width = self.batcher.slots
        for stack, reqs in groups.values():
            S = len(reqs)
            tids = [r.tenant for r in reqs]
            Xq = np.stack([np.asarray(r.X, np.float32) for r in reqs])
            avail = None
            if any(r.avail is not None for r in reqs):
                m = len(stack.tree.fit_lengths)
                avail = np.ones((S, m), np.float32)
                for s, r in enumerate(reqs):
                    if r.avail is not None:
                        avail[s] = np.asarray(r.avail, np.float32)
            if S < width:
                # pad to the flush width by repeating row 0: the jitted
                # program sees ONE (width, t, d) shape for every flush, so a
                # ragged tail batch never retraces; padded rows are sliced
                # off before anyone sees them
                reps = width - S
                tids = tids + [tids[0]] * reps
                Xq = np.concatenate([Xq, np.repeat(Xq[:1], reps, 0)])
                if avail is not None:
                    avail = np.concatenate(
                        [avail, np.repeat(avail[:1], reps, 0)]
                    )
            mu, var = stack.predict(tids, Xq, avail)
            jax.block_until_ready(mu)
            done = self.clock()
            for s, r in enumerate(reqs):
                lat = (done - r.enqueued_at) * 1e3
                self.latencies_ms.append(lat)
                out.append((r.tenant, mu[s], var[s], lat))
        return out

    def reset_stats(self) -> None:
        """Zero the latency/flush counters (called between the warm pass and
        the measured steady state so compile latency never pollutes p99)."""
        self.flushes = 0
        self.latencies_ms = []

    def stats(self) -> dict:
        lat = np.asarray(self.latencies_ms) if self.latencies_ms else \
            np.zeros(1)
        return {
            "flushes": self.flushes,
            "requests": len(self.latencies_ms),
            "p50_ms": float(np.percentile(lat, 50)),
            "p99_ms": float(np.percentile(lat, 99)),
            "cache": self.cache.stats(),
            "stacks": len(self._stacks),
            "stack_swaps": sum(s.swaps for s in self._stacks.values()),
        }


# --------------------------------------------------------------------------
# fleet construction + traffic loop (CLI, serve_gp --fleet, fleet_bench)
# --------------------------------------------------------------------------


def build_fleet(base_arts, n_tenants: int, store_dir: str):
    """Populate an :class:`~repro.core.fleet.ArtifactStore` with
    ``n_tenants`` artifacts derived from a handful of base fits: tenant i is
    an EXACT y-scaled variant (:func:`~repro.core.fleet.scale_targets`) of
    ``base_arts[i % len(base_arts)]`` — genuinely distinct posteriors, same
    bucket, no per-tenant fit cost.  Returns ``(store, tenant_ids)``;
    tenant ids are zero-padded strings so directory listings sort."""
    from repro.core.fleet import ArtifactStore, scale_targets

    store = ArtifactStore(store_dir)
    width = max(4, len(str(n_tenants - 1)))
    tids = []
    for i in range(n_tenants):
        c = 0.25 + 1.5 * ((i * 2654435761) % 1000) / 1000.0  # spread scales
        art_i = scale_targets(base_arts[i % len(base_arts)], c)
        tid = str(i).zfill(width)
        store.save(tid, art_i)
        tids.append(tid)
    return store, tids


def zipf_tenants(tids, n_requests: int, a: float = 1.1, seed: int = 0):
    """A zipf-mixed request stream over the tenant ids: tenant popularity
    p(rank) ∝ 1/rank^a — a few hot tenants dominate, a long cold tail
    exercises cache misses and stack swaps."""
    rng = np.random.default_rng(seed)
    ranks = np.arange(1, len(tids) + 1, dtype=np.float64)
    p = ranks ** (-float(a))
    p /= p.sum()
    order = rng.permutation(len(tids))  # popularity decoupled from id order
    return [tids[order[i]] for i in
            rng.choice(len(tids), size=n_requests, p=p)]


def serve_loop(server: FleetServer, tenant_stream, make_query,
               degraded_every: int = 0, degraded_avail=None) -> dict:
    """Drive a request stream through the server: submit every request,
    poll the deadline between submits, drain at the end.  Every
    ``degraded_every``-th flush-width block tags ONE tenant's request with
    the ``degraded_avail`` mask (per-tenant degraded-mode serving: chaos for
    one tenant must not perturb its co-batched neighbors — tests lock this).
    Returns the server's stats plus the completed-request count."""
    done = 0
    for i, tid in enumerate(tenant_stream):
        avail = None
        if degraded_every and degraded_avail is not None \
                and i % (degraded_every * server.batcher.slots) == 0:
            avail = degraded_avail
        done += len(server.submit(tid, make_query(i), avail))
        done += len(server.poll())
    done += len(server.drain())
    stats = server.stats()
    stats["completed"] = done
    return stats


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--protocol", default="broadcast",
                    choices=["center", "broadcast", "poe"])
    ap.add_argument("--gram-backend", default="pallas",
                    choices=["xla", "pallas"],
                    help="pallas routes broadcast serving through the "
                         "tenant-batched fused epilogue")
    ap.add_argument("--tenants", type=int, default=64)
    ap.add_argument("--base-fits", type=int, default=2,
                    help="distinct fits; tenants are exact y-scaled variants")
    ap.add_argument("--m", type=int, default=4, help="machines per tenant")
    ap.add_argument("--n", type=int, default=256, help="points per tenant fit")
    ap.add_argument("--d", type=int, default=6)
    ap.add_argument("--bits", type=int, default=8)
    ap.add_argument("--steps", type=int, default=10)
    ap.add_argument("--cache", type=int, default=32,
                    help="artifact cache capacity (count)")
    ap.add_argument("--cache-bytes", type=int, default=0,
                    help="artifact cache capacity in bytes (0 = unbounded)")
    ap.add_argument("--slots", type=int, default=8,
                    help="micro-batch flush width")
    ap.add_argument("--stack-slots", type=int, default=0,
                    help="resident stack rows (0 = 2x slots)")
    ap.add_argument("--budget-ms", type=float, default=2.0)
    ap.add_argument("--requests", type=int, default=400)
    ap.add_argument("--batch", type=int, default=16,
                    help="query points per request")
    ap.add_argument("--zipf", type=float, default=1.1)
    ap.add_argument("--store-dir", default=None,
                    help="tenant checkpoint store (default: a temp dir)")
    args = ap.parse_args()

    import tempfile

    import jax
    from repro.compat import setup_compilation_cache
    from repro.core import DGPConfig, DistributedGP
    from repro.core.fleet import fleet_trace_count
    from repro.core.protocols import serve_trace_count

    setup_compilation_cache()
    cfg = DGPConfig(
        protocol=args.protocol,
        gram_backend=args.gram_backend,
        gram_mode="dense" if args.protocol == "poe" else "nystrom",
        bits_per_sample=0 if args.protocol == "poe" else args.bits,
        steps=args.steps,
    )
    est = DistributedGP(cfg)
    rng = np.random.default_rng(0)
    W = rng.normal(size=(args.d, 2))
    f = lambda Z: np.sin(Z @ W[:, 0]) + 0.4 * (Z @ W[:, 1])

    t0 = time.perf_counter()
    base_arts = []
    for b in range(args.base_fits):
        X = rng.normal(size=(args.n, args.d)).astype(np.float32)
        y = (f(X) + 0.05 * rng.normal(size=args.n)).astype(np.float32)
        base_arts.append(est.fit(X, y, args.m, key=jax.random.PRNGKey(b)))
    print(f"fit {args.base_fits} base artifact(s) in "
          f"{time.perf_counter() - t0:.2f}s")

    with tempfile.TemporaryDirectory() as td:
        store_dir = args.store_dir or td
        t0 = time.perf_counter()
        store, tids = build_fleet(base_arts, args.tenants, store_dir)
        print(f"stored {len(tids)} tenant artifacts under {store_dir} in "
              f"{time.perf_counter() - t0:.2f}s")
        server = FleetServer(
            store, cache_artifacts=args.cache,
            cache_bytes=args.cache_bytes or None, slots=args.slots,
            budget_ms=args.budget_ms,
            stack_slots=args.stack_slots or None,
        )
        stream = zipf_tenants(tids, args.requests, a=args.zipf)
        make_query = lambda i: rng.normal(
            size=(args.batch, args.d)
        ).astype(np.float32)
        # warm pass traces the per-bucket programs; the measured steady
        # state must then hold every trace counter flat
        serve_loop(server, stream[: 4 * args.slots], make_query)
        server.reset_stats()
        c0 = fleet_trace_count(args.protocol)
        s0 = serve_trace_count(args.protocol)
        t0 = time.perf_counter()
        stats = serve_loop(server, stream, make_query)
        wall = time.perf_counter() - t0
        retraces = (fleet_trace_count(args.protocol) - c0) + \
            (serve_trace_count(args.protocol) - s0)
        qps = args.requests * args.batch / wall
        print(f"served {stats['completed']} requests x {args.batch} pts in "
              f"{wall:.2f}s -> {qps:.0f} q/s aggregate")
        print(f"latency p50 {stats['p50_ms']:.2f} ms  p99 "
              f"{stats['p99_ms']:.2f} ms  (budget {args.budget_ms} ms, "
              f"flush width {args.slots})")
        c = stats["cache"]
        print(f"cache: {c['hits']} hits / {c['misses']} misses "
              f"(rate {c['hit_rate']:.2f}), {c['evictions']} evictions; "
              f"stacks: {stats['stacks']} bucket(s), "
              f"{stats['stack_swaps']} tenant swaps")
        print(f"steady-state retraces: {retraces}")
        if retraces:
            raise SystemExit("FATAL: steady-state fleet loop retraced")


if __name__ == "__main__":
    main()
