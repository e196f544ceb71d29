#!/usr/bin/env python3
"""Bring-up smoke run of the distributed-GP system on one TPU chip.

    python3 chip_smoke.py [--seed S]        # one chip: phases (a)-(d)
    python3 chip_smoke.py --four-chips      # four chips: impl="mesh" at m=4

The deployment is the paper's §6 setup: m=40 machines, SE kernel, the
per-symbol wire at R=24 bits/sample and the Nyström training gram.  The data
has the kin40k input shape (d=8) with the dataset's full 10,000 training
points, an assumed size (the paper trains on 1,000), generated from --seed.

Each phase runs through the public API (``DistributedGP``, ``FleetServer``)
and checks its own result:

  (a) center,    gram_backend="pallas": fit, save/load, 8 warm predict
      batches of 128 points, one streamed update of 16 points, predict again;
  (b) broadcast, fusion kl, gram_backend="pallas": the same steps, served
      through the fused ``epilogue`` kernel;
  (c) poe,       fusion rbcm: the zero-rate baseline, the same steps;
  (d) fleet:     16 y-scaled tenants of (b) behind ``FleetServer`` for a few
      flushes, served through the tenant-batched ``epilogue_fleet`` kernel.

(a) and (b) are refit under gram_backend="xla" at the hyperparameters the
pallas fit trained, and must agree with it (see ``AGREE_REL``).

``--four-chips`` runs only center and broadcast at m=4 under impl="mesh"
(machines are chips, ``q_all_gather`` is the wire) against impl="batched" on
the same data, 250 points per machine as in the one-chip deployment: the
wire, payload and integrity ledgers must be integer-equal and the
predictions must agree (see ``MESH_AGREE_REL``).

The numbers printed per phase are smoke output, not benchmark measurements.
The last line of stdout is one JSON verdict; a run with any failed phase, or
one that finds no TPU, exits 1 without it.  Nothing falls back to the CPU.
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import os
import sys
import tempfile
import time
import traceback

HERE = os.path.dirname(os.path.abspath(__file__))

M = 40             # machines (paper §6)
BITS = 24          # R, wire bits per sample
DATASET = "kin40k"  # d=8 input shape (repro.data.synthetic.DATASET_SPECS)
N_TRAIN = 10_000   # kin40k's full training size (assumed; the paper uses 1,000)
BATCH = 128        # query points per predict batch
N_BATCHES = 8      # warm predict batches per phase
N_STREAM = 16      # points in the streamed update
TENANTS = 16       # fleet tenants, y-scaled variants of the (b) fit
FLEET_SLOTS = 4    # fleet flush width; stacks hold 2x that many tenants
MESH_M = 4         # machines (= chips) of the --four-chips phase
# each chip holds one machine's shard of the one-chip deployment (250 points),
# so the four-chip phase trains on 4 x 250 = 1,000 points: the paper's n_train
MESH_N_TRAIN = MESH_M * N_TRAIN // M

# pallas vs xla at identical hyperparameters: both run every matmul at full
# float32 precision, so they differ only by summation order inside the
# inner products (~1e-7 relative), amplified by the Nyström solves' condition
# number.  A kernel fault (a wrong block, a dropped tile, a wrong fusion row)
# moves predictions by the order of the target scale, so agreement is asked
# to 1% of std(y) for means and 1% of the prior variance for variances.
AGREE_REL = 1e-2
# mesh vs batched are the same float32 math placed differently (collectives
# in place of a vmapped gather); the same bound applies
MESH_AGREE_REL = 1e-2


def _fail(msg: str) -> None:
    print(f"chip_smoke: {msg}", file=sys.stderr)
    sys.exit(1)


def _import_repro():
    """The repro package of THIS checkout (src/ beside this file), never an
    installed copy, so the script alone in a directory fails."""
    src = os.path.join(HERE, "src")
    if not os.path.isfile(os.path.join(src, "repro", "__init__.py")):
        _fail(f"no repro package at {src}: run this script from a checkout "
              "of the repository")
    sys.path.insert(0, src)
    import repro

    if not os.path.abspath(repro.__file__).startswith(src + os.sep):
        _fail(f"imported repro from {repro.__file__}, not from {src}")
    return repro


def _require_tpu(count: int):
    import jax

    try:
        devs = jax.devices()
    except RuntimeError as e:
        _fail(f"no TPU found: JAX could not initialize a backend ({e})")
    if devs[0].platform != "tpu":
        _fail(f"no TPU found: JAX sees {len(devs)} {devs[0].platform} "
              "device(s); this smoke run needs a TPU and does not fall back "
              "to the CPU")
    if len(devs) < count:
        _fail(f"needs {count} TPU chips, JAX sees {len(devs)}")
    return devs


class CacheCounter:
    """Counts JAX persistent-compilation-cache hits and misses."""

    def __init__(self):
        import jax

        self.hits = self.misses = 0
        jax.monitoring.register_event_listener(self._on_event)

    def _on_event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1


def _peak_bytes(dev) -> int | None:
    stats = dev.memory_stats() or {}
    return stats.get("peak_bytes_in_use")


def _program_census(fn, *args) -> dict:
    """What a program really runs, from its jaxpr: the matmul precisions of
    every dot_general (Pallas kernel bodies included) and the interpret flag
    of every Pallas call."""
    import jax
    from repro.analysis.jaxpr_walk import walk_jaxpr

    prec, pallas = {}, []
    for eqn in walk_jaxpr(jax.make_jaxpr(fn)(*args)):
        name = eqn.primitive.name
        if name == "dot_general":
            p = eqn.params.get("precision")
            key = "DEFAULT" if p is None else "/".join(
                sorted({str(x).split(".")[-1] for x in p}))
            prec[key] = prec.get(key, 0) + 1
        elif name == "pallas_call":
            pallas.append(bool(eqn.params.get("interpret")))
    return {"dot_precision": prec, "pallas_calls": len(pallas),
            "pallas_interpret": sorted(set(pallas))}


def _check(cond: bool, what: str) -> None:
    if not cond:
        raise AssertionError(what)


def _finite_pred(mu, var, t: int, what: str) -> None:
    import numpy as np

    mu, var = np.asarray(mu), np.asarray(var)
    _check(mu.shape == (t,) and var.shape == (t,),
           f"{what}: prediction shapes {mu.shape}/{var.shape}, want ({t},)")
    _check(bool(np.isfinite(mu).all() and np.isfinite(var).all()),
           f"{what}: non-finite prediction")
    _check(bool((var > 0).all()), f"{what}: non-positive predictive variance")


def _block(tree):
    import jax

    jax.block_until_ready(jax.tree_util.tree_leaves(tree))
    return tree


@dataclasses.dataclass
class Data:
    X: object
    y: object
    queries: list        # N_BATCHES arrays (BATCH, d)
    y_queries: object    # (N_BATCHES * BATCH,)
    X_stream: object     # (N_STREAM, d)
    y_stream: object


def make_data(seed: int, n_train: int = N_TRAIN) -> Data:
    from repro.data import regression_dataset

    X, y, Xt, yt = regression_dataset(DATASET, seed=seed, n_train=n_train)
    nq = N_BATCHES * BATCH
    queries = [Xt[i * BATCH:(i + 1) * BATCH] for i in range(N_BATCHES)]
    return Data(X, y, queries, yt[:nq], Xt[nq:nq + N_STREAM],
                yt[nq:nq + N_STREAM])


def _predict_all(est, art, queries):
    """(mu, var) over every query batch, concatenated on the host."""
    import numpy as np

    preds = [est.predict(art, q) for q in queries]
    return (np.concatenate([np.asarray(mu) for mu, _ in preds]),
            np.concatenate([np.asarray(var) for _, var in preds]))


def run_protocol_phase(label: str, cfg, data: Data, seed: int, dev,
                       compare_xla: bool = False):
    """fit -> save/load -> warm predicts -> streamed update -> predict, with
    its checks; returns (artifact as loaded, summary line dict)."""
    import jax
    import numpy as np
    from repro.core import DistributedGP
    from repro.kernels import runtime

    est = DistributedGP(cfg)
    key = jax.random.PRNGKey(seed)
    t0 = time.perf_counter()
    art = _block(est.fit(data.X, data.y, M, key=key))
    t_fit = time.perf_counter() - t0

    with tempfile.TemporaryDirectory() as td:
        est.save(art, td)
        loaded = _block(est.load(td))
    q0 = data.queries[0]
    mu0, var0 = est.predict(art, q0)
    mu1, var1 = est.predict(loaded, q0)
    _check(np.array_equal(np.asarray(mu0), np.asarray(mu1))
           and np.array_equal(np.asarray(var0), np.asarray(var1)),
           f"{label}: save/load changed the predictions")
    art = loaded

    lat, mus, vars_ = [], [], []
    for q in data.queries:
        t0 = time.perf_counter()
        mu, var = est.predict(art, q)
        jax.block_until_ready((mu, var))
        lat.append(time.perf_counter() - t0)
        _finite_pred(mu, var, BATCH, f"{label} predict")
        mus.append(np.asarray(mu))
        vars_.append(np.asarray(var))
    mu_all, var_all = np.concatenate(mus), np.concatenate(vars_)
    yq = np.asarray(data.y_queries)
    smse = float(np.mean((mu_all - yq) ** 2) / np.var(yq))
    _check(smse < 1.0, f"{label}: SMSE {smse} does not beat the mean")

    machine = 1  # a transmitting machine under every protocol
    art2 = _block(est.update(art, data.X_stream, data.y_stream,
                             machine=machine))
    if cfg.protocol == "poe":
        _check(art2.wire_bits == art.wire_bits == 0,
               f"{label}: zero-rate ledger moved")
    else:
        rate = int(np.asarray(art.wire.rates[machine]).sum())
        _check(art2.wire_bits == art.wire_bits + N_STREAM * rate,
               f"{label}: update charged {art2.wire_bits - art.wire_bits} "
               f"bits, want {N_STREAM * rate}")
    mu2, var2 = est.predict(art2, q0)
    _finite_pred(mu2, var2, BATCH, f"{label} predict after update")

    census = _program_census(lambda x: est.predict(art, x), q0)
    if cfg.gram_backend == "pallas":
        _check(census["pallas_calls"] > 0 and
               census["pallas_interpret"] == [False],
               f"{label}: predict program does not run compiled Pallas "
               f"({census})")
    _check(set(census["dot_precision"]) == {"HIGHEST"},
           f"{label}: predict matmuls not all at HIGHEST ({census})")

    out = {
        "phase": label, "device_kind": dev.device_kind,
        "cold_fit_s": t_fit,
        "warm_predict_p50_ms": float(np.median(lat) * 1e3),
        "smse": smse, "peak_bytes_in_use": _peak_bytes(dev),
        "choose": str(runtime.choose()),
        "matmul_precision": census["dot_precision"],
        "pallas_calls": census["pallas_calls"],
        "pallas_interpret": census["pallas_interpret"],
        "wire_bits": int(art.wire_bits),
    }
    if compare_xla:
        est_x = DistributedGP(
            dataclasses.replace(cfg, gram_backend="xla", steps=0))
        art_x = _block(est_x.fit(data.X, data.y, M, key=key,
                                 params=art.params))
        mu_x, var_x = _predict_all(est_x, art_x, data.queries)
        dmu = float(np.max(np.abs(mu_all - mu_x)))
        dvar = float(np.max(np.abs(var_all - var_x)))
        prior = float(np.exp(np.asarray(art.params.log_a)))
        out.update(xla_max_abs_dmu=dmu, xla_max_abs_dvar=dvar,
                   mu_tol=AGREE_REL * float(np.std(yq)),
                   var_tol=AGREE_REL * prior)
        _check(art_x.wire_bits == art.wire_bits,
               f"{label}: xla ledger {art_x.wire_bits} != {art.wire_bits}")
        _check(dmu <= out["mu_tol"] and dvar <= out["var_tol"],
               f"{label}: pallas vs xla disagree (max |dmu| {dmu}, max "
               f"|dvar| {dvar}; tolerances {out['mu_tol']}, "
               f"{out['var_tol']})")
        del art_x
    del art2
    return art, out


class ScaledTenants:
    """An in-memory tenant store for FleetServer: tenant i is the EXACT
    y-scaled variant of one fitted artifact (``scale_targets``), built on
    load, so the tenants share every y-independent leaf instead of holding
    16 copies of the factor set on disk."""

    def __init__(self, art, n: int):
        self.art = art
        self.scale = {f"t{i:02d}": 0.25 + 1.5 * i / max(n - 1, 1)
                      for i in range(n)}

    def load(self, tenant):
        from repro.core.fleet import scale_targets

        return scale_targets(self.art, self.scale[tenant])


def run_fleet_phase(art_b, cfg, data: Data, dev):
    import jax
    import numpy as np
    from repro.core import DistributedGP
    from repro.core.fleet import fleet_trace_count, scale_targets
    from repro.kernels import runtime
    from repro.launch.fleet import FleetServer

    store = ScaledTenants(art_b, TENANTS)
    server = FleetServer(store, cache_artifacts=TENANTS, slots=FLEET_SLOTS,
                         budget_ms=1e9)
    tids = list(store.scale)
    stream = tids + tids[: TENANTS // 2]  # a second round re-admits evictees
    pending, answers, traces = [], [], []
    t0 = time.perf_counter()
    for i, tid in enumerate(stream):
        pending.append(i % N_BATCHES)
        got = server.submit(tid, data.queries[i % N_BATCHES])
        if got:
            traces.append(fleet_trace_count("broadcast"))
        # a flush answers its requests in the order they were submitted
        answers += [(t, pending.pop(0), mu, var) for t, mu, var, _ in got]
    answers += [(t, pending.pop(0), mu, var)
                for t, mu, var, _ in server.drain()]
    t_serve = time.perf_counter() - t0
    _check(len(answers) == len(stream),
           f"fleet: {len(answers)} answers for {len(stream)} requests")
    for _, _, mu, var in answers:
        _finite_pred(mu, var, BATCH, "fleet predict")
    # after the first flush compiled the stacked program, swapping tenants
    # in and out of the stack must not retrace it
    _check(traces[-1] == traces[0],
           f"fleet: stacked program retraced {traces[-1] - traces[0]} times")

    # every answer of two tenants equals that tenant's single-artifact serve
    est = DistributedGP(cfg)
    yq = np.asarray(data.y_queries)
    prior = float(np.exp(np.asarray(art_b.params.log_a)))
    dmu = dvar = 0.0
    for tid in (tids[0], tids[-1]):
        ref = scale_targets(art_b, store.scale[tid])
        for t, qi, mu, var in answers:
            if t != tid:
                continue
            mu_r, var_r = est.predict(ref, data.queries[qi])
            dmu = max(dmu, float(np.max(np.abs(np.asarray(mu) - mu_r))))
            dvar = max(dvar, float(np.max(np.abs(np.asarray(var) - var_r))))
    mu_tol = AGREE_REL * float(np.std(yq)) * max(store.scale.values())
    _check(dmu <= mu_tol and dvar <= AGREE_REL * prior,
           f"fleet: stacked serve disagrees with single-tenant serve (max "
           f"|dmu| {dmu}, max |dvar| {dvar})")

    stack = server.stacks()[0]
    census = _program_census(
        lambda x: stack.predict(stack.tenants()[:FLEET_SLOTS], x),
        np.stack(data.queries[:FLEET_SLOTS]),
    )
    _check(census["pallas_calls"] > 0 and census["pallas_interpret"] == [False],
           f"fleet: stacked program does not run compiled Pallas ({census})")
    stats = server.stats()
    return {
        "phase": "d:fleet", "device_kind": dev.device_kind,
        "requests": len(stream), "flushes": stats["flushes"],
        "serve_s": t_serve, "latency_p50_ms": stats["p50_ms"],
        "cache": stats["cache"], "stack_swaps": stats["stack_swaps"],
        "peak_bytes_in_use": _peak_bytes(dev),
        "choose": str(runtime.choose()),
        "matmul_precision": census["dot_precision"],
        "pallas_calls": census["pallas_calls"],
        "pallas_interpret": census["pallas_interpret"],
        "max_abs_dmu_vs_single": dmu, "max_abs_dvar_vs_single": dvar,
    }


def run_mesh_phase(data: Data, seed: int, devs):
    """center and broadcast at m=MESH_M: impl="mesh" against "batched"."""
    import jax
    import numpy as np
    from repro.core import DGPConfig, DistributedGP
    from repro.core.protocols import mesh

    msh = mesh.machine_mesh(MESH_M)
    _check(set(msh.devices.flat) == set(devs[:MESH_M]),
           f"machine mesh spans {msh.devices}, want {devs[:MESH_M]}")
    key = jax.random.PRNGKey(seed)
    yq = np.asarray(data.y_queries)
    rows = []
    for protocol in ("center", "broadcast"):
        cfg_b = DGPConfig(protocol=protocol, fusion="kl", bits_per_sample=BITS)
        est_b = DistributedGP(cfg_b)
        est_m = DistributedGP(dataclasses.replace(cfg_b, impl="mesh"))
        t0 = time.perf_counter()
        art_m = _block(est_m.fit(data.X, data.y, MESH_M, key=key))
        t_mesh = time.perf_counter() - t0
        art_b = _block(est_b.fit(data.X, data.y, MESH_M, key=key))
        for ledger in ("wire_bits", "payload_bits", "integrity_bits"):
            vm, vb = getattr(art_m, ledger), getattr(art_b, ledger)
            _check(int(vm) == int(vb),
                   f"mesh {protocol}: {ledger} {vm} != batched {vb}")
        if protocol == "broadcast":
            spans = {d for leaf in jax.tree_util.tree_leaves(art_m.factors)
                     for d in leaf.sharding.device_set}
            _check(spans == set(devs[:MESH_M]),
                   f"mesh broadcast factors live on {spans}")
        mu_m, var_m = _predict_all(est_m, art_m, data.queries)
        mu_b, var_b = _predict_all(est_b, art_b, data.queries)
        _finite_pred(mu_m, var_m, len(yq), f"mesh {protocol}")
        dmu = float(np.max(np.abs(mu_m - mu_b)))
        dvar = float(np.max(np.abs(var_m - var_b)))
        prior = float(np.exp(np.asarray(art_b.params.log_a)))
        _check(dmu <= MESH_AGREE_REL * float(np.std(yq))
               and dvar <= MESH_AGREE_REL * prior,
               f"mesh {protocol}: mesh vs batched disagree (max |dmu| {dmu}, "
               f"max |dvar| {dvar})")
        rows.append({
            "phase": f"mesh:{protocol}", "device_kind": devs[0].device_kind,
            "devices": len(devs), "m": MESH_M, "cold_fit_s_mesh": t_mesh,
            "wire_bits": int(art_m.wire_bits),
            "payload_bits": int(art_m.payload_bits),
            "integrity_bits": int(art_m.integrity_bits),
            "max_abs_dmu": dmu, "max_abs_dvar": dvar,
            "smse_mesh": float(np.mean((mu_m - yq) ** 2) / np.var(yq)),
            "smse_batched": float(np.mean((mu_b - yq) ** 2) / np.var(yq)),
        })
        del art_m, art_b
        gc.collect()
    return rows


def _phase(name: str, fn, failures: list):
    """Run one phase; a failure is reported and remembered, and the other
    phases still run so one bring-up run shows every fault."""
    t0 = time.perf_counter()
    try:
        out = fn()
    except Exception:  # noqa: BLE001 - every phase failure fails the run
        traceback.print_exc()
        print(f"[smoke {name}] FAILED after {time.perf_counter() - t0:.1f}s",
              flush=True)
        failures.append(name)
        return None
    return out


def _report(row: dict) -> None:
    print(f"[smoke {row['phase']}] " + json.dumps(row, default=str),
          flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the data, the machine split and the fits")
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the impl='mesh' phase on four chips")
    args = ap.parse_args(argv)

    _import_repro()
    devs = _require_tpu(MESH_M if args.four_chips else 1)
    from repro.compat import setup_compilation_cache
    from repro.core import DGPConfig

    cache_dir = setup_compilation_cache()
    cache = CacheCounter()
    print(f"[smoke] smoke output, not benchmark numbers | device "
          f"{devs[0].device_kind} x{len(devs)} | compilation cache "
          f"{cache_dir}", flush=True)
    data = make_data(args.seed,
                     MESH_N_TRAIN if args.four_chips else N_TRAIN)
    failures: list = []

    if args.four_chips:
        rows = _phase("mesh", lambda: run_mesh_phase(data, args.seed, devs),
                      failures) or []
        for row in rows:
            _report(row)
    else:
        pallas = dict(bits_per_sample=BITS, gram_mode="nystrom",
                      gram_backend="pallas")
        cfg_b = DGPConfig(protocol="broadcast", fusion="kl", **pallas)
        art_b = None
        for label, cfg, compare_xla in (
            ("a:center", DGPConfig(protocol="center", **pallas), True),
            ("b:broadcast", cfg_b, True),
            ("c:poe", DGPConfig(protocol="poe", fusion="rbcm",
                                gram_mode="dense", bits_per_sample=0), False),
        ):
            res = _phase(label, lambda: run_protocol_phase(
                label, cfg, data, args.seed, devs[0], compare_xla), failures)
            if res:
                _report(res[1])
                if cfg is cfg_b:
                    art_b = res[0]  # the fleet's tenants derive from it
            del res
            gc.collect()

        if art_b is None:
            failures.append("d:fleet (no broadcast artifact)")
        else:
            row = _phase("d:fleet", lambda: run_fleet_phase(
                art_b, cfg_b, data, devs[0]), failures)
            if row:
                _report(row)

    print(f"[smoke] compilation cache {cache_dir}: {cache.hits} hits, "
          f"{cache.misses} misses", flush=True)
    if failures:
        print(f"chip_smoke: failed phases: {', '.join(failures)}",
              file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
