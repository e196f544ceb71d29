#!/usr/bin/env python3
"""Run one benchmark cell once on the TPU this process finds.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cell, its configuration, its traffic mix and its metrics are all named in
``BENCHMARK.json`` at the checkout's root and found by name: the
configuration in its ``file``, the traffic in ``bench/traffic/<traffic>.json``
(whose ``driver`` names a module of ``bench/drivers/``), each per-layer
metric's reader in ``bench/metrics/<metric>.py``, and the limits of the
correctness check in ``bench/checks/<workload>.json``.

A run: set-up (data, fit, warm-up of every shape the window uses; counted as
``setup_s``), the measured window of ``--seconds``, then, with the program's
state freed, the comparison with the plain reference that decides
``correct``.  With ``--trace 1`` the window runs under the JAX profiler and
the result carries the cell's per-layer metrics instead of its end-to-end
ones.  The last line of stdout is the result as one JSON object; a run that
finds no TPU, or fewer chips than the cell asks for, prints none and exits 2.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
# JAX's persistent compilation cache at a fixed path inside the checkout
# (read when JAX is imported), unless the environment names one
os.environ.setdefault("JAX_COMPILATION_CACHE_DIR", os.path.join(ROOT, ".jax_cache"))

from bench import common  # noqa: E402

TRACE_DIR = os.path.join(ROOT, ".bench_trace")


def _load_module(path: str, name: str):
    if not os.path.isfile(path):
        raise common.Refused(f"missing {os.path.relpath(path, ROOT)}")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _read_json(path: str):
    if not os.path.isfile(path):
        raise common.Refused(f"missing {os.path.relpath(path, ROOT)}")
    with open(path) as f:
        return json.load(f)


class Ctx:
    """What a driver and a metric reader see of the run."""

    def __init__(self, workload, cfg, traffic, seed, seconds, trace):
        self.workload = workload
        self.cfg = cfg
        self.traffic = traffic
        self.seed = seed
        self.seconds = seconds
        self.trace_on = trace
        self.span = common.Spans(trace)

    @staticmethod
    def log(msg: str) -> None:
        print(f"[bench] {msg}", flush=True)


def _applies(metric: dict, workload: str, e2e_names: set) -> bool:
    if "workloads" in metric:
        return workload in metric["workloads"]
    return metric.get("moves", metric["name"]) in e2e_names


def load_cell(workload: str):
    """Everything ``BENCHMARK.json`` and the files it names say of one cell:
    (cell, config, traffic, check limits, driver module, end-to-end metrics,
    per-layer metrics, per-layer readers by name)."""
    spec = _read_json(os.path.join(ROOT, "BENCHMARK.json"))
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise common.Refused(f"unknown workload {workload!r}; known: "
                             f"{', '.join(cells)}")
    cell = cells[workload]
    cfg_entry = {c["name"]: c for c in spec["configs"]}[cell["config"]]
    cfg = _read_json(os.path.join(ROOT, cfg_entry["file"]))
    traffic = _read_json(os.path.join(BENCH, "traffic", cell["traffic"] + ".json"))
    checks = _read_json(os.path.join(BENCH, "checks", cell["name"] + ".json"))
    driver = _load_module(os.path.join(BENCH, "drivers", traffic["driver"] + ".py"),
                          "bench_driver_" + traffic["driver"])
    e2e = [m for m in spec["end_to_end"]
           if m["name"] == "setup_s" or cell["name"] in m.get("workloads", [])
           or "workloads" not in m]
    e2e_names = {m["name"] for m in e2e}
    per_layer = [m for m in spec["per_layer"]
                 if _applies(m, cell["name"], e2e_names)]
    readers = {m["name"]: _load_module(
        os.path.join(BENCH, "metrics", m["name"] + ".py"),
        "bench_metric_" + m["name"].replace(".", "_")) for m in per_layer}

    return cell, cfg, traffic, checks, driver, e2e, per_layer, readers


def judge(nums: dict, limits: dict, failed: int = 0, log=None) -> tuple:
    """(correct, {name: {"value", "limit"}}): every limited number present,
    finite and at most its limit, and no request failed."""
    import numpy as np

    correct = failed == 0
    check = {}
    for name, limit in limits.items():
        v = nums.get(name)
        ok = v is not None and bool(np.isfinite(v)) and v <= limit
        correct = correct and ok
        check[name] = {"value": v, "limit": limit}
    for name, v in nums.items():
        if name not in limits and log is not None:
            log(f"compared, no limit: {name} {v!r}")
    return bool(correct), check


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    cell, cfg, traffic, checks, driver, e2e, per_layer, readers = \
        load_cell(args.workload)

    common.import_repro()
    devs = common.require_tpu(cell["chips"])
    import jax
    from repro.compat import setup_compilation_cache
    from repro.kernels import runtime

    cache_dir = setup_compilation_cache()
    counter = common.CacheCounter()
    ctx = Ctx(cell["name"], cfg, traffic, args.seed, args.seconds,
              bool(args.trace))
    ctx.log(f"{cell['name']}: config {cell['config']}, traffic "
            f"{cell['traffic']}, seed {args.seed}, {args.seconds} s, trace "
            f"{args.trace}; device {devs[0].device_kind} x{len(devs)}; "
            f"compilation cache {cache_dir}")

    state = driver.setup(ctx)
    gc.collect()
    jax.effects_barrier()
    c_before, sweeps_before = counter.snapshot(), runtime.sweep_count()
    if args.trace:
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0  # host spans only, not every Python call
        jax.profiler.start_trace(TRACE_DIR, profiler_options=opts)
    t_window = time.perf_counter()
    setup_s = t_window - T_START
    with ctx.span("window"):
        result = driver.window(state, ctx)
    if args.trace:
        jax.profiler.stop_trace()
    c_after, sweeps_after = counter.snapshot(), runtime.sweep_count()
    in_window = {"cache_hits": c_after[0] - c_before[0],
                 "cache_misses": c_after[1] - c_before[1],
                 "compilations": c_after[2] - c_before[2],
                 "autotune_sweeps": sweeps_after - sweeps_before}
    ctx.log(f"inside the window: {in_window['cache_misses']} compilations "
            f"(persistent-cache misses), {in_window['autotune_sweeps']} "
            f"autotune sweeps, {in_window['compilations']} programs built "
            f"({in_window['cache_hits']} loaded from the cache); set-up "
            f"{setup_s:.3f} s; over the run: {c_after[1]} compilations, "
            f"{c_after[0]} cache loads")
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in devs[: cell["chips"]]]
    peak = max((p for p in peaks if p is not None), default=None)
    ctx.log(f"peak HBM bytes in use (fullest chip): {peak}; requests "
            f"attempted {result['attempted']}, failed {result['failed']}")

    out = driver.outputs(state, ctx)
    del state
    gc.collect()

    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs), "memory_peak_bytes": peak}
    metrics = {}
    breakdown = None
    if args.trace:
        from bench import trace as btrace

        tr = btrace.load(TRACE_DIR, chips=cell["chips"])
        peak_row = btrace.peak_of(devs[0].device_kind)
        mctx = btrace.MetricCtx(tr, result["counters"], cfg, traffic, peak_row)
        device["busy_s"] = tr.busy_s()
        device["window_s"] = tr.window_s()
        for m in per_layer:
            v = readers[m["name"]].read(mctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        breakdown = tr.breakdown()
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
    else:
        for m in e2e:
            v = setup_s if m["name"] == "setup_s" else result["e2e"].get(m["name"])
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}

    # the comparison with the plain reference decides ``correct``
    nums = driver.compare(out, ctx)
    correct, check = judge(nums, checks["limits"], result["failed"], ctx.log)

    line = {"correct": bool(correct), "attempted": result["attempted"],
            "failed": result["failed"], "metrics": metrics, "device": device}
    if breakdown is not None:
        line["breakdown"] = breakdown
    line["check"] = check
    for name, c in check.items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
