"""Benchmark driver: one function per paper table/figure.
Prints ``name,us_per_call,derived`` CSV rows (see benchmarks/common.py).

  PYTHONPATH=src python -m benchmarks.run          # quick pass (CI scale)
  PYTHONPATH=src python -m benchmarks.run --full   # paper-scale settings
  PYTHONPATH=src python -m benchmarks.run --json   # + write BENCH_<name>.json
                                                   # (us/call per benchmark row;
                                                   #  see EXPERIMENTS.md §Perf)
"""
from __future__ import annotations

import argparse
import json
import sys
import time


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--json", action="store_true",
                    help="write BENCH_<name>.json per selected benchmark")
    ap.add_argument("--only", default=None,
                    help="comma list of: fig2,fig3,fig4,fig56,fig7,kernels,"
                         "ablation_bits,hotpath,serve,mesh,vq,wire,"
                         "fault,stream,fleet")
    args = ap.parse_args()
    quick = not args.full

    from repro.compat import setup_compilation_cache

    setup_compilation_cache()
    from . import fig2_distortion, fig3_pca, fig4_gp1d, fig56_regression, fig7_sparse
    from . import kernels_bench, ablation_bits, hotpath_bench, serve_bench
    from . import mesh_bench, vq_bench, wire_bench, fault_bench, stream_bench
    from . import fleet_bench
    from . import common

    benches = {
        "fig2": lambda: fig2_distortion.main(quick=quick),
        "fig3": lambda: fig3_pca.main(quick=quick),
        "fig4": lambda: fig4_gp1d.main(quick=quick),
        "fig56": lambda: fig56_regression.main(quick=quick),
        "fig7": lambda: fig7_sparse.main(quick=quick),
        "kernels": lambda: kernels_bench.main(quick=quick),
        "ablation_bits": lambda: ablation_bits.main(quick=quick),
        "hotpath": lambda: hotpath_bench.main(quick=quick),
        "serve": lambda: serve_bench.main(quick=quick),
        "mesh": lambda: mesh_bench.main(quick=quick),
        "vq": lambda: vq_bench.main(quick=quick),
        "wire": lambda: wire_bench.main(quick=quick),
        "fault": lambda: fault_bench.main(quick=quick),
        "stream": lambda: stream_bench.main(quick=quick),
        "fleet": lambda: fleet_bench.main(quick=quick),
    }
    selected = args.only.split(",") if args.only else list(benches)
    print("name,us_per_call,derived")
    for name in selected:
        t0 = time.time()
        start = len(common.RESULTS)
        benches[name]()
        if args.json:
            rows = common.RESULTS[start:]
            with open(f"BENCH_{name}.json", "w") as f:
                json.dump(rows, f, indent=1)
            print(f"# wrote BENCH_{name}.json ({len(rows)} rows)", flush=True)
        print(f"# {name} done in {time.time()-t0:.1f}s", flush=True)


if __name__ == "__main__":
    main()
