#!/usr/bin/env python3
"""Record the TPU trace of one refit of a benchmark cell at its own size, for
the tests of the per-layer readers (run on a chip; writes the
``.xplane.pb``, gzipped, to the path given).

    python3 bench/tests/record_cell_trace.py sarcos-refit \\
        bench/testdata/sarcos/v5e_sarcos_refit.xplane.pb.gz

The cell's configuration, as ``BENCHMARK.json`` names it, is fitted once
with the persistent compilation cache on (its set-up), then refitted inside
one ``bench.window`` and one ``bench.refit`` span, as the benchmark's window
does.  Prints the host events inside the ``repro.fit`` root, by total time
(``record_fit_trace.census``), and the peak device memory.
"""
import gzip
import os
import shutil
import sys
import tempfile

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
for p in (ROOT, os.path.join(ROOT, "src"), HERE):
    sys.path.insert(0, p)
os.environ.setdefault("JAX_COMPILATION_CACHE_DIR", os.path.join(ROOT, ".jax_cache"))

import jax  # noqa: E402

from bench import cells, data as bdata, run as brun, trace  # noqa: E402
from bench.common import block  # noqa: E402
from record_fit_trace import census  # noqa: E402


def main(workload: str, out: str) -> None:
    from repro.compat import setup_compilation_cache

    dev = jax.devices()[0]
    print(f"device {dev.device_kind}, compilation cache "
          f"{setup_compilation_cache()}")
    cfg = brun.load_cell(workload)[1]
    ds = cells.dataset(cfg, bdata.derived_seeds(7, 2)[0])
    est = cells.estimator(cfg)
    block(est.fit(ds.X, ds.y, cfg["m"], key=ds.key()))
    tmp = tempfile.mkdtemp()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(tmp, profiler_options=opts)
    with jax.profiler.TraceAnnotation("bench.window"):
        with jax.profiler.TraceAnnotation("bench.refit"):
            block(est.fit(ds.X, ds.y, cfg["m"], key=ds.key()))
    jax.profiler.stop_trace()
    path = trace.find_xplane(tmp)
    os.makedirs(os.path.dirname(os.path.abspath(out)), exist_ok=True)
    with open(path, "rb") as src, gzip.open(out, "wb", compresslevel=9) as dst:
        shutil.copyfileobj(src, dst)
    print(f"wrote {out}: {os.path.getsize(out)} bytes")
    stats = dev.memory_stats() or {}
    print(f"peak bytes in use {stats.get('peak_bytes_in_use')} of "
          f"{stats.get('bytes_limit')}")
    for sec, n, name in census(path):
        print(f"{sec * 1e3:10.3f} ms {n:6d}  {name[:120]}")
    shutil.rmtree(tmp, ignore_errors=True)


if __name__ == "__main__":
    main(sys.argv[1], sys.argv[2])
