#!/usr/bin/env python3
"""Record the small TPU trace of one broadcast refit that
``bench/testdata/fit/`` keeps for the tests of ``bench/program_trace.py``
(run on a chip; writes the ``.xplane.pb``, gzipped, to the path given).

    python3 bench/tests/record_fit_trace.py bench/testdata/fit/v5e_fit.xplane.pb.gz

The committed configuration at the bench tests' small size (``conftest``:
480 points over 8 machines, 8 training steps) is fitted once with the
persistent compilation cache on, then refitted inside one ``bench.window``
and one ``bench.refit`` span, as the benchmark's window does: the program's
own ``repro.fit`` spans, JAX's dispatch and program-building events and the
persistent-cache loads of the refit are all in the trace.  Prints the host
events inside the ``repro.fit`` root, by total time, to find their names.
"""
import collections
import gzip
import json
import os
import shutil
import sys
import tempfile

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
for p in (ROOT, os.path.join(ROOT, "src")):
    sys.path.insert(0, p)
os.environ.setdefault("JAX_COMPILATION_CACHE_DIR", os.path.join(ROOT, ".jax_cache"))

import jax  # noqa: E402

from bench import cells, data as bdata, program_trace, trace  # noqa: E402
from bench.common import block  # noqa: E402

SMALL_CFG = {"n_train": 480, "m": 8, "steps": 8}  # conftest.SMALL_CFG


def census(path: str, top: int = 60) -> list:
    """[(seconds, count, name)] of the host events inside the root span."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    host = [p for p in pd.planes if p.name == "/host:CPU"][0]
    events = [(e.name, e.start_ns, e.start_ns + e.duration_ns)
              for ln in host.lines for e in ln.events]
    roots = [(s, e) for n, s, e in events if n == program_trace.ROOT_SPAN]
    tot, cnt = collections.Counter(), collections.Counter()
    for n, s, e in events:
        if any(a <= s < b for a, b in roots):
            key = "PjitFunction(...)" if n.startswith("PjitFunction(") else n
            tot[key] += (e - s) * 1e-9
            cnt[key] += 1
    return [(tot[k], cnt[k], k) for k in sorted(tot, key=lambda k: -tot[k])[:top]]


def main(out: str) -> None:
    from repro.compat import setup_compilation_cache

    print(f"device {jax.devices()[0].device_kind}, compilation cache "
          f"{setup_compilation_cache()}")
    with open(os.path.join(ROOT, "bench", "configs",
                           "kin40k-m40-broadcast-r25.json")) as f:
        cfg = dict(json.load(f), **SMALL_CFG)
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
    for sec, n, name in census(path):
        print(f"{sec * 1e3:10.3f} ms {n:6d}  {name[:120]}")
    shutil.rmtree(tmp, ignore_errors=True)


if __name__ == "__main__":
    main(sys.argv[1])
