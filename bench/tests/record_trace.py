#!/usr/bin/env python3
"""Record the small TPU trace ``bench/testdata`` keeps for the trace
reduction's test (run on a chip; writes under the directory given).

    python3 bench/tests/record_trace.py <out_dir>

Inside one ``bench.window`` span: three ``bench.query`` spans, each running
the Pallas ``gram`` kernel (128 x 250 x 8) and a plain XLA matmul, with a
20 ms host sleep between them, and one ``bench.fit`` span running a
256 x 256 x 256 matmul five times.
"""
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, os.path.join(ROOT, "src"))

import jax  # noqa: E402


def main(out: str) -> None:
    from repro.kernels.gram.ops import gram

    x = jax.random.normal(jax.random.PRNGKey(0), (128, 8))
    y = jax.random.normal(jax.random.PRNGKey(1), (250, 8))
    a = jax.random.normal(jax.random.PRNGKey(2), (256, 256))
    mm = jax.jit(lambda u, v: u @ v.T)
    sq = jax.jit(lambda u: u @ u)
    with jax.default_matmul_precision("highest"):
        jax.block_until_ready((gram(x, y), mm(x, y), sq(a)))
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(out, profiler_options=opts)
        with jax.profiler.TraceAnnotation("bench.window"):
            for _ in range(3):
                with jax.profiler.TraceAnnotation("bench.query"):
                    jax.block_until_ready((gram(x, y), mm(x, y)))
                time.sleep(0.02)
            with jax.profiler.TraceAnnotation("bench.fit"):
                b = a
                for _ in range(5):
                    b = sq(b)
                b.block_until_ready()
        jax.profiler.stop_trace()


if __name__ == "__main__":
    main(sys.argv[1])
