"""The broadcast factor build in a traced run: the device operations of its
program, found by the program's jitted name, and the work it has to do.

Its programs (``repro.core.protocols.broadcast.broadcast_factor_buffers``,
which sets out the artifact's factor buffers, and
``broadcast_factor_group``, called once per group of receivers) run as the
modules ``jit_broadcast_factor_buffers`` and ``jit_broadcast_factor_group``
on the chip; their operations are those of the trace's ``XLA Ops`` line
that lie inside those modules' events.  Its wire products come from the ``qgram_packed`` kernel
called inside it; those kernel operations are left out here, because
``qgram_packed_roofline`` counts them.  A trace without the program (a
program that builds its factors otherwise) gives no time.
"""
from __future__ import annotations

import math
import re

from bench import trace

MODULE = re.compile(r"^jit_broadcast_factor_(buffers|group)\b")
QGRAM = re.compile(r"qgram_packed_pallas")


def device_s(tr: trace.Trace) -> float:
    """Seconds in which an operation of the build's programs ran on chip 0,
    less those of its ``qgram_packed`` kernel operations."""
    spans = trace._union([(o.start, o.end) for o in tr.modules
                          if MODULE.match(o.name)])
    if not spans or not tr.ops:
        return 0.0
    ops = tr.ops[0]
    busy = trace._union([(o.start, o.end) for o in ops])
    qgram = trace._union([(o.start, o.end) for o in ops if trace.is_kernel(o.name)
                          and QGRAM.search(trace.instruction_name(o.name))])
    build = trace._subtract(busy, qgram)
    return trace._intersect(build, spans) * 1e-9


def work(cfg: dict) -> tuple:
    """(flops, bytes) of the factor build of one broadcast fit.

    Per receiver, with K = ceil(n_train / m) Nyström centers (its own
    points) and N = n_train columns: the SE kernel over its K x N columns and
    K x K centers from their inner products (6 operations an entry: two
    squared norms, the product's double, the length scale, exp and the
    amplitude), two K x K Choleskys (K^3/3 each: L_KK and L_M), the triangular
    solve W = L_KK^{-1} G_KN (K^2 N), W W^T for L_M (2 K^2 N), alpha (W y
    and W^T t, 4 K N, and a K x K solve pair, 2 K^2), the serve cache's
    triangular inverse (K^3/3) and W alpha (2 K N).  Bytes: the inner
    products read and W written (K N each), the three K x K factors written
    (L_KK, L_M and the inverse), alpha and W alpha: float32."""
    m = cfg["m"]
    N = cfg["n_train"]
    K = math.ceil(N / m)
    flops = (6 * K * (K + N) + 2 * K**3 / 3 + K * K * N + 2 * K * K * N
             + 4 * K * N + 2 * K * K + K**3 / 3 + 2 * K * N)
    nbytes = 4 * (2 * K * N + 3 * K * K + N + K)
    return m * flops, m * nbytes
