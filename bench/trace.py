"""Reduction of a JAX profiler trace (``.xplane.pb``) to what the per-layer
metrics read.

A TPU trace has one plane per chip, ``/device:TPU:<i>``.  Its ``XLA Ops``
line holds one event per HLO instruction that ran, named by the
instruction's text (``%name = shape op(...), custom_call_target=...``); a
Pallas kernel is a ``custom-call`` with ``custom_call_target="tpu_custom_call"``
whose instruction name carries its jitted wrapper's name (``gram_pallas``,
``vmap_jit_gram_pallas__``).  The ``Async XLA Ops`` line holds the async
halves (copies, collectives).  The host plane ``/host:CPU`` holds the
benchmark's own ``bench.<kind>`` spans (``common.Spans``), one
``bench.window`` span around the whole measured window, on the same clock.

The device's clock and the host's are aligned by the profiler to about a
millisecond, not better: on a TPU v5e the device events of a call were seen
up to 1.5 ms before the host span that launched and awaited them.
:func:`load` therefore shifts the device events by the offset that puts the
most device busy time inside the host's bench spans (searched over +-5 ms),
so that device time is attributed to the right call.

Everything is in nanoseconds internally and returned in seconds.
"""
from __future__ import annotations

import bisect
import dataclasses
import glob
import json
import os
import re

WINDOW_SPAN = "bench.window"
SPAN_PREFIX = "bench."
COLLECTIVE = re.compile(
    r"all-gather|all-reduce|reduce-scatter|collective-permute|all-to-all|"
    r"collective-broadcast")
PEAKS = os.path.join(os.path.dirname(os.path.abspath(__file__)), "peaks.json")


def peak_of(device_kind: str) -> dict:
    """The peak row of a device kind; an unknown device is an error."""
    with open(PEAKS) as f:
        table = json.load(f)
    if device_kind not in table["devices"]:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{PEAKS}; known: {', '.join(table['devices'])}")
    return table["devices"][device_kind]


def instruction_name(op_text: str) -> str:
    """``%name.3 = ...`` -> ``name.3``."""
    head = op_text.split(" = ", 1)[0].strip()
    return head[1:] if head.startswith("%") else head


def is_kernel(op_text: str) -> bool:
    return 'custom_call_target="tpu_custom_call"' in op_text


def _union(intervals):
    """Merge (start, end) intervals; returns a sorted disjoint list."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def _length(merged) -> float:
    return float(sum(e - s for s, e in merged))


def _intersect(a, b) -> float:
    """Length of the intersection of two merged interval lists."""
    i = j = 0
    tot = 0.0
    while i < len(a) and j < len(b):
        s = max(a[i][0], b[j][0])
        e = min(a[i][1], b[j][1])
        if e > s:
            tot += e - s
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return tot


def _subtract(a, b):
    """Merged intervals of a minus merged intervals b."""
    out = []
    j = 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append([cur, b[k][0]])
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append([cur, e])
    return out


def _covered(merged_b):
    """x -> measure of (merged_b intersected with (-inf, x]), vectorized."""
    import numpy as np

    if not merged_b:
        return lambda x: np.zeros_like(x, dtype=np.float64)
    b = np.asarray(merged_b, np.float64)
    xs = b.reshape(-1)
    cum = np.concatenate([[0.0], np.cumsum(b[:, 1] - b[:, 0])])
    ys = np.repeat(cum, 2)[1:-1]
    return lambda x: np.interp(x, xs, ys)


def clock_offset(busy, spans, reach_ns: float = 5e6, step_ns: float = 20e3):
    """The shift of device time (ns, added to device timestamps) that puts
    the most of the merged ``busy`` intervals inside the merged host
    ``spans``; the middle of the best plateau, 0 when nothing overlaps."""
    import numpy as np

    if not busy or not spans:
        return 0.0
    a = np.asarray(busy, np.float64)
    F = _covered(spans)
    shifts = np.arange(-reach_ns, reach_ns + step_ns / 2, step_ns)
    inside = np.asarray([np.sum(F(a[:, 1] + d) - F(a[:, 0] + d)) for d in shifts])
    best = inside.max()
    if best <= 0:
        return 0.0
    top = shifts[inside >= best - 1e-9 * max(best, 1.0)]
    return float(0.5 * (top.min() + top.max()))


@dataclasses.dataclass
class Op:
    name: str      # the instruction's text
    start: float   # ns
    end: float


@dataclasses.dataclass
class Trace:
    ops: list            # per chip: [Op] of the XLA Ops line, in the window
    async_ops: list      # per chip: [Op] of the Async XLA Ops line
    spans: list          # [(name, start, end)] host bench spans
    t0: float
    t1: float
    offset_ns: float = 0.0  # shift applied to device time (see clock_offset)
    modules: list = dataclasses.field(default_factory=list)  # chip 0's programs

    # -- the window and the device's busy time ------------------------------

    def window_s(self) -> float:
        return (self.t1 - self.t0) * 1e-9

    def _busy(self, chip: int):
        return _union([(o.start, o.end) for o in self.ops[chip]])

    def busy_s(self) -> float:
        """Seconds in which an operation ran, averaged over the chips."""
        return sum(_length(self._busy(c)) for c in range(len(self.ops))) \
            * 1e-9 / len(self.ops)

    # -- spans ----------------------------------------------------------------

    def span_intervals(self, kind: str):
        name = SPAN_PREFIX + kind
        return _union([(s, e) for n, s, e in self.spans if n == name])

    def span_count(self, kind: str) -> int:
        name = SPAN_PREFIX + kind
        return sum(1 for n, _, _ in self.spans if n == name)

    def device_time_in_spans(self, kind: str) -> float:
        """Seconds of device busy time inside the host spans of one kind,
        averaged over the chips."""
        spans = self.span_intervals(kind)
        return sum(_intersect(self._busy(c), spans)
                   for c in range(len(self.ops))) * 1e-9 / len(self.ops)

    # -- kernels and collectives ---------------------------------------------

    def kernel_time(self, pattern: str) -> float:
        """Seconds of the Pallas kernel events whose instruction name matches
        ``pattern`` (a regex), summed over the chips."""
        rx = re.compile(pattern)
        return sum(o.end - o.start for ops in self.ops for o in ops
                   if is_kernel(o.name) and rx.search(instruction_name(o.name))
                   ) * 1e-9

    def collective_s(self) -> tuple:
        """(collective seconds, of which no compute ran) averaged over the
        chips; None when the trace has no collective."""
        tot = exposed = 0.0
        found = False
        for c in range(len(self.ops)):
            coll = [(o.start, o.end) for o in self.ops[c] + self.async_ops[c]
                    if COLLECTIVE.search(instruction_name(o.name))]
            if not coll:
                continue
            found = True
            coll = _union(coll)
            compute = _union([(o.start, o.end) for o in self.ops[c]
                              if not COLLECTIVE.search(instruction_name(o.name))])
            tot += _length(coll)
            exposed += _length(_subtract(coll, compute))
        if not found:
            return None
        n = len(self.ops)
        return tot * 1e-9 / n, exposed * 1e-9 / n

    # -- breakdown --------------------------------------------------------------

    def breakdown(self, top: int = 10) -> dict:
        """On chip 0: the device operations that took most time, named
        ``<program>:<instruction>`` without numeric suffixes, and the longest
        idle gaps, each named by the host span that was open in it."""
        mods = sorted(self.modules, key=lambda m: m.start)
        starts = [m.start for m in mods]
        by_op = {}
        for o in self.ops[0]:
            i = bisect.bisect_right(starts, o.start) - 1
            prog = re.sub(r"\(\d+\)$", "", mods[i].name) \
                if i >= 0 and mods[i].end >= o.start else "?"
            key = prog + ":" + re.sub(r"\.\d+$", "", instruction_name(o.name))
            by_op[key] = by_op.get(key, 0.0) + (o.end - o.start)
        device_ops = sorted(by_op.items(), key=lambda kv: -kv[1])[:top]
        busy = self._busy(0)
        gaps = _subtract([[self.t0, self.t1]], busy)
        spans = sorted((s, e, n) for n, s, e in self.spans if n != WINDOW_SPAN)
        starts = [s for s, _, _ in spans]
        named = []
        for s, e in sorted(gaps, key=lambda g: g[0] - g[1])[:top]:
            mid = 0.5 * (s + e)
            i = bisect.bisect_right(starts, mid) - 1
            label = "between calls"
            if i >= 0 and spans[i][1] >= mid:
                label = spans[i][2][len(SPAN_PREFIX):]
            named.append([label, (e - s) * 1e-9])
        return {"device_ops": [[k, v * 1e-9] for k, v in device_ops],
                "idle_gaps": named}


def find_xplane(trace_dir: str) -> str:
    files = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                          "*.xplane.pb")))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return files[-1]


def load(path: str, chips: int = 1) -> Trace:
    """Reduce the trace at ``path`` (a ``.xplane.pb`` or the directory the
    profiler wrote) to the first ``chips`` TPU planes and the bench spans,
    clipped to the ``bench.window`` span (the whole trace if it has none)."""
    from jax.profiler import ProfileData

    if os.path.isdir(path):
        path = find_xplane(path)
    pd = ProfileData.from_file(path)
    devices, spans = {}, []
    for plane in pd.planes:
        m = re.fullmatch(r"/device:TPU:(\d+)", plane.name)
        if m:
            lines = {ln.name: ln for ln in plane.lines}
            devices[int(m.group(1))] = tuple(
                [Op(e.name, e.start_ns, e.start_ns + e.duration_ns)
                 for e in lines[k].events] if k in lines else []
                for k in ("XLA Ops", "Async XLA Ops", "XLA Modules"))
        elif plane.name == "/host:CPU":
            for ln in plane.lines:
                for e in ln.events:
                    if e.name.startswith(SPAN_PREFIX):
                        spans.append((e.name, e.start_ns,
                                      e.start_ns + e.duration_ns))
    if len(devices) < chips:
        raise ValueError(f"trace has {len(devices)} TPU planes, want {chips}")
    ids = sorted(devices)[:chips]
    calls = _union([(s, e) for n, s, e in spans if n != WINDOW_SPAN])
    offset = clock_offset(
        _union([(o.start, o.end) for o in devices[ids[0]][0]]), calls)
    for i in ids:
        devices[i] = tuple([Op(o.name, o.start + offset, o.end + offset)
                            for o in ops] for ops in devices[i])
    win = [(s, e) for n, s, e in spans if n == WINDOW_SPAN]
    if win:
        t0, t1 = win[0]
    else:
        every = [o for i in ids for o in devices[i][0]]
        t0 = min([o.start for o in every] + [s for _, s, _ in spans])
        t1 = max([o.end for o in every] + [e for _, _, e in spans])

    def clip(ops):
        return [Op(o.name, max(o.start, t0), min(o.end, t1)) for o in ops
                if o.end > t0 and o.start < t1]

    return Trace([clip(devices[i][0]) for i in ids],
                 [clip(devices[i][1]) for i in ids], spans, t0, t1, offset,
                 clip(devices[ids[0]][2]))


@dataclasses.dataclass
class MetricCtx:
    """What a per-layer metric reader gets: the reduced trace, the driver's
    counters over the window, the configuration and traffic, and the peak
    row of the device."""

    trace: "Trace"
    counters: dict
    cfg: dict
    traffic: dict
    peak: dict

    def least_time(self, flops: float, nbytes: float) -> tuple:
        """(least seconds, the bound: "compute" or "memory") of a piece of
        work on this device."""
        tc = flops / self.peak["flops_per_s"]
        tm = nbytes / self.peak["hbm_bytes_per_s"]
        return (tc, "compute") if tc >= tm else (tm, "memory")

    def roofline(self, pattern: str, flops: float, nbytes: float):
        """Share (%) of the kernel's time that the least time of the work
        done would take; None when the trace holds no such kernel."""
        t = self.trace.kernel_time(pattern)
        if not t or flops <= 0:
            return None
        return 100.0 * self.least_time(flops, nbytes)[0] / t
