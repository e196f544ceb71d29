"""The program's spans (``repro.spans``) in a profiler trace of real fits:
one ``repro.fit`` root per fit, its phase spans nested in it in order and
sharing its ``fit`` id, and no cost or effect with no profiler running."""
import glob

import jax
import numpy as np
import pytest

from repro import spans
from repro.core import DGPConfig, DistributedGP
from repro.core.protocols import base

PHASES = ("repro.fit.wire", "repro.fit.train", "repro.fit.factors")
SMALL = dict(bits_per_sample=8, steps=8)


def _data(n=480, d=4, seed=0):
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(n, d)).astype(np.float32)
    return X, np.sin(X.sum(1)).astype(np.float32)


def _read(trace_dir):
    """([(name, start, end, fit id)] of the ``repro.*`` host events, by
    start; the start of every call of the training scan's jitted program)."""
    from jax.profiler import ProfileData

    (path,) = glob.glob(f"{trace_dir}/plugins/profile/*/*.xplane.pb")
    out, scans = [], []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(spans.PREFIX):
                    out.append((e.name, e.start_ns, e.start_ns + e.duration_ns,
                                dict(e.stats).get("fit")))
                elif e.name == "PjitFunction(train_scan)":
                    scans.append(e.start_ns)
    return sorted(out, key=lambda s: s[1]), scans


@pytest.fixture(scope="module", params=["broadcast", "center"])
def traced(request, tmp_path_factory):
    """Two fits of one protocol under the profiler, the first through
    ``DistributedGP.fit`` and the second through the legacy
    ``protocols.base.fit``; (protocol, spans, training-scan calls)."""
    protocol = request.param
    X, y = _data()
    key = jax.random.PRNGKey(1)
    est = DistributedGP(DGPConfig(protocol=protocol, **SMALL))

    def legacy():
        parts = base.split_machines(X, y, 4, key)
        return base.fit(parts, SMALL["bits_per_sample"], protocol=protocol,
                        steps=SMALL["steps"])

    out = str(tmp_path_factory.mktemp(f"trace_{protocol}"))
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0  # host spans only, as the benchmark traces
    jax.profiler.start_trace(out, profiler_options=opts)
    try:
        for fit in (lambda: est.fit(X, y, 4, key=key), legacy):
            jax.block_until_ready(jax.tree_util.tree_leaves(fit()))
    finally:
        jax.profiler.stop_trace()
    return (protocol, *_read(out))


def test_one_root_per_fit_with_distinct_ids(traced):
    _, got, _ = traced
    roots = [s for s in got if s[0] == "repro.fit"]
    assert len(roots) == 2
    ids = [r[3] for r in roots]
    assert all(isinstance(i, int) for i in ids)
    assert ids[0] != ids[1]


def test_phases_nest_in_their_root_in_order(traced):
    _, got, _ = traced
    roots = [s for s in got if s[0] == "repro.fit"]
    for _, r0, r1, fit in roots:
        inside = [s for s in got if s[0] != "repro.fit" and r0 <= s[1] < r1]
        assert tuple(s[0] for s in inside) == PHASES
        for name, s0, s1, sid in inside:
            assert r0 <= s0 <= s1 <= r1, name
            assert sid == fit, name
        for a, b in zip(inside, inside[1:]):
            assert a[2] <= b[1], (a[0], b[0])  # no overlap


def test_span_without_profiler_is_a_noop():
    with spans.span("fit.wire", extra=1):
        pass
    with spans.fit_span():
        first = spans._FIT.get()
        with spans.span("fit.train"):
            pass
    assert first is not None and spans._FIT.get() is None
    with spans.fit_span():
        assert spans._FIT.get() not in (None, first)


def test_training_scan_has_a_stable_program_name(traced):
    _, got, dispatched = traced
    roots = [s for s in got if s[0] == "repro.fit"]
    train = [s for s in got if s[0] == "repro.fit.train"]
    assert len(train) == len(roots) == 2
    for _, t0, t1, _ in train:  # retraced and dispatched in every fit
        assert any(t0 <= s < t1 for s in dispatched)
