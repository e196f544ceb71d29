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
MARKER = "repro.fit.train.program"
GROUP = "repro.fit.factors.group"
SMALL = dict(bits_per_sample=8, steps=8)


def _data(n=480, d=4, seed=0):
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(n, d)).astype(np.float32)
    return X, np.sin(X.sum(1)).astype(np.float32)


def _read(trace_dir):
    """([(name, start, end, fit id, built)] of the ``repro.*`` host events,
    by start, ``built`` the training marker's stat; the start of every call
    of the training scan's jitted program)."""
    from jax.profiler import ProfileData

    (path,) = glob.glob(f"{trace_dir}/plugins/profile/*/*.xplane.pb")
    out, scans = [], []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(spans.PREFIX):
                    st = dict(e.stats)
                    out.append((e.name, e.start_ns, e.start_ns + e.duration_ns,
                                st.get("fit"), st.get("built")))
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
    """The three phases in order, the training marker, and in a broadcast
    fit one span per call of the factor build's group program, inside
    ``repro.fit.factors``."""
    protocol, got, _ = traced
    roots = [s for s in got if s[0] == "repro.fit"]
    for _, r0, r1, fit, _ in roots:
        inside = [s for s in got if s[0] != "repro.fit" and r0 <= s[1] < r1]
        phases = [s for s in inside if s[0] in PHASES]
        assert tuple(s[0] for s in phases) == PHASES
        groups = [s for s in inside if s[0] == GROUP]
        assert [s[0] for s in inside if s[0] not in PHASES + (GROUP,)] == [MARKER]
        assert bool(groups) == (protocol == "broadcast")
        _, f0, f1, _, _ = phases[-1]
        assert all(f0 <= g0 <= g1 <= f1 for _, g0, g1, _, _ in groups)
        for name, s0, s1, sid, _ in inside:
            assert r0 <= s0 <= s1 <= r1, name
            assert sid == fit, name
        for a, b in zip(phases, phases[1:]):
            assert a[2] <= b[1], (a[0], b[0])  # no overlap


def test_training_marker_says_whether_the_program_was_built(traced):
    """The marker closes inside ``repro.fit.train`` after the training
    program's call; the second fit, of the same data, builds nothing."""
    _, got, _ = traced
    train = [s for s in got if s[0] == "repro.fit.train"]
    marks = [s for s in got if s[0] == MARKER]
    assert len(marks) == len(train) == 2
    for (_, t0, t1, *_), (_, m0, m1, *_) in zip(train, marks):
        assert t0 <= m0 <= m1 <= t1
    built = [b for *_, b in marks]
    assert built[0] in (0, 1) and built[1] == 0


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
    for _, t0, t1, *_ in train:  # dispatched in every fit
        assert any(t0 <= s < t1 for s in dispatched)


def test_broadcast_spans_count_the_kernels_decodes(monkeypatch, tmp_path):
    """With the packed kernel on the path (interpret mode here), the wire
    span carries the row-tile decodes of machine 0's column of the wire
    products and each group span its group's: one kernel call per sender
    in each, whatever the group's receivers, two row tiles a block at
    150-row shards."""
    from jax.profiler import ProfileData

    monkeypatch.setenv("REPRO_FORCE_PALLAS", "1")
    X, y = _data(n=600)
    est = DistributedGP(DGPConfig(protocol="broadcast", gram_backend="pallas",
                                  **SMALL))
    jax.profiler.start_trace(str(tmp_path))
    try:
        jax.block_until_ready(jax.tree_util.tree_leaves(
            est.fit(X, y, 4, key=jax.random.PRNGKey(2))))
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(f"{tmp_path}/plugins/profile/*/*.xplane.pb")
    decodes = {}
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for e in line.events:
                st = dict(e.stats)
                if "qgram_decodes" in st:
                    decodes.setdefault(e.name, []).append(st["qgram_decodes"])
    # one group of all 4 receivers where the device reports no memory limit
    assert decodes == {"repro.fit.wire": [4 * 2], GROUP: [4 * 2]}
