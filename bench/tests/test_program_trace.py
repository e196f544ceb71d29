"""The reduction of the program's spans and JAX's host events
(``bench/program_trace.py``) and its six readers: on hand-made events, on
one broadcast refit recorded on a TPU v5e (``bench/testdata/fit``, made by
``record_fit_trace.py``), and the three older readers unchanged by it."""
import gzip
import importlib.util
import os
import shutil

import pytest

from bench import program_trace, trace

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMALL = os.path.join(BENCH, "testdata", "v5e_small.xplane.pb")
FIT = os.path.join(BENCH, "testdata", "fit", "v5e_fit.xplane.pb.gz")
OLD = ("device_s.refit", "qgram_packed_roofline", "device_idle.refit")
NEW = ("idle_s.fit.wire", "idle_s.fit.train", "idle_s.fit.factors",
       "idle_s.fit.self", "build_s.fit", "dispatches.fit")
PEAK = {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
CFG = {"m": 8, "d": 8, "n_train": 480, "bits_per_sample": 25, "max_bits": 12}


def reader(name):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(BENCH, "metrics", name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def read(name, tr, fits=1):
    return reader(name).read(trace.MetricCtx(tr, {"fits": fits}, CFG, {}, PEAK))


@pytest.fixture(scope="module")
def fit_path(tmp_path_factory):
    out = tmp_path_factory.mktemp("fit") / "v5e_fit.xplane.pb"
    with gzip.open(FIT) as src, open(out, "wb") as dst:
        shutil.copyfileobj(src, dst)
    return str(out)


@pytest.fixture(scope="module")
def recorded(fit_path):
    return program_trace.load(fit_path, chips=1)


# -- hand-made events ----------------------------------------------------------


def _trace(busy, spans, host=(), t0=0, t1=100):
    ops = [trace.Op("%fusion = f32[8] fusion(...)", s, e) for s, e in busy]
    return program_trace.ProgramTrace(
        [ops], [[]], [], t0, t1, program_spans=[(n, s, e, {}) for n, s, e in spans],
        host_events=list(host))


ROOT_AND_PHASES = [("repro.fit", 10, 50), ("repro.fit.wire", 12, 20),
                   ("repro.fit.train", 20, 40), ("repro.fit.factors", 40, 48)]


def test_idle_inside_spans_of_one_name():
    t = _trace([(14, 16), (30, 35)], ROOT_AND_PHASES)
    assert t.idle_in_spans("fit.wire") == pytest.approx(6e-9)   # 8 - 2 busy
    assert t.idle_in_spans("fit.train") == pytest.approx(15e-9)  # 20 - 5 busy
    assert t.idle_in_spans("fit.factors") == pytest.approx(8e-9)


def test_self_idle_leaves_out_the_children():
    # root 10..50, children cover 12..48: self is 10..12 and 48..50
    t = _trace([(11, 12), (30, 35)], ROOT_AND_PHASES)
    assert t.root_self_idle() == pytest.approx(3e-9)
    total = sum(t.idle_in_spans(p) for p in ("fit.wire", "fit.train",
                                             "fit.factors"))
    assert total + t.root_self_idle() == pytest.approx(
        t._idle(t.span_union("fit")))


def test_roots_outside_the_window_do_not_count():
    spans = ROOT_AND_PHASES + [("repro.fit", 90, 120), ("repro.fit.wire", 95, 99)]
    t = _trace([], spans)
    assert t.roots() == [(10, 50)]
    assert t.idle_in_spans("fit.wire") == pytest.approx(8e-9)


def test_host_events_count_only_inside_roots():
    host = [("PjitFunction(a)", 5, 8),      # before the root
            ("PjitFunction(b)", 12, 14),
            ("PjitFunction(b)", 12, 14),    # the profiler's second event
            ("PjitFunction(c)", 20, 30),    # traces ...
            ("PjitFunction(d)", 21, 22),    # ... a call inside it
            ("lower_sharding_computation", 23, 28),
            ("PjitFunction(e)", 45, 60)]    # starts inside, ends outside
    t = _trace([], ROOT_AND_PHASES, host)
    assert t.calls() == [(5, 8), (12, 14), (20, 30), (45, 60)]
    assert t.host_count_in_roots(t.calls()) == 3
    assert t.build_intervals() == [[20, 30]]
    assert t.host_time_in_roots(t.build_intervals()) == pytest.approx(10e-9)
    assert t.host_time_in_roots(t.calls()) == pytest.approx(2e-9 + 10e-9 + 5e-9)


def test_per_fit_divides_by_the_roots_that_match_the_fits():
    two = ROOT_AND_PHASES + [("repro.fit", 60, 80), ("repro.fit.wire", 62, 70)]
    t = _trace([], two)
    assert t.per_fit(10.0, 2) == 5.0
    assert t.per_fit(10.0, 3) is None
    assert _trace([], []).per_fit(10.0, 0) is None


# -- the recorded refit ---------------------------------------------------------


def test_the_refit_holds_the_program_spans(recorded):
    names = {n for n, *_ in recorded.program_spans}
    assert {"repro.fit", "repro.fit.wire", "repro.fit.train",
            "repro.fit.factors"} <= names
    (root,) = recorded.roots()
    fits = {st.get("fit") for _, _, _, st in recorded.program_spans}
    assert len(fits) == 1 and None not in fits
    for n, s, e, _ in recorded.program_spans:
        assert root[0] <= s <= e <= root[1], n


def test_the_refit_holds_jax_events_under_v5e_names(recorded):
    names = {n for n, _, _ in recorded.host_events}
    assert "PjitFunction(train_scan)" in names
    assert {"lower_sharding_computation", "trace_to_jaxpr_dynamic",
            "LoadProgramMaybeFromCache"} <= names
    events = sum(1 for n, _, _ in recorded.host_events
                 if n.startswith("PjitFunction("))
    assert 0 < recorded.host_count_in_roots(recorded.calls()) < events
    build = recorded.host_time_in_roots(recorded.build_intervals())
    (root,) = recorded.roots()
    assert 0 < build < (root[1] - root[0]) * 1e-9


def test_the_phases_account_for_the_idle_root(recorded):
    phases = [recorded.idle_in_spans(p) for p in ("fit.wire", "fit.train",
                                                   "fit.factors")]
    assert all(p > 0 for p in phases)
    whole = recorded._idle(recorded.span_union("fit"))
    assert sum(phases) + recorded.root_self_idle() == pytest.approx(whole)


def test_the_new_readers_on_the_recorded_refit(recorded):
    got = {n: read(n, recorded) for n in NEW}
    assert all(v is not None and v > 0 for v in got.values()), got
    assert got["dispatches.fit"] == recorded.host_count_in_roots(recorded.calls())
    assert all(read(n, recorded, fits=2) is None for n in NEW)


def test_the_new_readers_say_nothing_without_program_spans():
    small = program_trace.load(SMALL, chips=1)
    assert small.program_spans == []
    assert all(read(n, small) is None for n in NEW)


@pytest.mark.parametrize("which", ["small", "fit"])
def test_readers_find_the_trace_the_harness_wrote(which, fit_path, tmp_path,
                                                  monkeypatch):
    """As in ``run.py``: the readers get the plain reduced trace and read the
    host events from the trace directory themselves."""
    from bench import run as brun

    src = SMALL if which == "small" else fit_path
    dst = tmp_path / "plugins" / "profile" / "run"
    dst.mkdir(parents=True)
    shutil.copyfile(src, dst / "t.xplane.pb")
    monkeypatch.setattr(brun, "TRACE_DIR", str(tmp_path))
    plain = trace.load(str(tmp_path), chips=1)
    extended = program_trace.load(src, chips=1)
    for name in NEW:
        assert read(name, plain) == read(name, extended), name
    assert (read("build_s.fit", plain) is None) == (which == "small")


# -- the older readers, unchanged -----------------------------------------------


def test_older_readers_keep_their_values_on_the_small_trace():
    tr = trace.load(SMALL, chips=1)
    assert read("device_s.refit", tr) is None
    assert read("qgram_packed_roofline", tr) is None
    assert read("device_idle.refit", tr) == 99.95670339700075
    assert tr.offset_ns == 1210000.0
    assert tr.window_s() == 0.073629333


@pytest.mark.parametrize("which", ["small", "fit"])
def test_older_readers_read_the_extended_trace_alike(which, fit_path):
    path = SMALL if which == "small" else fit_path
    plain, extended = trace.load(path, chips=1), program_trace.load(path, chips=1)
    for name in OLD:
        assert read(name, extended) == read(name, plain), name
    assert extended.breakdown() == plain.breakdown()
    assert extended.offset_ns == plain.offset_ns
