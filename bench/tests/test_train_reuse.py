"""The reader of ``train_reuse.fit`` on hand-made events: the share of the
counted fits whose training marker says the program was not built."""
import importlib.util
import os

from bench import program_trace, trace

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
NAME = "train_reuse.fit"


def _read(spans, fits):
    spec = importlib.util.spec_from_file_location(
        NAME, os.path.join(BENCH, "metrics", NAME + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    tr = program_trace.ProgramTrace([[]], [[]], [], 0, 1000,
                                    program_spans=spans, host_events=[])
    return mod.read(trace.MetricCtx(tr, {"fits": fits}, {}, {}, {}))


def _fits(*built):
    """One root of 100 ns per entry of ``built``, with its train span and,
    unless the entry is None, a marker carrying it."""
    out = []
    for i, b in enumerate(built):
        t = 100 * i
        out += [("repro.fit", t, t + 90, {"fit": i}),
                ("repro.fit.train", t + 10, t + 50, {"fit": i})]
        if b is not None:
            out.append(("repro.fit.train.program", t + 40, t + 41,
                        {"fit": i, "built": b}))
    return out


def test_every_fit_reused():
    assert _read(_fits(0, 0, 0, 0), 4) == 100.0


def test_some_fits_built():
    assert _read(_fits(1, 0, 0, 0), 4) == 75.0
    assert _read(_fits(2, 0, 1, 0), 4) == 50.0
    assert _read(_fits(1, 1), 2) == 0.0


def test_a_fit_without_its_marker_counts_as_not_reused():
    assert _read(_fits(0, None, 0, 0), 4) == 75.0


def test_no_marker_no_value():
    """A program without the marker: roots and phases only."""
    assert _read(_fits(None, None), 2) is None
    assert _read([], 0) is None


def test_markers_outside_the_counted_fits_do_not_count():
    spans = _fits(0, 0) + [("repro.fit.train.program", 500, 501, {"built": 1})]
    assert _read(spans, 2) == 100.0
    assert _read(_fits(0, 0), 3) is None  # roots are not the window's fits
