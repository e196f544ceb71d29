"""The ``qgram_decodes.fit`` reader: the per-fit sum of the ``qgram_decodes``
stats on the program's spans, on hand-made traces and on a refit recorded
on a TPU v5e before the program counted its decodes (no value, never 0)."""
import gzip
import importlib.util
import os
import shutil

import pytest

from bench import program_trace, trace

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SARCOS = os.path.join(BENCH, "testdata", "sarcos", "v5e_sarcos_refit.xplane.pb.gz")


def read(tr, fits):
    spec = importlib.util.spec_from_file_location(
        "qgram_decodes_fit", os.path.join(BENCH, "metrics", "qgram_decodes.fit.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read(trace.MetricCtx(tr, {"fits": fits}, {}, {}, {}))


def _trace(spans):
    return program_trace.ProgramTrace([[]], [[]], [], 0, 1000,
                                      program_spans=list(spans))


def _fit(fit, t0, groups, per_group, column):
    """One fit's spans: the wire span with machine 0's column's decodes and
    ``groups`` group spans with ``per_group`` each."""
    out = [("repro.fit", t0, t0 + 400, {"fit": fit}),
           ("repro.fit.wire", t0, t0 + 50,
            {"fit": fit, "qgram_decodes": column}),
           ("repro.fit.factors", t0 + 100, t0 + 390,
            {"fit": fit, "groups": groups, "receivers": 4})]
    out += [("repro.fit.factors.group", t0 + 110 + 20 * g, t0 + 120 + 20 * g,
             {"fit": fit, "qgram_decodes": per_group}) for g in range(groups)]
    return out


def test_decodes_are_the_per_fit_sum_of_the_stats():
    # sarcos-refit's counts: 40 x 9 for machine 0's column, 10 groups of
    # 4 receivers x 40 senders x 9
    spans = _fit(1, 0, 10, 4 * 40 * 9, 40 * 9) + _fit(2, 500, 10, 4 * 40 * 9,
                                                      40 * 9)
    assert read(_trace(spans), fits=2) == pytest.approx(14_760.0)
    # the window counted another number of fits: no value
    assert read(_trace(spans), fits=3) is None


def test_spans_outside_the_counted_fits_do_not_count():
    spans = _fit(1, 0, 1, 40 * 40 * 2, 40 * 2)
    stray = ("repro.fit.factors.group", 2000, 2010, {"qgram_decodes": 999})
    assert read(_trace(spans + [stray]), fits=1) == pytest.approx(3_280.0)


def test_no_stat_no_value():
    spans = [("repro.fit", 0, 400, {"fit": 1}),
             ("repro.fit.wire", 0, 50, {"fit": 1}),
             ("repro.fit.factors", 100, 390, {"fit": 1, "groups": 1}),
             ("repro.fit.factors.group", 110, 380, {"fit": 1})]
    assert read(_trace(spans), fits=1) is None
    assert read(_trace([]), fits=1) is None


def test_a_refit_recorded_before_the_count_gives_no_value(tmp_path):
    out = tmp_path / "refit.xplane.pb"
    with gzip.open(SARCOS) as s, open(out, "wb") as d:
        shutil.copyfileobj(s, d)
    tr = program_trace.load(str(out), chips=1)
    assert tr.roots()
    assert read(tr, fits=len(tr.roots())) is None
