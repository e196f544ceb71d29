"""The trace reduction, on a small trace recorded on a TPU v5e
(``bench/testdata``, made by ``record_trace.py``) and on hand-made events."""
import glob
import os

import pytest

from bench import trace

TESTDATA = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                        "testdata")
GRAM = r"(?<![a-z])gram_pallas"


@pytest.fixture(scope="module")
def recorded():
    files = glob.glob(os.path.join(TESTDATA, "*.xplane.pb"))
    assert files, "bench/testdata holds the recorded trace"
    return trace.load(files[0], chips=1)


def test_window_is_the_window_span(recorded):
    # three queries with 20 ms sleeps between them, then five matmuls
    assert 0.06 < recorded.window_s() < 5.0
    assert recorded.span_count("query") == 3
    assert recorded.span_count("fit") == 1


def test_busy_time_within_the_window(recorded):
    busy = recorded.busy_s()
    assert 0 < busy < recorded.window_s()


def test_kernel_time_by_name(recorded):
    t = recorded.kernel_time(GRAM)
    assert t > 0
    assert recorded.kernel_time(r"qgram_packed_pallas") == 0
    assert t <= recorded.device_time_in_spans("query") + 1e-12


def test_device_time_inside_spans(recorded):
    q = recorded.device_time_in_spans("query")
    f = recorded.device_time_in_spans("fit")
    assert q > 0 and f > 0
    assert q + f <= recorded.busy_s() + 1e-12


def test_idle_gaps_name_what_the_host_did(recorded):
    b = recorded.breakdown()
    assert 0 < len(b["device_ops"]) <= 10 and 0 < len(b["idle_gaps"]) <= 10
    longest = max(g[1] for g in b["idle_gaps"])
    assert longest >= 0.015  # the 20 ms host sleeps between queries
    assert all(isinstance(g[0], str) for g in b["idle_gaps"])
    assert recorded.collective_s() is None


def _ops(*spans):
    return [trace.Op(name, s, e) for name, s, e in spans]


def test_collective_time_and_its_exposed_part():
    ops = _ops(("%fusion.1 = f32[8] fusion(...)", 0, 10),
               ("%all-gather.2 = f32[8] all-gather(...)", 5, 20),
               ("%fusion.3 = f32[8] fusion(...)", 15, 18))
    t = trace.Trace([ops], [[]], [], 0, 30)
    total, exposed = t.collective_s()
    assert total == pytest.approx(15e-9)
    assert exposed == pytest.approx(7e-9)  # 10..15 and 18..20
    assert t.busy_s() == pytest.approx(20e-9)


def test_interval_helpers():
    assert trace._union([(0, 2), (1, 3), (5, 6)]) == [[0, 3], [5, 6]]
    assert trace._intersect([[0, 3], [5, 6]], [[2, 5.5]]) == pytest.approx(1.5)
    assert trace._subtract([[0, 10]], [[2, 3], [5, 6]]) == [[0, 2], [3, 5], [6, 10]]


def test_kernel_names_are_matched_on_the_instruction():
    text = ('%vmap_jit_gram_pallas__.2 = f32[8,128,256] custom-call(...), '
            'custom_call_target="tpu_custom_call"')
    t = trace.Trace([_ops((text, 0, 4), ("%qgram_pallas.1 = f32[8] custom-call(...), "
                                         'custom_call_target="tpu_custom_call"', 4, 9))],
                    [[]], [], 0, 10)
    assert t.kernel_time(GRAM) == pytest.approx(4e-9)
