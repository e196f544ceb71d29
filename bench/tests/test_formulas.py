"""The roofline formulas against hand-counted shapes, and the peak table."""
import importlib.util
import os

import pytest

from bench import trace

METRICS = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                       "metrics")


def metric(name):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(METRICS, name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_qgram_packed_work_by_hand():
    # 2 machines of 2 points, d=1, R=3: pairs (0,1), (1,0); each a 2x2 block
    # of 1-long inner products (2 flops each); one word per row, 8-level
    # tables, 2x1 projected rows per pair, 2 mask entries per machine
    cfg = {"m": 2, "n_train": 4, "d": 1, "bits_per_sample": 3, "max_bits": 12}
    flops, nbytes = metric("qgram_packed_roofline").work(cfg)
    assert flops == 2 * (2 * 2 * 2 * 1)
    assert nbytes == 4 * (2 * 2 * 1 + 2 * 1 * 8 + 2 * 2 * 1 + 2 * 2 + 2 * 2 * 2)


def test_qgram_packed_work_at_the_cell_size():
    cfg = {"m": 40, "n_train": 10000, "d": 8, "bits_per_sample": 25,
           "max_bits": 12}
    flops, nbytes = metric("qgram_packed_roofline").work(cfg)
    assert flops == 1560 * 2 * 250 * 250 * 8          # 1.56 GFLOP
    assert nbytes == 4 * (40 * 250 + 40 * 8 * 4096 + 1560 * 250 * 8
                          + 40 * 250 + 1560 * 250 * 250)


def test_least_time_takes_the_larger_bound():
    peak = trace.peak_of("TPU v5 lite")
    ctx = trace.MetricCtx(None, {}, {}, {}, peak)
    t, bound = ctx.least_time(197e12, 1.0)
    assert bound == "compute" and t == pytest.approx(1.0)
    t, bound = ctx.least_time(1.0, 819e9)
    assert bound == "memory" and t == pytest.approx(1.0)


def test_unknown_device_is_an_error():
    with pytest.raises(KeyError):
        trace.peak_of("TPU v9 imaginary")
