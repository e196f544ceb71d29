"""The three readers of the broadcast factor build (``device_s.fit.factors``,
``factors_roofline``, ``factor_groups.fit``) and ``factor_build.work``: the
work formula against hand counts at both configurations, the readers on
hand-made traces, on the refit recorded before the build program existed
(``bench/testdata/fit``: no value, never 0) and on a refit of the
``sarcos-refit`` cell recorded on a TPU v5e by ``record_cell_trace.py``
(``bench/testdata/sarcos``)."""
import gzip
import importlib.util
import json
import os
import shutil

import pytest

from bench import factor_build, program_trace, trace

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
OLD_FIT = os.path.join(BENCH, "testdata", "fit", "v5e_fit.xplane.pb.gz")
SARCOS = os.path.join(BENCH, "testdata", "sarcos", "v5e_sarcos_refit.xplane.pb.gz")
READERS = ("device_s.fit.factors", "factors_roofline", "factor_groups.fit")
PEAK = {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def config(name):
    with open(os.path.join(BENCH, "configs", name + ".json")) as f:
        return json.load(f)


def reader(name):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(BENCH, "metrics", name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def read(name, tr, cfg, fits=1):
    return reader(name).read(trace.MetricCtx(tr, {"fits": fits}, cfg, {}, PEAK))


def unzip(src, tmp_path):
    out = tmp_path / os.path.basename(src)[: -len(".gz")]
    with gzip.open(src) as s, open(out, "wb") as d:
        shutil.copyfileobj(s, d)
    return program_trace.load(str(out), chips=1)


# -- the work of one fit's build ----------------------------------------------


def test_work_by_hand():
    # 2 machines of 2 points: K=2 centers, N=4 columns per receiver
    flops, nbytes = factor_build.work({"m": 2, "n_train": 4})
    assert flops == pytest.approx(2 * (72 + 16 / 3 + 16 + 32 + 32 + 8
                                       + 8 / 3 + 16))
    assert nbytes == 2 * 4 * (16 + 12 + 4 + 2)


# counted by hand per receiver, times m = 40: kin40k K=250, N=10,000 gives
# 1,921,125,000 flops and 20,791,000 bytes; sarcos K=1,113 (ceil(44,484 /
# 40)), N=44,484 gives 167,298,988,941 flops and 411,133,152 bytes
@pytest.mark.parametrize("name,flops,nbytes", [
    ("kin40k-m40-broadcast-r25", 76_845_000_000, 831_640_000),
    ("sarcos-m40-broadcast-r64", 6_691_959_557_640, 16_445_326_080),
])
def test_work_at_the_configurations(name, flops, nbytes):
    assert factor_build.work(config(name)) == (pytest.approx(flops), nbytes)


# -- hand-made traces ---------------------------------------------------------


QGRAM_OP = ('%vmap_vmap_jit_qgram_packed_pallas___.3 = f32[8] custom-call(), '
            'custom_call_target="tpu_custom_call"')


def _trace(ops, modules, spans=()):
    return program_trace.ProgramTrace(
        [[trace.Op(n, s, e) for n, s, e in ops]], [[]], [], 0, 1000,
        modules=[trace.Op(n, s, e) for n, s, e in modules],
        program_spans=list(spans))


def test_build_time_is_its_programs_less_the_kernel():
    tr = _trace(
        ops=[("%fusion.1 = f32[8] fusion()", 0, 50),       # another program
             ("%while.2 = f32[8] while()", 100, 400),      # the build's loop
             (QGRAM_OP, 120, 200),                          # its wire products
             ("%fusion.3 = f32[8] fusion()", 200, 300)],
        modules=[("jit_train_scan(1)", 0, 60),
                 ("jit_broadcast_factor_group(7)", 100, 400)])
    assert factor_build.device_s(tr) == pytest.approx(220e-9)
    cfg = {"m": 2, "n_train": 4}
    assert read("device_s.fit.factors", tr, cfg, fits=2) == pytest.approx(110e-9)
    least = max(x / p for x, p in zip(factor_build.work(cfg),
                                      (PEAK["flops_per_s"], PEAK["hbm_bytes_per_s"])))
    assert read("factors_roofline", tr, cfg, fits=2) == pytest.approx(
        100 * 2 * least / 220e-9)


def test_groups_are_the_mean_stat_of_the_factor_spans():
    spans = [("repro.fit", 0, 400, {"fit": 1}),
             ("repro.fit.factors", 100, 300, {"fit": 1, "groups": 10,
                                              "receivers": 4}),
             ("repro.fit", 500, 900, {"fit": 2}),
             ("repro.fit.factors", 600, 800, {"fit": 2, "groups": 8,
                                              "receivers": 5})]
    tr = _trace([], [], spans)
    assert read("factor_groups.fit", tr, {}, fits=2) == pytest.approx(9.0)
    # the window counted another number of fits: no value
    assert read("factor_groups.fit", tr, {}, fits=3) is None


def test_no_build_program_no_value():
    tr = _trace([("%fusion.1 = f32[8] fusion()", 0, 50)],
                [("jit_train_scan(1)", 0, 60)],
                [("repro.fit", 0, 100, {"fit": 1}),
                 ("repro.fit.factors", 60, 90, {"fit": 1})])
    for name in READERS:
        assert read(name, tr, {"m": 2, "n_train": 4}) is None, name


# -- recorded on a TPU v5e ------------------------------------------------------


def test_the_refit_recorded_before_the_build_program_gives_no_value(tmp_path):
    tr = unzip(OLD_FIT, tmp_path)
    cfg = dict(config("kin40k-m40-broadcast-r25"), m=8, n_train=480)
    for name in READERS:
        assert read(name, tr, cfg) is None, name


def test_a_recorded_sarcos_refit_gives_values(tmp_path):
    tr = unzip(SARCOS, tmp_path)
    cfg = config("sarcos-m40-broadcast-r64")
    build = read("device_s.fit.factors", tr, cfg)
    assert 0 < build < 30
    assert 0 < read("factors_roofline", tr, cfg) <= 100
    assert read("factor_groups.fit", tr, cfg) > 1
    # the wire products' kernel runs inside the build and is still found
    assert tr.kernel_time("qgram_packed_pallas") > 0
