"""Every cell, end to end on the CPU at a small size (the TPU check skipped):
the result line has the contract's keys, its metrics are the cell's
end-to-end metrics, and the program agrees with the reference."""
import json
import os
import shutil
import subprocess
import sys

import pytest

from bench import run as brun

ROOT = brun.ROOT
WORKLOADS = [w["name"] for w in json.load(open(os.path.join(ROOT, "BENCHMARK.json")))["workloads"]]


def last_json(out: str) -> dict:
    return json.loads(out.strip().splitlines()[-1])


@pytest.mark.parametrize("workload", WORKLOADS)
def test_cell_runs_correct(cpu_run, capsys, workload):
    cpu_run(workload, seed=2**31 + 11, seconds=1.0)
    line = last_json(capsys.readouterr().out)
    assert {"correct", "attempted", "failed", "metrics", "device"} <= set(line)
    assert list(line)[-1] == "check"
    cell, *_, e2e, _, _ = brun.load_cell(workload)
    assert set(line["metrics"]) == {m["name"] for m in e2e}
    assert line["correct"], line["check"]
    assert line["attempted"] > 0 and line["failed"] == 0
    assert line["check"], "a cell compares at least one number"


def _run_cli(args, cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.update(env_extra or {})
    return subprocess.run([sys.executable, "bench/run.py"] + args, cwd=cwd,
                          env=env, capture_output=True, text=True, timeout=300)


def test_without_a_tpu_no_result():
    r = _run_cli(["--workload", WORKLOADS[0], "--seed", "1", "--seconds", "1",
                  "--trace", "0"], ROOT)
    assert r.returncode != 0
    assert "no TPU" in r.stderr
    assert not r.stdout.strip().startswith("{")


def test_benchmark_alone_no_result(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "bench"), tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    r = _run_cli(["--workload", WORKLOADS[0], "--seed", "1", "--seconds", "1",
                  "--trace", "0"], tmp_path)
    assert r.returncode != 0
    assert r.stdout.strip() == "" or not r.stdout.strip().splitlines()[-1].startswith("{")
