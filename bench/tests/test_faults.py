"""A run with the timed path broken underneath must come out not correct:
the harness's look for a chip is skipped and everything else runs, with one
fault of ``bench/faults.py`` planted in the program for the whole run."""
import json

import pytest

from bench import faults

WORKLOAD = "broadcast-refit"


def last_json(out: str) -> dict:
    return json.loads(out.strip().splitlines()[-1])


@pytest.mark.parametrize("fault", sorted(faults.FAULTS))
def test_fault_is_not_correct(cpu_run, capsys, fault):
    with faults.planted(fault):
        cpu_run(WORKLOAD, seed=5)
    line = last_json(capsys.readouterr().out)
    assert line["correct"] is False, line["check"]


def test_sound_run_is_correct(cpu_run, capsys):
    """The same run with no fault planted passes, so each fault above is
    what fails its run."""
    cpu_run(WORKLOAD, seed=5)
    line = last_json(capsys.readouterr().out)
    assert line["correct"] is True, line["check"]


def test_control_tool_judges_by_the_limits(small, capsys):
    """``control.py`` puts every reading through the harness's own limits:
    a planted fault reads not correct there too, and the tool says so."""
    from bench import control

    rc = control.main(["--workload", WORKLOAD, "--seeds", "5",
                       "--seconds", "1", "--fault", "no_exchange"])
    rows = [json.loads(ln[len("CONTROL "):])
            for ln in capsys.readouterr().out.splitlines()
            if ln.startswith("CONTROL ")]
    assert rc == 0 and len(rows) == 1
    assert rows[0]["program_correct"] is False
    assert set(rows[0]["check"]) == set(rows[0]["program"])
