"""The control must come out not correct: the plain reference put in the
program's place at the next lower matmul precision (``high`` for the
configuration's ``highest`` float32), judged by the cell's own limits, at
the cell's own size.  The CPU computes every float32 matmul in full
whatever precision is asked, so the control exists only on a TPU: run

    JAX_PLATFORMS=tpu python -m pytest bench/tests/test_control.py

on a chip.  The test drives ``control.py`` in a child process, so that this
process never holds the chip."""
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
ON_CHIP = os.environ.get("JAX_PLATFORMS") == "tpu"
WORKLOAD = "broadcast-refit"
SEED = 2**31 + 977


@pytest.mark.skipif(not ON_CHIP, reason="the control's lower precision "
                    "exists only on a TPU (JAX_PLATFORMS=tpu)")
def test_control_is_not_correct():
    r = subprocess.run(
        [sys.executable, os.path.join("bench", "control.py"), "--workload",
         WORKLOAD, "--seeds", str(SEED), "--seconds", "2"],
        cwd=ROOT, capture_output=True, text=True, timeout=900)
    print(r.stdout[-4000:])
    print(r.stderr[-4000:], file=sys.stderr)
    assert r.returncode == 0, "the program was not correct or the control was"
    assert '"control_correct": false' in r.stdout
