#!/usr/bin/env python3
"""Readings the correctness limits of a cell are set from, on the chip.

    python3 bench/control.py --workload <name> --seeds 1,2,3 --seconds 5
    python3 bench/control.py --workload <name> --seeds 1,2,3 --fault half_data

For each seed, in one process: the cell's set-up and a window of
``--seconds`` as a benchmark run makes them, then the compared numbers of
the program against the plain reference (the lower reading) and, unless
``--control 0``, of the control against the reference: the reference
itself, put in the program's place and computed at the next lower matmul
precision, ``high`` (three bf16 passes) for the configuration's ``highest``
float32 (the upper reading).  With ``--fault`` the program runs with that
fault of ``bench/faults.py`` planted, and only its numbers are read.

Every reading goes through the limits of ``bench/checks/<workload>.json``
by the benchmark's own judgement (``run.judge``); one JSON line per seed
gives each number and each verdict.  Exits 1 unless every sound program run
comes out correct and every control and planted fault comes out not
correct.  The benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import contextlib
import gc
import json
import os
import sys

BENCH = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(BENCH))

from bench import run as brun  # noqa: E402  (sets the compile cache path)
from bench import common, faults  # noqa: E402

CONTROL_PRECISION = "high"


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=5.0)
    ap.add_argument("--control", type=int, choices=(0, 1), default=1)
    ap.add_argument("--fault", choices=sorted(faults.FAULTS))
    args = ap.parse_args(argv)
    cell, cfg, traffic, checks, driver, _, _, _ = brun.load_cell(args.workload)
    limits = checks["limits"]
    common.import_repro()
    common.require_tpu(cell["chips"])
    from repro.compat import setup_compilation_cache

    setup_compilation_cache()
    as_expected = True
    for seed in (int(s) for s in args.seeds.split(",")):
        ctx = brun.Ctx(cell["name"], cfg, traffic, seed, args.seconds, False)
        with faults.planted(args.fault) if args.fault else contextlib.nullcontext():
            state = driver.setup(ctx)
            result = driver.window(state, ctx)
            out = driver.outputs(state, ctx)
        del state
        gc.collect()
        nums = driver.compare(out, ctx)
        ok, check = brun.judge(nums, limits, result["failed"])
        row = {"workload": cell["name"], "seed": seed, "fault": args.fault,
               "e2e": result["e2e"], "program": nums, "program_correct": ok,
               "check": check}
        as_expected = as_expected and ok == (args.fault is None)
        if args.control and args.fault is None:
            ctrl = driver.compare(
                driver.reference_outputs(out, ctx, CONTROL_PRECISION), ctx)
            ctrl_ok, _ = brun.judge(ctrl, limits)
            row.update(control=ctrl, control_correct=ctrl_ok)
            as_expected = as_expected and not ctrl_ok
        print("CONTROL " + json.dumps(row), flush=True)
        del out
        gc.collect()
    print(f"control: {'as expected' if as_expected else 'NOT as expected'}",
          flush=True)
    return 0 if as_expected else 1


if __name__ == "__main__":
    sys.exit(main())
