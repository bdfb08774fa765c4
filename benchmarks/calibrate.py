#!/usr/bin/env python3
"""Readings for the limits of ``correct``, on the chip, in one process.

    python3 benchmarks/calibrate.py --workload <name> --seeds 1 2 3 ... \
        [--control-seeds 3] [--seconds 1] [--stand-ins-only] [--fault <name>]

For every seed: the program's warm-up step and a short window at the cell's
own size through the cell's driver, then the numbers that ``correct``
compares (the lower readings). For the first ``--control-seeds`` seeds also
the numbers of every stand-in the driver module lists (``STAND_INS``; the
control, which is the reference at bfloat16 in the program's place, and the
faults planted in the reference put there: the upper readings). With
``--stand-ins-only`` the program does not run; with ``--fault`` it runs with
that fault of ``tests/faults.py`` planted under the timed path. The data come
from the driver module's ``make_problem``. One JSON line a seed on stdout;
nothing here is a benchmark result and the driver never runs it.
"""

from __future__ import annotations

import argparse
import contextlib
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))


def main() -> int:
    from benchmarks import run as harness

    parser = argparse.ArgumentParser()
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seeds", type=int, nargs="+", required=True)
    parser.add_argument("--control-seeds", type=int, default=3)
    parser.add_argument("--seconds", type=float, default=1.0)
    parser.add_argument("--stand-ins-only", action="store_true",
                        help="skip the program: the reference and the stand-ins alone")
    parser.add_argument("--fault", help="run the program with this fault of tests/faults.py planted")
    args = parser.parse_args()

    start = harness.Start(args.workload, program="calibrate.py")
    config, tag = start.config, start.tag
    from benchmarks.traffic.steps import Window

    def say(message: str) -> None:
        sys.stderr.write(f"{tag} {message}\n")
        sys.stderr.flush()

    for i, seed in enumerate(args.seeds):
        t0 = time.perf_counter()
        module, driver = start.driver(seed, say)
        line = {"workload": args.workload, "seed": seed}
        if args.stand_ins_only:
            problem = module.make_problem(config, seed)
            kept = module.reference_run(config, start.traffic, problem, say)
        else:
            driver.prepare()
            window = Window(seconds=args.seconds)
            planted = contextlib.nullcontext()
            if args.fault:
                from benchmarks.tests import faults

                planted = faults.planted(args.fault, module)
                line["fault"] = args.fault
            with planted:
                driver.run(window)
            line.update(steps=window.steps, step_s=window.length / window.steps,
                        step_seconds=window.step_seconds,
                        program_s=time.perf_counter() - t0)
            driver.collect()
            problem = driver.problem
            driver.release()
            t0 = time.perf_counter()
            line["program"] = driver.check()
            line["reference_s"] = time.perf_counter() - t0
            kept = driver.kept_reference
        if i < args.control_seeds:
            for stand_in in module.STAND_INS:
                t0 = time.perf_counter()
                line[stand_in] = module.control_numbers(
                    config, problem, *kept, stand_in=stand_in, log=say)
                line[f"{stand_in}_s"] = time.perf_counter() - t0
        print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
