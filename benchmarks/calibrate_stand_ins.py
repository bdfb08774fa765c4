#!/usr/bin/env python3
"""Upper readings for the limits of ``correct``: the stand-ins alone, on the
chip, in one process.

    python3 benchmarks/calibrate_stand_ins.py --workload <name> --seeds 1 2 3

``calibrate.py --stand-ins-only`` for a traffic driver that brings its own
data generator and its own list of stand-ins: the driver module's
``make_problem(config, seed)``, ``reference_run`` and every name in its
``STAND_INS``, each put in the program's place by its ``control_numbers``. The
program does not run. One JSON line a seed on stdout; nothing here is a
benchmark result and the driver never runs it.
"""

from __future__ import annotations

import argparse
import importlib
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
    args = parser.parse_args()

    start = harness.Start(args.workload, program="calibrate_stand_ins.py")
    module = importlib.import_module(f"benchmarks.traffic.{start.traffic['driver']}")

    def say(message: str) -> None:
        sys.stderr.write(f"{start.tag} {message}\n")
        sys.stderr.flush()

    for seed in args.seeds:
        problem = module.make_problem(start.config, seed)
        t0 = time.perf_counter()
        kept = module.reference_run(start.config, problem, say)
        line = {"workload": args.workload, "seed": seed,
                "reference_s": time.perf_counter() - t0, "reference": kept[1][1]}
        for stand_in in module.STAND_INS:
            t0 = time.perf_counter()
            line[stand_in] = module.control_numbers(
                start.config, problem, *kept, stand_in=stand_in, log=say)
            line[f"{stand_in}_s"] = time.perf_counter() - t0
        print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
