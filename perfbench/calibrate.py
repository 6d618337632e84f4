"""Readings from which a cell's limits are set: the numbers ``correct``
compares, for the program and for the control, on many seeds in one
process (set-up is paid per seed, compiles once).

    python3 perfbench/calibrate.py --workload <name> --seeds 1,2,3 \
        --seconds 5

For each seed it runs the cell's job with a short window at the cell's own
load, compares the window's output with the reference, and reads the
control (the reference computed a precision below the configuration's, in
the program's place) on the same inputs. One JSON line per seed, then a
summary: the largest program reading (lower) and the smallest control
reading (upper) of each number. The benchmark's own runs never run this.
"""
import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "src"))

from perfbench import harness  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated seeds")
    ap.add_argument("--seconds", type=float, default=5.0)
    args = ap.parse_args(argv)

    cell = harness.load_cell(args.workload)
    harness.setup_compile_cache()
    devices = harness.require_devices(cell.chips)
    job = harness.load_module("jobs", cell.config["job"])
    program, control = {}, {}
    for seed in (int(s) for s in args.seeds.split(",")):
        t0 = harness.now()
        out = job.run(harness.Run(cell, seed, args.seconds, False, t0,
                                  devices, control=True))
        print(json.dumps({"seed": seed, "attempted": out.attempted,
                          "failed": out.failed, "program": out.values,
                          "control": out.control,
                          "seconds": harness.now() - t0}), flush=True)
        for k, v in out.values.items():
            program.setdefault(k, []).append(v)
        for k, v in out.control.items():
            control.setdefault(k, []).append(v)
    print(json.dumps({"workload": args.workload,
                      "lower": {k: max(v) for k, v in program.items()},
                      "upper": {k: min(v) for k, v in control.items()},
                      "program": program, "control": control}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
