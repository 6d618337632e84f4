"""Runs one benchmark cell on the chips of this machine.

    python3 perfbench/run.py --workload <name> --seed <n> --seconds <s> \
        --trace <0|1>

Set-up (weights from the seed, warm-up of the cell's own shapes, compiles)
counts as ``setup_s``; then the cell's units run back to back for
``--seconds`` and the window closes at the first unit boundary after that.
``--trace 1`` runs the same window under the profiler and reports the
per-layer metrics instead of the end-to-end ones. After the window the
run's output is compared with a plain reference; the last line printed is
one JSON object with ``correct``, ``attempted``, ``failed``, ``metrics``,
``device`` and, last, ``checks`` (each compared number with its limit).

Exits non-zero, with no result, when JAX finds no TPU or fewer chips than
the cell asks for.
"""
import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "src"))

from perfbench import harness  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    cell = harness.load_cell(args.workload)
    harness.setup_compile_cache()
    devices = harness.require_devices(cell.chips)
    job = harness.load_module("jobs", cell.config["job"])
    run = harness.Run(cell, args.seed, args.seconds, bool(args.trace),
                      T_PROCESS, devices)
    outcome = job.run(run)
    print(f"compiles inside the window: "
          f"{outcome.record.get('compiles_in_window')}", file=sys.stderr)
    harness.print_result(harness.result_line(cell, run, outcome))
    return 0


if __name__ == "__main__":
    sys.exit(main())
