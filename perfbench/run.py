"""Run one cell of the benchmark once.

    python3 perfbench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Prints one JSON object as the last line of standard output; exits 2 and
prints no result where JAX finds no TPU or fewer chips than the cell asks
for.
"""

from __future__ import annotations

import time

PROCESS_T0 = time.perf_counter()  # set-up is counted from here

import argparse  # noqa: E402
import importlib  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    parser.add_argument("--keep-trace", default=None,
                        help="also write the reduced trace as JSON to this path")
    parser.add_argument("--keep-xplane", default=None,
                        help="also copy the raw profiler output into this directory")
    args = parser.parse_args(argv)

    from perfbench import harness

    cell = harness.load_cell(args.workload)
    entry = importlib.import_module("perfbench.entries." + cell.traffic["entry"])
    result, checks = entry.run(
        cell,
        seed=args.seed,
        seconds=args.seconds,
        trace=bool(args.trace),
        process_t0=PROCESS_T0,
        keep_trace=args.keep_trace,
        keep_xplane=args.keep_xplane,
    )
    harness.emit(result, checks)
    return 0


if __name__ == "__main__":
    sys.exit(main())
