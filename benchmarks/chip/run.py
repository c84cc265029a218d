#!/usr/bin/env python3
"""Run one cell of the chip benchmark once.

    python3 benchmarks/chip/run.py --workload thermal.design --seed 7 \\
        --seconds 30 --trace 0

The cell, its configuration, traffic, driver, reference and metric readers
are found by the names in ``BENCHMARK.json``. Off a TPU, or with fewer chips
than the cell asks for, it exits 3 and prints no result. The last line of
standard output is the result object; ``--trace 1`` reports the cell's
per-layer metrics from a profiler trace instead of its end-to-end metrics.
"""

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(HERE)), "src"))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from chipbench.files import load_benchmark, resolve_cell
    from chipbench.harness import NoChip, run_cell

    cell = resolve_cell(load_benchmark(), args.workload)
    try:
        run_cell(cell, args.seed, args.seconds, bool(args.trace), T0)
    except NoChip as e:
        print(f"run.py: {e}; nothing was run", file=sys.stderr)
        return 3
    return 0


if __name__ == "__main__":
    sys.exit(main())
