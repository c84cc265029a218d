#!/usr/bin/env python3
"""Readings that the limits of a cell's compared numbers are set from.

    python3 benchmarks/chip/controls/readings.py --workload qwen05.serve \\
        --seeds 1001-1012 --control precision:2001-2003 \\
        --control drop_commit:2101-2103 --units 1

Sets the cell up once in one process, then for each seed drives ``--units``
units of the cell's own work through its timed path and prints the numbers
it compares. For each ``--control KIND:SEEDS`` it prints the same numbers
with the control in place: ``precision`` puts the plain reference in the
precision below the configuration's in the program's place; any other kind
is a fault that the cell's driver plants in the timed path (the serving
driver's ``drop_commit`` and ``retrace``). Run it on the chip, at the
cell's own size. One JSON object per line on standard output.
"""

import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))
sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(os.path.dirname(HERE))), "src"))


def seed_range(text: str):
    out = []
    for part in text.split(","):
        if "-" in part:
            a, b = part.split("-")
            out += list(range(int(a), int(b) + 1))
        elif part:
            out.append(int(part))
    return out


def readings(cell, seeds, controls, units, options=None, emit=print):
    """Set the cell up once; yield one record per seed. ``controls`` is a
    list of (kind, seeds)."""
    from chipbench.files import load_module

    ref = load_module(cell.reference_path)
    first = seeds[0] if seeds else controls[0][1][0]
    driver = load_module(cell.driver_path).Driver(
        cell, first, ref, dict(options or {}))
    driver.setup()
    out = []
    for kind, group in [(None, seeds)] + list(controls):
        driver.fault = kind if kind not in (None, "precision") else None
        for seed in group:
            driver.reseed(seed)
            t = time.perf_counter()
            for _ in range(units):
                driver.run_unit()
            rec = {"seed": seed, "control": kind,
                   "unit_s": (time.perf_counter() - t) / units,
                   "numbers": driver.numbers(control=kind == "precision")}
            emit(json.dumps(rec))
            out.append(rec)
    return out


def control_arg(text: str):
    kind, _, seeds = text.partition(":")
    return kind, seed_range(seeds)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control", type=control_arg, action="append", default=[],
                    metavar="KIND:SEEDS")
    ap.add_argument("--units", type=int, default=1)
    args = ap.parse_args(argv)

    import jax
    from chipbench.files import load_benchmark, resolve_cell
    from repro.launch.compile_cache import enable_compile_cache

    if jax.devices()[0].platform != "tpu":
        print("readings.py: JAX found no TPU; nothing was run", file=sys.stderr)
        return 3
    enable_compile_cache()
    cell = resolve_cell(load_benchmark(), args.workload)
    readings(cell, seed_range(args.seeds), args.control, args.units,
             emit=lambda s: print(s, flush=True))
    return 0


if __name__ == "__main__":
    sys.exit(main())
