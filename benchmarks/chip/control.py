"""Readings for the limits of ``correct``: the program's numbers on many seeds
and the control's on some, at the cell's own size, in one process.

    python benchmarks/chip/control.py --workload <cell> --seeds 1-12 \
        --control-seeds 1-3 [--seconds 2]

For each seed the cell's driver loads that seed's weights and inputs, runs a
short window at the cell's own load (whole batches or steps, at least one),
and reads the numbers ``correct`` compares against the plain reference; on
the control seeds also the control's (the reference computed in fp8 in the
program's place).  One JSON line per seed on standard output.  Benchmark runs
never run this.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path[:0] = [str(HERE), str(HERE.parents[1] / "src")]

import harness  # noqa: E402


def seeds(text: str):
    out = []
    for part in text.split(","):
        a, _, b = part.partition("-")
        out += list(range(int(a), int(b or a) + 1))
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=seeds, required=True)
    ap.add_argument("--control-seeds", type=seeds, default=[])
    ap.add_argument("--seconds", type=float, default=1.0)
    args = ap.parse_args(argv)

    cell = harness.cell(args.workload)
    devices = harness.require_devices(cell.chips)
    harness.configure_jax()
    run = harness.Run(cell, args.seeds[0], args.seconds, False, devices)
    driver = harness.load_module("drivers", cell.traffic["driver"]).Driver(run)
    driver.setup()
    for i, seed in enumerate(args.seeds):
        if i:
            run.seed = seed
            driver.load()
        driver.window()
        r = driver.readings(control=seed in args.control_seeds)
        print(json.dumps(dict(seed=seed, **r)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
