"""Readings that the limits of a cell's compared numbers are set from, on
the chip, in one process:

    python3 bench/calibrate.py --workload <name> --seeds 1,2,... \
        [--control-seeds 7,8,9] [--control-levels high,bf16] [--seconds 0.5]

For each of ``--seeds``, one run of the cell as the benchmark makes it (a
short window), printing the numbers compared: the lower readings. For each
of ``--control-seeds``, the control: the reference put in the program's
place and computed in the precision below the configuration's, set against
the reference itself: the upper readings. One JSON line per run on standard
output. The benchmark's own runs do not run this.
"""
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]


def main() -> int:
    import argparse
    import importlib

    from bench.lib import harness

    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--seconds", type=float, default=0.5)
    ap.add_argument("--control-levels", default="",
                    help="the control's precisions, comma-separated, where the driver "
                         "offers more than one (default: the driver's own)")
    args = ap.parse_args()
    spec = harness.load_json(harness.ROOT / "BENCHMARK.json")
    seeds = [int(s) for s in args.seeds.split(",") if s]
    controls = [int(s) for s in args.control_seeds.split(",") if s]
    cell = harness.build_cell(spec, args.workload, 0, args.seconds, False)
    devices = harness.require_chips(cell.chips)
    harness.enable_compile_cache()
    driver = importlib.import_module(f"bench.lib.drivers.{cell.traffic['driver']}")
    for seed in seeds:
        cell.seed = seed
        t0 = time.perf_counter()
        out = driver.run(cell, devices)
        print(json.dumps({"kind": "program", "seed": seed, "seconds": time.perf_counter() - t0,
                          "checks": {c.name: c.value for c in out.checks}}), flush=True)
    levels = {"levels": args.control_levels.split(",")} if args.control_levels else {}
    for seed in controls:
        cell.seed = seed
        for level, checks in driver.control(cell, devices, **levels).items():
            print(json.dumps({"kind": "control", "seed": seed, "level": level,
                              "checks": {c.name: c.value for c in checks}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
