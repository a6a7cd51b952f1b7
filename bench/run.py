"""Run one benchmark cell once, on the chip, and print its result line.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout. The cells, their configurations, traffic
mixes and metrics are listed in ``BENCHMARK.json``. The run needs a TPU with
as many chips as the cell asks for; without one it prints no result and
exits non-zero. The last line of standard output is one JSON object
(``correct``, ``attempted``, ``failed``, ``metrics``, ``device``, and with
``--trace 1`` a ``breakdown``), with the numbers compared against the
reference last, under ``checks``; the same numbers, each beside its limit,
are the last lines of standard error.
"""
import os
import sys
import time

T_START = time.perf_counter()
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

if __name__ == "__main__":
    from bench.lib import harness

    sys.exit(harness.main(t_start=T_START))
