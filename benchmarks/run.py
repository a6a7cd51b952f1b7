"""Benchmark harness — one module per paper table/figure.

Each suite streams ``(name, us_per_call, derived)`` rows through a
composite tracker (repro.obs): stdout CSV (the historical format), an
optional JSONL event log, and a schema-versioned ``BENCH_<suite>.json``
perf artifact with provenance (git rev, jax version, device kind, seed)
and regression gates for ``benchmarks/bench_diff.py``. Modules:

  fig1_convergence   Fig. 1/7   EF21-P vs MARINA-P (same/ind/perm), const/Polyak
  table2_sigma       Table 2    sigma_A per (n, noise scale), paper sizes
  stepsize_grid      Table 3/6  tuned Polyak factor grid
  comm_complexity    Cor. 1/2   rounds-to-eps vs closed-form complexity
  kernel_bench       —          Pallas kernel (interpret) microbenchmarks
  wire_bench         DESIGN §3  wire codec throughput (also a standalone CLI
                                with measured-vs-analytic parity checks);
                                also provides the ``encode`` suite — fused
                                on-device encode roofline + byte-identity
                                gate (DESIGN §11)
  transport_bench    DESIGN §8  frame/CRC throughput + clean-vs-degraded
                                MARINA-P chaos run (goodput, rounds_ratio)
  serve_bench        DESIGN §10 DecodeEngine prefill/decode span p50/p99
                                latency + tokens/s (smoke config)
  scenario_matrix    DESIGN §9  (algorithm x stepsize x client-mix) fleet
                                cells, one BENCH_scenario_<cell>.json each
  roofline_report    §Roofline  dominant-term bound per (arch x shape) dry-run

Select subsets: ``python -m benchmarks.run fig1 table2 ...`` (default: all
except roofline_report when no dry-run records exist). A suite that raises
prints its traceback, emits a ``<suite>/FAILED`` row, skips its BENCH
artifact, and the run exits non-zero — CI cannot green-light a broken
benchmark.
"""
from __future__ import annotations

import argparse
import os
import sys
import traceback

# Regression gates baked into each suite's BENCH artifact (self-describing
# baselines — bench_diff reads them back). Timing tolerances are loose
# (5x) because CI machines vary; deterministic deriveds are tight.
_TIME = {"pattern": "*", "field": "us_per_call", "direction": "lower", "rtol": 4.0}
GATES = {
    "kernels": [_TIME],
    "wire": [
        _TIME,
        # derived value = codec throughput in GB/s (higher is better)
        {"pattern": "wire/*", "field": "value", "direction": "higher", "rtol": 0.9},
    ],
    "encode": [
        _TIME,
        # host-codec GB/s floor (device interpret rows are covered by _TIME;
        # their wall-clock varies too much across CI machines for a
        # throughput gate)
        {"pattern": "encode/host_*", "field": "value", "direction": "higher", "rtol": 0.9},
        # fused streams must equal the host codec's bytes — a correctness
        # gate riding the perf artifact (1.0 = identical, exact match)
        {"pattern": "encode/byte_identical", "field": "value", "direction": "eq", "rtol": 0.0},
    ],
    "table2": [
        # sigma_A is deterministic for a fixed seed/platform
        {"pattern": "table2/*", "field": "value", "direction": "eq", "rtol": 0.05},
    ],
    "fig1": [_TIME],
    "stepsize_grid": [_TIME],
    "comm_complexity": [_TIME],
    "roofline": [],
    "transport": [
        _TIME,
        # chaos-run quality: payload bytes delivered / wire bytes sent
        {"pattern": "transport/goodput", "field": "value", "direction": "higher", "rtol": 0.3},
        # degraded rounds-to-target / clean rounds-to-target
        {"pattern": "transport/rounds_ratio", "field": "value", "direction": "lower", "rtol": 0.5},
    ],
    "serve": [
        _TIME,
        # span-derived request latency percentiles (ms, lower is better);
        # slack matches _TIME — CI machines vary widely on wall-clock
        {"pattern": "serve/*_ms", "field": "value", "direction": "lower", "rtol": 4.0},
        # decode throughput from the same spans (higher is better)
        {"pattern": "serve/tokens_per_s", "field": "value", "direction": "higher", "rtol": 0.8},
    ],
    "scenario": [
        _TIME,
        # convergence speed per matrix cell (deterministic for a fixed seed;
        # slack covers cross-platform float drift)
        {"pattern": "scenario/*/rounds_to_target", "field": "value", "direction": "lower", "rtol": 0.5},
        # analytic downlink cost must not creep up
        {"pattern": "scenario/*/s2w_bits", "field": "value", "direction": "lower", "rtol": 0.5},
        # delivered / sent participant messages under the mix's fault model
        {"pattern": "scenario/*/goodput", "field": "value", "direction": "higher", "rtol": 0.3},
    ],
}


def main(argv=None) -> int:
    from benchmarks import (
        comm_complexity,
        fig1_convergence,
        kernel_bench,
        roofline_report,
        scenario_matrix,
        serve_bench,
        stepsize_grid,
        table2_sigma,
        transport_bench,
        wire_bench,
    )
    from repro import obs

    suites = {
        "fig1": fig1_convergence.bench,
        "table2": table2_sigma.bench,
        "stepsize_grid": stepsize_grid.bench,
        "comm_complexity": comm_complexity.bench,
        "kernels": kernel_bench.bench,
        "wire": wire_bench.bench,
        "encode": wire_bench.bench_encode,
        "roofline": roofline_report.bench,
        "transport": transport_bench.bench,
        "serve": serve_bench.bench,
        # per-cell artifacts land next to the suite artifact (args.out is
        # bound at call time, after parsing)
        "scenario": lambda tracker=None: scenario_matrix.bench(
            tracker=tracker, out_dir=args.out),
    }
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("suites", nargs="*",
                    help=f"subset of {sorted(suites)} (default: all with available inputs)")
    ap.add_argument("--out", default=os.environ.get("REPRO_BENCH_DIR", "runs/bench"),
                    help="directory for BENCH_<suite>.json artifacts")
    ap.add_argument("--jsonl", default=None,
                    help="also append every event to this JSONL log")
    ap.add_argument("--seed", type=int, default=0,
                    help="recorded in BENCH env provenance")
    args = ap.parse_args(argv)

    unknown = [s for s in args.suites if s not in suites]
    if unknown:
        ap.error(f"unknown suites {unknown}; choose from {sorted(suites)}")
    selected = list(args.suites)
    if not selected:
        selected = ["fig1", "table2", "stepsize_grid", "comm_complexity", "kernels",
                    "wire", "encode", "transport", "serve", "scenario"]
        if os.path.isdir(roofline_report.DEFAULT_DIR) and os.listdir(roofline_report.DEFAULT_DIR):
            selected.append("roofline")

    jsonl = obs.JsonlTracker(args.jsonl) if args.jsonl else None
    print("name,us_per_call,derived")
    failures = []
    for key in selected:
        sink = obs.BenchJsonSink(key, args.out, seed=args.seed, gates=GATES.get(key, []))
        tracker = obs.CompositeTracker(obs.CsvStdoutTracker(), sink, jsonl)
        try:
            with tracker.time_block(f"{key}/suite"):
                rows = suites[key](tracker=tracker)
            for name, us, derived in rows:
                tracker.log_row(name, us, derived)
            sink.finish()
        except Exception:  # noqa: BLE001 - report, then fail the run
            traceback.print_exc()
            print(f"{key}/FAILED,0,nan")
            failures.append(key)
    if jsonl is not None:
        jsonl.finish()
    if failures:
        print(f"FAILED suites: {','.join(failures)}", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    sys.exit(main())
