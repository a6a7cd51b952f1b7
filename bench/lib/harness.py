"""One run of one cell: find its pieces by name, require the chip, build,
warm up, measure, check, print the last line.

Everything that belongs to one configuration, traffic mix or per-layer
metric sits in a file of its own, found by the name in ``BENCHMARK.json``:

    bench/configs/<config>.json    the configuration as it is run
    bench/traffic/<mix>.json       the mix's parameters; ``driver`` names the
                                   general driver in bench/lib/drivers/
    bench/limits/<workload>.json   the limits of the numbers compared
    bench/metrics/<metric>.py      ``read(traced) -> float | None``
"""
from __future__ import annotations

import dataclasses
import importlib
import importlib.util
import json
import shutil
import sys
import tempfile
import time
from pathlib import Path
from typing import Callable, Dict, List, Optional

from . import xplane
from .result import Check, print_checks, result_line

ROOT = Path(__file__).resolve().parents[2]
BENCH = ROOT / "bench"


class NoChip(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


def load_json(path: Path) -> dict:
    with open(path) as fh:
        return json.load(fh)


@dataclasses.dataclass
class Cell:
    workload: str
    config: dict
    traffic: dict
    limits: Dict[str, float]
    chips: int
    seed: int
    seconds: float
    trace: bool


@dataclasses.dataclass
class Traced:
    """What a traced run hands the per-layer readers: the device trace, the
    measured window on the trace's clock, the program's spans moved onto
    that clock, and the counts of the work done in the window."""

    trace: xplane.Trace
    lo: float
    hi: float
    spans: List[dict]
    work: dict
    chips: int

    @property
    def window_s(self) -> float:
        return (self.hi - self.lo) / 1e9

    def device_planes(self) -> List[str]:
        return sorted(self.trace.device_ops)[: self.chips]


@dataclasses.dataclass
class Outcome:
    """What a driver hands back. ``metrics`` holds end-to-end values by
    name; ``setup_end`` is the ``perf_counter`` time the window opened."""

    setup_end: float
    window_end: float
    metrics: Dict[str, float]
    attempted: int
    failed: int
    checks: List[Check]
    memory_peak_bytes: int
    traced: Optional[Traced] = None


def seed_key(seed: int):
    """A PRNG key for any whole seed up to 2**62: the low 31 bits seed the
    key and the rest is folded in, so every value stays a signed 32-bit int."""
    import jax

    return jax.random.fold_in(jax.random.PRNGKey(seed % 2**31), seed // 2**31)


def require_chips(count: int):
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"JAX found no TPU (platform {devs[0].platform!r}); "
                     "the benchmark runs only on the chip")
    if len(devs) < count:
        raise NoChip(f"the cell needs {count} chips, JAX found {len(devs)}")
    return devs[:count]


CACHE_DIR = ROOT / ".jax_cache"


def enable_compile_cache() -> str:
    """JAX's persistent compilation cache at a fixed directory inside the
    checkout, holding every program of the run, so that only a cell's first
    run in a checkout compiles. It is the program's default directory too
    (``repro.launch.compile_cache``); the benchmark sets it whatever the
    environment says, so that two checkouts never share a cache."""
    import jax

    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return str(CACHE_DIR)


def compile_clock() -> List[float]:
    """The ``perf_counter`` time at which each XLA backend compile of this
    process ended, from now on: a window should hold none."""
    import jax

    ends: List[float] = []

    def listen(event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            ends.append(time.perf_counter())

    jax.monitoring.register_event_duration_secs_listener(listen)
    return ends


def memory_peak_bytes(devices) -> int:
    """``peak_bytes_in_use`` of the fullest chip."""
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devices)


class GcWatch:
    """Python's garbage collections between ``start`` and ``stop``, by
    generation, and the longest: printed beside the window, so that a slow
    round can be laid to the collector or cleared of it."""

    def __init__(self) -> None:
        self.gc: List[tuple] = []
        self.t_gc = 0.0

    def _collect(self, phase: str, info: dict) -> None:
        if phase == "start":
            self.t_gc = time.perf_counter()
        else:
            self.gc.append((info["generation"], time.perf_counter() - self.t_gc))

    def start(self) -> None:
        import gc

        gc.callbacks.append(self._collect)

    def stop(self) -> None:
        import gc

        gc.callbacks.remove(self._collect)

    def summary(self) -> str:
        longest = max((d for _, d in self.gc), default=0.0)
        gens = [sum(g == n for g, _ in self.gc) for n in (0, 1, 2)]
        return f"collections by generation {gens}, longest {longest} s"


class TraceWindow:
    """A profiler trace of the measured window, in a temporary directory.

    ``start`` opens the trace just before the window and marks the host
    clock in it, so that the program's ``perf_counter`` spans can be moved
    onto the trace's clock; ``stop`` takes the window's ends on the host
    clock and closes the trace; ``reduce`` reads it."""

    MARK = "bench/clock"

    def __init__(self) -> None:
        self.dir = tempfile.mkdtemp(prefix="bench_trace_")
        self.t_open = self.t_close = self.t_mark = 0.0

    def start(self) -> None:
        import jax

        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        opts.enable_hlo_proto = False
        jax.profiler.start_trace(self.dir, profiler_options=opts)
        with jax.profiler.TraceAnnotation(self.MARK):
            self.t_mark = time.perf_counter()

    def stop(self, t_open: float, t_close: float) -> None:
        import jax

        self.t_open, self.t_close = t_open, t_close
        jax.profiler.stop_trace()

    def reduce(self, spans: List[dict], work: dict, chips: int) -> Traced:
        try:
            trace = xplane.load(xplane.find_xplane(self.dir))
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)
        marks = xplane.annotations(trace, self.MARK)
        if not marks:
            raise RuntimeError("the trace holds no clock mark")
        offset = marks[0][0] - self.t_mark * 1e9
        moved = [dict(s, t0=s["t0"] * 1e9 + offset, t1=s["t1"] * 1e9 + offset)
                 for s in spans]
        return Traced(trace=trace, lo=self.t_open * 1e9 + offset,
                      hi=self.t_close * 1e9 + offset, spans=moved, work=work,
                      chips=chips)


def applies(entry: dict, workload: str, reported: Optional[set] = None) -> bool:
    """Whether a metric of ``BENCHMARK.json`` is reported in ``workload``."""
    if "workloads" in entry:
        return workload in entry["workloads"]
    return reported is None or entry.get("moves") in reported


def load_metric(name: str) -> Callable:
    spec = importlib.util.spec_from_file_location(
        f"bench_metric_{name.replace('.', '_').replace('-', '_')}",
        BENCH / "metrics" / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def breakdown(traced: Traced) -> dict:
    """The ten device ops that took most time, and the ten longest idle
    gaps named by the innermost program span that was open in them."""
    ops: Dict[str, float] = {}
    planes = traced.device_planes()
    for plane in planes:
        for name, ns in xplane.op_ns_by_name(traced.trace.device_ops[plane],
                                             traced.lo, traced.hi).items():
            name = xplane.short_name(name)
            ops[name] = ops.get(name, 0.0) + ns / 1e9 / len(planes)
    gaps = []
    for a, b in xplane.idle_gaps(traced.trace, planes[0], traced.lo, traced.hi):
        mid = (a + b) / 2
        open_spans = [s for s in traced.spans if s["t0"] <= mid <= s["t1"]]
        inner = min(open_spans, key=lambda s: s["t1"] - s["t0"], default=None)
        gaps.append((f"host:{inner['name']}" if inner else "host:outside spans",
                     (b - a) / 1e9))
    return {
        "device_ops": sorted(ops.items(), key=lambda kv: -kv[1])[:10],
        "idle_gaps": [list(g) for g in sorted(gaps, key=lambda g: -g[1])[:10]],
    }


def build_cell(spec: dict, workload: str, seed: int, seconds: float,
               trace: bool) -> Cell:
    wl = next((w for w in spec["workloads"] if w["name"] == workload), None)
    if wl is None:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json")
    cfg_entry = next(c for c in spec["configs"] if c["name"] == wl["config"])
    return Cell(
        workload=workload,
        config=load_json(ROOT / cfg_entry["file"]),
        traffic=load_json(BENCH / "traffic" / f"{wl['traffic']}.json"),
        limits=load_json(BENCH / "limits" / f"{workload}.json"),
        chips=int(wl["chips"]), seed=seed, seconds=seconds, trace=trace)


def run_cell(cell: Cell, devices, t_start: float) -> Outcome:
    """Build, warm up, measure and check ``cell`` on ``devices``."""
    driver = importlib.import_module(f"bench.lib.drivers.{cell.traffic['driver']}")
    outcome = driver.run(cell, devices)
    outcome.metrics["setup_s"] = outcome.setup_end - t_start
    return outcome


def report(spec: dict, cell: Cell, outcome: Outcome, devices) -> tuple:
    """The result line's metrics and device, as ``BENCHMARK.json`` asks."""
    import jax

    e2e = [m for m in spec["end_to_end"] if applies(m, cell.workload)]
    for m in e2e:
        if m["name"] not in outcome.metrics:
            raise RuntimeError(f"the driver measured no {m['name']}")
    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices()),
              "memory_peak_bytes": outcome.memory_peak_bytes}
    if not cell.trace:
        return {m["name"]: (outcome.metrics[m["name"]], m["unit"]) for m in e2e}, device, None
    t = outcome.traced
    reported = {m["name"] for m in e2e}
    metrics = {}
    for m in spec["per_layer"]:
        if applies(m, cell.workload, reported):
            value = load_metric(m["name"])(t)
            if value is not None:
                metrics[m["name"]] = (value, m["unit"])
    planes = t.device_planes()
    if not planes:
        raise RuntimeError("the trace holds no device plane")
    busy = xplane.busy_ns(t.trace, t.lo, t.hi)
    device["busy_s"] = sum(busy[p] for p in planes) / len(planes) / 1e9
    device["window_s"] = t.window_s
    return metrics, device, breakdown(t)


def main(argv: Optional[List[str]] = None, t_start: Optional[float] = None) -> int:
    import argparse

    t_start = time.perf_counter() if t_start is None else t_start
    ap = argparse.ArgumentParser(description="Run one benchmark cell once.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    spec = load_json(ROOT / "BENCHMARK.json")
    cell = build_cell(spec, args.workload, args.seed, args.seconds, bool(args.trace))
    try:
        devices = require_chips(cell.chips)
    except NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 3
    print(f"bench: compile cache at {enable_compile_cache()}", file=sys.stderr)
    compiles = compile_clock()
    outcome = run_cell(cell, devices, t_start)
    inside = sum(outcome.setup_end <= t <= outcome.window_end for t in compiles)
    print(f"bench: {inside} compiles inside the window, {len(compiles)} in the run",
          file=sys.stderr)
    metrics, device, brk = report(spec, cell, outcome, devices)
    correct = all(c.ok for c in outcome.checks) and outcome.failed == 0
    line = result_line(correct=correct, attempted=outcome.attempted,
                       failed=outcome.failed, metrics=metrics, device=device,
                       breakdown=brk, checks=outcome.checks)
    print_checks(outcome.checks)
    print(line, flush=True)
    return 0
