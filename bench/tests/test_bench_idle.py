"""The host loop's idle readers (``bench/lib/idle.py``): the chip's idle time
under each of the program's spans, on hand-made device ops and annotations,
and on the annotations a real span writes into a CPU profiler trace."""
import random

import pytest

from bench.lib import idle, readers, xplane
from bench.lib.harness import Traced, load_metric

OPS = [("fusion.1", 0.0, 10.0), ("fusion.2", 5.0, 20.0), ("all-reduce.3", 30.0, 40.0)]
SPANS = ("dispatch", "log", "feed")


def _traced(host, ops=None, lo=0.0, hi=50.0, chips=1, spans=()):
    planes = {f"/device:TPU:{i}": list(ops if ops is not None else OPS) for i in range(chips)}
    trace = xplane.Trace(device_ops=planes, host=sorted(host, key=lambda h: h[1]))
    return Traced(trace=trace, lo=lo, hi=hi, spans=list(spans), work={}, chips=chips)


def test_idle_under_each_span():
    # the chip is idle in (20, 30) and (40, 50)
    t = _traced([("repro:dispatch", 18.0, 32.0), ("repro:log", 41.0, 45.0),
                 ("repro:log", 47.0, 60.0), ("repro:feed", 0.0, 5.0),
                 ("repro:round", 0.0, 60.0), ("bench/clock", 20.0, 30.0)])
    assert idle.idle_under_span_pct(t, "dispatch") == pytest.approx(100 * 10 / 50)
    assert idle.idle_under_span_pct(t, "log") == pytest.approx(100 * (4 + 3) / 50)
    assert idle.idle_under_span_pct(t, "feed") == 0.0
    for name in SPANS:
        assert load_metric(f"idle_{name}.step")(t) == idle.idle_under_span_pct(t, name)


def test_overlapping_annotations_count_once_and_chips_average():
    host = [("repro:dispatch", 15.0, 25.0), ("repro:dispatch", 22.0, 28.0)]
    assert idle.span_intervals(_traced(host), "dispatch") == [(15.0, 28.0)]
    # on the second chip the gap (20, 30) is busy: half of 8 ns of idle on average
    busy_gap = OPS + [("fusion.4", 20.0, 30.0)]
    t = _traced(host, chips=2)
    t.trace.device_ops["/device:TPU:1"] = busy_gap
    assert idle.idle_under_span_pct(t, "dispatch") == pytest.approx(100 * 8 / 2 / 50)


def test_spans_the_trace_lost_at_its_ends_come_from_the_program():
    # the profiler keeps no annotation open when it started: the program's
    # feed span (moved onto the trace's clock) that crosses lo stands in
    spans = [{"name": "feed", "t0": -30.0, "t1": 25.0},
             {"name": "feed", "t0": 41.0, "t1": 45.0}]  # twinned: counted once
    t = _traced([("repro:feed", 41.0, 45.0)], spans=spans)
    assert idle.span_intervals(t, "feed") == [(-30.0, 25.0), (41.0, 45.0)]
    assert idle.idle_under_span_pct(t, "feed") == pytest.approx(100 * (5 + 4) / 50)


def test_device_clock_offset_from_launch_and_completion_bounds():
    # in ms: the device stamps 2 ms early; the step runs over host [5, 102],
    # the next program over host [109.2, 109.7]; the host reads the step's
    # completion at 102.5 and launches the next program at 109
    ms = 1e6
    ops = [("fusion.1", 3 * ms, 60 * ms), ("fusion.2", 60 * ms, 100 * ms),
           ("convert.3", 107.2 * ms, 107.7 * ms)]
    host = [("repro:wait", 5 * ms, 103 * ms), ("ReadSyncFlag", 102.5 * ms, 102.8 * ms),
            ("tpu::System::Execute", 109 * ms, 109.1 * ms), ("repro:feed", 108 * ms, 110 * ms)]
    t = _traced(host, ops=ops, hi=120 * ms)
    # at least 109 - 107.2 ms early, at most 102.5 - 100: the midpoint
    assert idle.device_early_ns(t.trace, "/device:TPU:0") == pytest.approx((1.8 + 2.5) / 2 * ms)
    # the feed moved onto the device's clock, [105.85, 107.85] ms, is idle
    # but for the op over [107.2, 107.7]
    assert idle.idle_under_span_pct(t, "feed") == pytest.approx(100 * (2 - 0.5) / 120)
    # without the runtime's events the offset cannot be bounded: none is taken
    bare = _traced([h for h in host if h[0].startswith("repro:")], ops=ops, hi=120 * ms)
    assert idle.device_early_ns(bare.trace, "/device:TPU:0") == 0.0


def test_nothing_to_read_without_the_program_annotations():
    # the parent program writes no annotation, or only under other names
    for host in ([], [("bench/clock", 0.0, 1.0), ("dispatch", 20.0, 30.0),
                      ("repro:round", 0.0, 50.0)]):
        t = _traced(host)
        for name in SPANS:
            assert idle.idle_under_span_pct(t, name) is None
            assert load_metric(f"idle_{name}.step")(t) is None


def test_the_three_never_exceed_the_device_idle_share():
    rng = random.Random(7)
    for _ in range(300):
        ops, t0 = [], rng.uniform(-20, 10)
        while t0 < 120:
            t1 = t0 + rng.uniform(0.1, 15)
            ops.append(("op", t0, t1))
            t0 = t1 + rng.choice([0.0, rng.uniform(0, 8)])
        host, t0 = [], rng.uniform(-20, 10)
        while t0 < 120:  # the loop's spans follow one another
            for name in ("round", "feed", "dispatch", "wait", "log"):
                t1 = t0 + rng.uniform(0.1, 10)
                host.append((f"repro:{name}", t0, t1))
                t0 = t1 + rng.uniform(0, 2) * (name != "round")
        for name in ("tpu::System::Execute", "ReadSyncFlag"):  # a device clock offset
            host += [(name, a, a + 0.1) for a in (rng.uniform(-20, 120) for _ in range(20))]
        t = _traced(host, ops=ops, lo=0.0, hi=100.0)
        parts = [idle.idle_under_span_pct(t, name) for name in SPANS]
        assert all(p >= 0 for p in parts)
        assert sum(parts) <= readers.device_idle_pct(t) + 1e-9


def test_reads_the_annotations_the_program_writes(tmp_path):
    import jax
    import jax.numpy as jnp

    from repro import obs
    from repro.obs.trace import ANNOTATION_PREFIX

    assert idle.PREFIX == ANNOTATION_PREFIX
    tr = obs.MemoryTracker()
    with jax.profiler.trace(str(tmp_path)):
        for i in range(3):
            with tr.span("round", round=i):
                with tr.span("feed"):
                    x = jnp.full(8, i)
                with tr.span("dispatch"):
                    y = x * 2
                with tr.span("wait"):
                    y.block_until_ready()
                with tr.span("log"):
                    float(y[0])
    trace = xplane.load(xplane.find_xplane(str(tmp_path)))
    spans = {e["span_id"]: e for e in tr.events}
    traced = Traced(trace=trace, lo=0.0, hi=1.0, spans=[], work={}, chips=1)
    for name in SPANS + ("wait", "round"):
        got = idle.span_intervals(traced, name)
        want = [e for e in spans.values() if e["name"] == name]
        assert len(got) == len(want) == 3, name
        for (a, b), e in zip(got, sorted(want, key=lambda e: e["t0"])):
            assert abs((b - a) / 1e9 - (e["t1"] - e["t0"])) < 1e-4, name
