"""The trace reduction and the per-layer readers, on hand-made intervals and
on a small trace recorded once on a TPU v5e (``bench/testdata``)."""
import math
from pathlib import Path

import pytest

from bench.lib import readers, xplane
from bench.lib.harness import Traced, load_metric

TESTDATA = Path(__file__).resolve().parents[1] / "testdata"
OPS = [("fusion.1", 0.0, 10.0), ("fusion.2", 5.0, 20.0), ("all-reduce.3", 30.0, 40.0)]


def _traced(spans=(), work=None, lo=0.0, hi=50.0):
    trace = xplane.Trace(device_ops={"/device:TPU:0": list(OPS)}, host=[])
    return Traced(trace=trace, lo=lo, hi=hi, spans=list(spans), work=work or {}, chips=1)


def test_union_clips_and_merges():
    iv = [(a, b) for _, a, b in OPS]
    assert xplane.union_ns(iv, 0, 50) == 30
    assert xplane.union_ns(iv, 8, 35) == 12 + 5
    assert xplane.union_ns(iv, 41, 50) == 0


def test_op_time_by_name_and_idle_gaps():
    assert xplane.op_ns_by_name(OPS, 0, 35) == {"fusion.1": 10, "fusion.2": 15,
                                                "all-reduce.3": 5}
    t = _traced()
    assert xplane.idle_gaps(t.trace, "/device:TPU:0", 0, 50) == [(20, 30), (40, 50)]


def test_readers_on_hand_made_intervals():
    t = _traced(work={"tokens": 100, "flops_per_token": 1e3, "peak_flops": 1e12})
    assert readers.device_idle_pct(t) == pytest.approx(40.0)
    assert readers.model_flops_pct(t) == pytest.approx(100 * 1e3 * 100 / 50e-9 / 1e12)
    assert readers.model_flops_pct(_traced(work={"tokens": 0})) is None


def test_every_metric_file_reads_or_returns_nothing():
    t = _traced()
    for path in sorted((TESTDATA.parent / "metrics").glob("*.py")):
        value = load_metric(path.stem)(t)
        assert value is None or math.isfinite(value), path.stem


def test_reduction_of_a_recorded_v5e_trace():
    # recorded on a TPU v5e: six runs of one 1024x1024 f32 matmul program,
    # four of them and after a 5 ms pause two more inside "bench/window"
    t = xplane.load(str(TESTDATA / "v5e_matmul.xplane.pb"))
    assert list(t.device_ops) == ["/device:TPU:0"]
    ops = t.device_ops["/device:TPU:0"]
    assert len(ops) == 18
    assert xplane.busy_ns(t, 0, 1e12) == {"/device:TPU:0": 71138.0}
    by_name = {xplane.short_name(k): v for k, v in xplane.op_ns_by_name(ops, 0, 1e12).items()}
    assert by_name == {"copy-start": 79.0, "copy-done": 15.0, "fusion": 71044.0}
    (lo, hi), = xplane.annotations(t, "bench/window")
    assert (lo, hi) == (57434109.0, 67359919.0)
    assert xplane.busy_ns(t, lo, hi)["/device:TPU:0"] == 50273.0
    traced = Traced(trace=t, lo=lo, hi=hi, spans=[], work={}, chips=1)
    assert readers.device_idle_pct(traced) == pytest.approx(100 * (1 - 50273 / (hi - lo)))
