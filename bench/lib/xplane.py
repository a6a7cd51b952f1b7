"""Reduction of a profiler trace (``.xplane.pb``) to device busy time, op
durations by name, and the windows of host annotations.

Times are in nanoseconds on the trace's own clock. Device planes are named
``/device:<KIND>:<n>``; their ops sit on the line ``XLA Ops``, each named by
its HLO text. On a TPU v5e the device's timestamps run about 1.2 ms ahead
of the host's annotations (a program starts on the device's clock 1.2 ms
before the host's enqueue of it): windows seconds long do not feel it. Host
annotations (``jax.profiler.TraceAnnotation``) are events of the host
plane ``/host:CPU``.
"""
from __future__ import annotations

import dataclasses
import glob
import os
from typing import Dict, Iterable, List, Tuple

Interval = Tuple[float, float]

DEVICE_PREFIX = "/device:"
HOST_PLANE = "/host:CPU"
OPS_LINE = "XLA Ops"


@dataclasses.dataclass
class Trace:
    #: device plane name -> [(op name, start_ns, end_ns)], sorted by start
    device_ops: Dict[str, List[Tuple[str, float, float]]]
    #: host events [(name, start_ns, end_ns)]
    host: List[Tuple[str, float, float]]


def find_xplane(trace_dir: str) -> str:
    """The one ``.xplane.pb`` file under ``trace_dir``."""
    found = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def load(path: str) -> Trace:
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    device_ops: Dict[str, List[Tuple[str, float, float]]] = {}
    host: List[Tuple[str, float, float]] = []
    for plane in pd.planes:
        op_lines = [line for line in plane.lines if line.name == OPS_LINE]
        if plane.name.startswith(DEVICE_PREFIX) and op_lines:
            ops = [(e.name, float(e.start_ns), float(e.start_ns + e.duration_ns))
                   for line in op_lines for e in line.events]
            device_ops[plane.name] = sorted(ops, key=lambda o: o[1])
        elif plane.name == HOST_PLANE:
            host.extend((e.name, float(e.start_ns), float(e.start_ns + e.duration_ns))
                        for line in plane.lines for e in line.events)
    return Trace(device_ops=device_ops, host=sorted(host, key=lambda h: h[1]))


def short_name(op: str) -> str:
    """An op's name without its HLO text: ``%fusion.3 = f32[] fusion(...)``
    gives ``fusion.3``."""
    return op.split(" = ", 1)[0].lstrip("%").strip()


def clip(intervals: Iterable[Interval], lo: float, hi: float) -> List[Interval]:
    return [(max(a, lo), min(b, hi)) for a, b in intervals if b > lo and a < hi]


def union_ns(intervals: Iterable[Interval], lo: float, hi: float) -> float:
    """Length of the union of ``intervals`` inside ``[lo, hi]``."""
    total, end = 0.0, lo
    for a, b in sorted(clip(intervals, lo, hi)):
        if b <= end:
            continue
        total += b - max(a, end)
        end = b
    return total


def busy_ns(trace: Trace, lo: float, hi: float) -> Dict[str, float]:
    """Per device plane, the time in ``[lo, hi]`` in which some op ran."""
    return {name: union_ns(((a, b) for _, a, b in ops), lo, hi)
            for name, ops in trace.device_ops.items()}


def op_ns_by_name(ops: Iterable[Tuple[str, float, float]], lo: float,
                  hi: float) -> Dict[str, float]:
    """Summed device time of the ops of each name, clipped to ``[lo, hi]``."""
    out: Dict[str, float] = {}
    for name, a, b in ops:
        a, b = max(a, lo), min(b, hi)
        if b > a:
            out[name] = out.get(name, 0.0) + (b - a)
    return out


def annotations(trace: Trace, name: str) -> List[Interval]:
    """The ``[start, end]`` of every host annotation called ``name``."""
    return [(a, b) for n, a, b in trace.host if n == name]


def idle_gaps(trace: Trace, plane: str, lo: float, hi: float) -> List[Interval]:
    """The intervals in ``[lo, hi]`` in which no op ran on ``plane``."""
    gaps, end = [], lo
    for a, b in sorted(clip(((a, b) for _, a, b in trace.device_ops[plane]), lo, hi)):
        if a > end:
            gaps.append((end, a))
        end = max(end, b)
    if hi > end:
        gaps.append((end, hi))
    return gaps
