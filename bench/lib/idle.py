"""The chip's idle time put down to what the host was doing: the share of a
traced window in which no op ran on the chip while the host was inside one
of the training loop's spans (``feed``, ``dispatch``, ``log``).

The program opens a profiler annotation named ``repro:<span>`` for the
length of each of its spans (``repro.obs.trace``), so the host's intervals
are read from the trace, on the trace's own clock. The profiler keeps only
annotations opened and closed while it runs; a span open when the trace
started or stopped is taken from the program's spans, which the harness
moved onto the trace's clock. A program that opens no such annotation gives
nothing to read.

The device stamps an instant earlier than the host does (on a TPU v5e by
about 1.4 ms: a program's first op is stamped before the host's launch of
it). The offset is read from the trace, between two bounds that causality
sets on each round whose step the host waited for (``wait``):

* the first op after the step belongs to a program the host launched after
  the wait: the device stamps early by at least the first launch
  (``tpu::System::Execute``) after the wait's end less that op's start;
* the host reads the step's completion (``ReadSyncFlag``) after its last
  op: by at most the last such read inside the wait less the step's end.

The midpoint of the tightest bounds is taken (a trace without those
runtime events is read uncorrected), and the host's intervals are moved by
it onto the device's clock. The window stays where ``device_idle.step``
reads it, so the three shares never sum to more than that one.
"""
from __future__ import annotations

from typing import List, Optional

from . import xplane

#: the program's annotation prefix (``repro.obs.trace.ANNOTATION_PREFIX``),
#: kept here so that the benchmark reads a program that lacks it
PREFIX = "repro:"
LAUNCH = "tpu::System::Execute"
COMPLETION_READ = "ReadSyncFlag"
#: ops closer than this belong to one stretch of device work (a step's ops
#: lie at most about 11 us apart on a TPU v5e; between steps the loop leaves
#: the device idle for milliseconds)
JOIN_NS = 50e3


def merged(intervals, join: float = 0.0) -> List[xplane.Interval]:
    """Sorted intervals with those that overlap, or lie closer than
    ``join``, joined."""
    out: List[list] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1] + join:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def span_intervals(t, span: str) -> List[xplane.Interval]:
    """The host's intervals inside the program's span ``span``, on the
    trace's clock: its annotations, and the program's spans of that name
    that no annotation twins (open when the trace started or stopped)."""
    ann = xplane.annotations(t.trace, PREFIX + span)
    lost = [(s["t0"], s["t1"]) for s in t.spans if s["name"] == span
            and not any(a < s["t1"] and s["t0"] < b for a, b in ann)]
    return merged(ann + lost)


def overlap_ns(xs: List[xplane.Interval], ys: List[xplane.Interval]) -> float:
    """Length of the intersection of two sorted lists of disjoint intervals."""
    total, i, j = 0.0, 0, 0
    while i < len(xs) and j < len(ys):
        a, b = max(xs[i][0], ys[j][0]), min(xs[i][1], ys[j][1])
        total += max(0.0, b - a)
        if xs[i][1] < ys[j][1]:
            i += 1
        else:
            j += 1
    return total


def device_early_ns(trace: xplane.Trace, plane: str) -> float:
    """How much earlier the device stamps an instant on ``plane`` than the
    host does (see the module docstring); 0 where the trace cannot bound it."""
    stretches = merged(((a, b) for _, a, b in trace.device_ops[plane]), JOIN_NS)
    launches = [a for n, a, _ in trace.host if n == LAUNCH]
    reads = [a for n, a, _ in trace.host if n == COMPLETION_READ]
    lower, upper = [], []
    for w0, w1 in xplane.annotations(trace, PREFIX + "wait"):
        k = max(range(len(stretches)), default=None,
                key=lambda i: min(stretches[i][1], w1) - max(stretches[i][0], w0))
        if k is None or k + 1 >= len(stretches):
            continue
        after = [a for a in launches if a >= w1]
        if after:
            lower.append(min(after) - stretches[k + 1][0])
        inside = [a for a in reads if w0 <= a <= w1]
        if inside:
            upper.append(max(inside) - stretches[k][1])
    if not lower or not upper:
        return 0.0
    return (max(lower) + min(upper)) / 2


def idle_under_span_pct(t, span: str) -> Optional[float]:
    """100 x (time in ``[lo, hi]`` in which no op ran on the chip while the
    host was inside ``span``) / window, averaged over the cell's chips;
    ``None`` where the trace holds no annotation of ``span``."""
    planes = t.device_planes()
    if (not xplane.annotations(t.trace, PREFIX + span) or not planes
            or t.hi <= t.lo):
        return None
    host = span_intervals(t, span)
    idle = 0.0
    for p in planes:
        early = device_early_ns(t.trace, p)
        idle += overlap_ns(xplane.idle_gaps(t.trace, p, t.lo, t.hi),
                           [(a - early, b - early) for a, b in host])
    return 100.0 * idle / len(planes) / (t.hi - t.lo)
