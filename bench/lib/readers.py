"""The arithmetic of the per-layer metrics, shared by the readers in
``bench/metrics/``. Each takes a :class:`bench.lib.harness.Traced` and
returns a number, or ``None`` where the trace holds nothing to read."""
from __future__ import annotations

from typing import Optional

from . import xplane


def device_idle_pct(t) -> Optional[float]:
    """100 x (1 - busy / window), averaged over the cell's chips."""
    planes = t.device_planes()
    if not planes or t.hi <= t.lo:
        return None
    busy = xplane.busy_ns(t.trace, t.lo, t.hi)
    return 100.0 * (1.0 - sum(busy[p] for p in planes) / len(planes) / (t.hi - t.lo))


def model_flops_pct(t) -> Optional[float]:
    """Operations the forward and backward passes need, times tokens per
    second of the window, over the chips' bf16 peak."""
    w = t.work
    if not w.get("tokens") or t.window_s <= 0:
        return None
    return 100.0 * w["flops_per_token"] * w["tokens"] / t.window_s / (
        t.chips * w["peak_flops"])
