"""The last line of a run, and the compared numbers printed beside it."""
from __future__ import annotations

import dataclasses
import json
import math
import sys
from typing import Dict, List, Optional


@dataclasses.dataclass
class Check:
    """One number compared with the reference, and its limit: the run is
    correct only where every ``value`` is finite and at most ``limit``."""

    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return math.isfinite(self.value) and self.value <= self.limit


def print_checks(checks: List[Check], stream=None) -> None:
    """The compared numbers, each beside its limit: the last lines of
    standard error."""
    stream = stream or sys.stderr
    for c in checks:
        print(f"check {c.name} {c.value!r} limit {c.limit!r} "
              f"{'ok' if c.ok else 'FAILED'}", file=stream)
    stream.flush()


def result_line(*, correct: bool, attempted: int, failed: int,
                metrics: Dict[str, tuple], device: dict,
                breakdown: Optional[dict], checks: List[Check]) -> str:
    """One JSON object: ``metrics`` maps a name to ``(value, unit)``; the
    compared numbers come last, under ``checks``."""
    out = {
        "correct": bool(correct),
        "attempted": int(attempted),
        "failed": int(failed),
        "metrics": {k: {"value": float(v), "unit": u} for k, (v, u) in metrics.items()},
        "device": device,
    }
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["checks"] = {c.name: {"value": c.value if math.isfinite(c.value) else str(c.value),
                              "limit": c.limit} for c in checks}
    return json.dumps(out)
