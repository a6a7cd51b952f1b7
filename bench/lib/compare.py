"""The numbers that decide ``correct`` for a training-like run: the
program's readings set against the reference's.

A norm is compared by the gap between the program's norm and the
reference's, leaf by leaf, measured against the reference's norm of that
leaf or of the median leaf, whichever is larger (some leaves' gradients are
all but zero); the worst leaf is the number. The first gradient is also
compared entry by entry (``grad_diff``): the norm of the difference of the
two, leaf by leaf, against the same measure. A gap of norms hides an error
that is spread evenly over a leaf, which a computation in a lower precision
makes; the norm of the difference does not.
"""
from __future__ import annotations

import math
import statistics
from typing import List, Optional, Sequence

from .result import Check


def rel_gap(p: float, r: float) -> float:
    return abs(p - r) / abs(r) if r else (0.0 if p == r else math.inf)


def worst_leaf_gap(prog: Sequence[float], ref: Sequence[float],
                   keep: Optional[Sequence[bool]] = None) -> float:
    if len(prog) != len(ref):
        return math.inf
    keep = keep if keep is not None else [True] * len(ref)
    kept = [r for r, k in zip(ref, keep) if k]
    if not kept:
        return math.inf
    med = statistics.median(kept)
    gaps = [abs(p - r) / max(r, med) if max(r, med) > 0 else math.inf
            for p, r, k in zip(prog, ref, keep) if k]
    return max(gaps) if all(map(math.isfinite, prog)) else math.inf


def worst_leaf_diff(prog: Sequence, ref: Sequence) -> float:
    """Per leaf, ``|prog - ref|`` against ``max(|ref|, median leaf |ref|)``;
    the worst leaf. ``prog`` and ``ref`` hold arrays, leaf by leaf."""
    import numpy as np

    if prog is None or len(prog) != len(ref):
        return math.inf
    norms = [float(np.linalg.norm(r.ravel())) for r in ref]
    med = statistics.median(norms)
    gaps = [float(np.linalg.norm((p.ravel() - r.ravel()))) / max(n, med)
            for p, r, n in zip(prog, ref, norms)]
    worst = max(gaps) if gaps else math.inf
    return worst if math.isfinite(worst) else math.inf


def moved_leaves(ref_grad: Sequence[float]) -> List[bool]:
    """Leaves whose reference gradient is at least a thousandth of the
    median leaf's: the others move under Adam by round-off alone."""
    med = statistics.median(ref_grad)
    return [g >= 1e-3 * med for g in ref_grad]


def train_checks(prog: dict, ref: dict, limits: dict) -> List[Check]:
    """``prog`` and ``ref`` hold ``loss`` (per round), ``grad`` (the first
    gradient's leaf norms), ``m1`` (AdamW's first moment after the first
    round, leaf by leaf: (1 - b1) times the first gradient) and ``change``
    (per tree, the leaf norms of its change over the checked rounds)."""
    if len(prog["loss"]) != len(ref["loss"]):
        loss = math.inf
    else:
        loss = max(rel_gap(p, r) for p, r in zip(prog["loss"], ref["loss"]))
    grad = worst_leaf_gap(prog["grad"], ref["grad"])
    keep = moved_leaves(ref["grad"])
    if len(prog["change"]) != len(ref["change"]):
        change = math.inf
    else:
        change = max(worst_leaf_gap(p, r, keep)
                     for p, r in zip(prog["change"], ref["change"]))
    return [Check("loss_gap", loss, limits["loss_gap"]),
            Check("grad_gap", grad, limits["grad_gap"]),
            Check("grad_diff", worst_leaf_diff(prog.get("m1"), ref["m1"]), limits["grad_diff"]),
            Check("change_gap", change, limits["change_gap"])]
