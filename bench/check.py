"""The output check: what the timed path produced against the reference.

Set-up drives the compiled round, the one the window then runs, through
the first `STEPS` rounds on distinct batches, and reads from its state:
each round's loss; after round 1 the per-leaf norm of the parameters'
change (the first gradients as the optimizer applied them: the Sophia
step is clipped, so it carries their signs); after round `STEPS` the
per-leaf norm of the parameters' change.  `bench.reference` follows the
same rounds.  Each number is compared by its worst case:

* ``loss``: the largest relative gap of a round's loss;
* ``step``, ``change``: the largest gap between the program's and the
  reference's norm of a leaf, over the reference's norm of that leaf or
  of the median leaf, whichever is larger, leaving out leaves whose
  reference gradient is under `FLAT_LEAF` of the median leaf's (they
  move by round-off alone).

The Sophia state is not compared: the first moment is stored in float8
e4m3, which keeps only its largest coordinates, and the curvature
estimate is zero at these configurations in program and reference alike
(PERF.md).
"""
from __future__ import annotations

from typing import Dict, List

import numpy as np

#: rounds the reference follows
STEPS = 2
#: a leaf whose first gradient is under this share of the median leaf's
#: moves by round-off alone and is left out of ``step`` and ``change``
FLAT_LEAF = 1e-3
NUMBERS = ("loss", "step", "change")


def leaf_gap(prog: np.ndarray, ref: np.ndarray, keep=None) -> float:
    prog = np.asarray(prog, np.float64)
    ref = np.asarray(ref, np.float64)
    if keep is not None:
        prog, ref = prog[keep], ref[keep]
    if not np.all(np.isfinite(prog)):
        return float("inf")
    floor = max(float(np.median(ref)), np.finfo(np.float64).tiny)
    return float(np.max(np.abs(prog - ref) / np.maximum(ref, floor)))


def gaps(prog: Dict, ref: Dict) -> Dict[str, float]:
    """The compared numbers; an empty or non-finite reading is +inf."""
    lp = np.asarray(prog["loss"], np.float64)
    lr = np.asarray(ref["loss"], np.float64)
    loss = (float(np.max(np.abs(lp - lr) / np.abs(lr)))
            if lp.shape == lr.shape and np.all(np.isfinite(lp))
            else float("inf"))
    g = np.sqrt(np.asarray(ref["gsq"], np.float64))
    keep = g >= FLAT_LEAF * np.median(g)
    return {"loss": loss,
            "step": leaf_gap(prog["step"], ref["step"], keep),
            "change": leaf_gap(prog["change"], ref["change"], keep)}


def verdict(found: Dict[str, float], limits: Dict[str, float]) -> bool:
    return all(found[k] <= limits[k] for k in NUMBERS)


def lines(found: Dict[str, float], limits: Dict[str, float]) -> List[str]:
    """One line per compared number, with its limit."""
    return [f"check {k} {found[k]!r} limit {limits[k]!r}" for k in NUMBERS]
