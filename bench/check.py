"""The comparison that decides ``correct`` for a training cell.

The program and the reference each give, for the first three steps from
the same weights and batches: each step's loss, each step's upload mask,
the norm of every leaf of the aggregate gradient nabla after one step (the
first gradient as the server's optimizer gets it), and the norm of every
leaf's change theta^3 - theta^0.  The numbers compared:

  loss_gap        max over steps of |loss_p - loss_r| / |loss_r|
  grad_gap        max over leaves of |n_p - n_r| / max(n_r, median_r)
  dtheta_gap      the same for the change of the parameters, over the
                  leaves whose reference gradient is at least
                  ``MOVED_RULE`` x the median leaf's: the others move by
                  round-off alone
  uploads_differ  upload decisions that differ, out of steps x workers

Each is the gap between the two norms of a leaf, not the norm of their
difference, measured against the reference's norm of that leaf or of the
median leaf, whichever is larger.
"""
from __future__ import annotations

from typing import Dict, List, Tuple

import numpy as np

MOVED_RULE = 1e-3
NUMBERS = ("loss_gap", "grad_gap", "dtheta_gap", "uploads_differ")


def _leaf_gap(prog: Dict[str, float], ref: Dict[str, float],
              keys: List[str]) -> float:
    if set(prog) != set(ref):
        raise ValueError(f"leaf sets differ: {sorted(set(prog) ^ set(ref))}")
    r = np.array([ref[k] for k in keys])
    p = np.array([prog[k] for k in keys])
    base = np.maximum(r, np.median(r))
    with np.errstate(invalid="ignore", divide="ignore"):
        gap = np.where(base > 0, np.abs(p - r) / base,
                       np.where(p == r, 0.0, np.inf))
    return float(np.max(gap))


def gaps(prog: Dict, ref: Dict) -> Dict[str, float]:
    """The numbers compared, from two readings dicts."""
    lp, lr = np.asarray(prog["loss"], float), np.asarray(ref["loss"], float)
    loss_gap = float(np.max(np.abs(lp - lr) / np.abs(lr)))
    if not np.isfinite(lp).all():
        loss_gap = float("inf")
    med = float(np.median(list(ref["grad"].values())))
    moved = [k for k, v in ref["grad"].items() if v >= MOVED_RULE * med]
    return {
        "loss_gap": loss_gap,
        "grad_gap": _leaf_gap(prog["grad"], ref["grad"], sorted(ref["grad"])),
        "dtheta_gap": _leaf_gap(prog["dtheta"], ref["dtheta"], sorted(moved)),
        "uploads_differ": float(np.sum(np.asarray(prog["mask"], bool)
                                       != np.asarray(ref["mask"], bool))),
    }


def judge(numbers: Dict[str, float], limits: Dict[str, float]
          ) -> Tuple[bool, Dict[str, Dict[str, float]]]:
    """(correct, {name: {value, limit}}); a NaN fails."""
    table = {k: {"value": numbers[k], "limit": float(limits[k])}
             for k in NUMBERS}
    ok = all(v["value"] <= v["limit"] for v in table.values())
    return ok, table
