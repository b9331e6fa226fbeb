"""The comparison that decides ``correct``.

Both sides give the same readings of the program's first calls (see the
reference modules' ``run``): each step's loss, the per-worker leaf norms of
the momentum after the first call, and of the weights' change after the
last.  Three numbers come of them:

* ``loss_gap`` — the largest |loss − reference| / |reference| over steps;
* ``grad_gap`` — by the worst leaf, the gap between the two momentum norms
  after the first call (the gradients the optimizer got, weighted by μ),
  over the larger of the reference's norm of that leaf and of the median
  leaf of that worker;
* ``update_gap`` — the same for the change of the weights after the last
  call.  Leaves whose reference gradient is under a thousandth of the
  median leaf's move by round-off alone and are left out of it.

A cell's ``check`` names the numbers it compares.  A NaN or a missing
reading counts as infinitely far.
"""
from __future__ import annotations

import math

import numpy as np

QUIET = 1e-3          # a leaf whose gradient is under this share of the
#                       median leaf's is moved by round-off alone


def _clean(v) -> float:
    v = float(v)
    return v if math.isfinite(v) else math.inf


def _gap_by_leaf(prog, ref, keep=None) -> float:
    prog, ref = np.asarray(prog, np.float64), np.asarray(ref, np.float64)
    if prog.shape != ref.shape or not np.all(np.isfinite(prog)):
        return math.inf
    worst = 0.0
    for p_k, r_k in zip(prog, ref):
        den = np.maximum(r_k, np.median(r_k))
        gap = np.abs(p_k - r_k) / np.maximum(den, 1e-30)
        if keep is not None:
            gap = gap[keep]
        if gap.size:
            worst = max(worst, float(gap.max()))
    return worst


def quiet_leaves(ref_m_first) -> np.ndarray:
    """(n_leaves,) True for the leaves the update comparison keeps."""
    r = np.asarray(ref_m_first, np.float64)
    top = r.max(axis=0)
    return top >= QUIET * np.median(top)


def _loss_gap(lp, lr) -> float:
    lp, lr = np.asarray(lp, np.float64), np.asarray(lr, np.float64)
    if lp.shape != lr.shape or not np.all(np.isfinite(lp)):
        return math.inf
    return float(np.max(np.abs(lp - lr) / np.abs(lr)))


def numbers(prog: dict, ref: dict) -> dict:
    keep = quiet_leaves(ref["m_first"])
    return {
        "loss_gap": _clean(_loss_gap(prog["losses"], ref["losses"])),
        "grad_gap": _clean(_gap_by_leaf(prog["m_first"], ref["m_first"])),
        "update_gap": _clean(_gap_by_leaf(prog["dx"], ref["dx"], keep)),
    }


def judge(values: dict, limits: dict) -> tuple[bool, list]:
    """(correct, [{"name", "value", "limit"}]) over the cell's limits."""
    rows = [{"name": n, "value": values.get(n, math.inf), "limit": lim}
            for n, lim in limits.items()]
    return all(r["value"] <= r["limit"] for r in rows), rows
