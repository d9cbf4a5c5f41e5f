"""The comparison that decides ``correct`` for a training cell.

Both sides hand over the same readings of the first steps: each step's
loss, the per-leaf norm of the first gradient, and the per-leaf norm of
the parameters' change over those steps, as ``{leaf path: number}``.
Norm gaps are |program's norm - reference's norm| over the larger of the
reference's norm of that leaf and of its median leaf, taken by the worst
leaf, and beside it by the median leaf, which is steadier from seed to
seed.  Leaves whose reference gradient is under a thousandth of the
median leaf's (a convolution's bias under batch normalisation) are left
out of the change: they move by round-off alone.
"""

from __future__ import annotations

import math
import statistics

import numpy as np

DEAD_LEAF = 1e-3
NOT_A_NUMBER = 1e30  # what a gap that is not finite reads: JSON has no infinity


def _leaf_gaps(prog, ref, keys):
    """(gap of the worst leaf, that leaf, gap of the median leaf)"""
    med = statistics.median(ref[k] for k in keys)
    per = []
    for k in keys:
        gap = abs(prog[k] - ref[k]) / max(ref[k], med)
        if not math.isfinite(gap):
            return NOT_A_NUMBER, k, NOT_A_NUMBER
        per.append((gap, k))
    worst, where = max(per)
    return worst, where, statistics.median(g for g, _ in per)


def gaps(prog, ref):
    """{name: value} of every number compared, and {name: leaf} of where
    the norm gaps were worst."""
    out, where = {}, {}
    for i, (a, b) in enumerate(zip(prog["losses"], ref["losses"])):
        g = abs(a - b) / abs(b)
        out[f"loss{i + 1}_gap"] = g if math.isfinite(g) else NOT_A_NUMBER
    keys = sorted(ref["grad_norms"])
    out["grad_gap"], where["grad_gap"], out["grad_gap_median_leaf"] = \
        _leaf_gaps(prog["grad_norms"], ref["grad_norms"], keys)
    med = statistics.median(ref["grad_norms"][k] for k in keys)
    live = [k for k in keys if ref["grad_norms"][k] >= DEAD_LEAF * med]
    out["change_gap"], where["change_gap"], out["change_gap_median_leaf"] = \
        _leaf_gaps(prog["change_norms"], ref["change_norms"], live)
    if "first_grad" in prog and "first_grad" in ref:
        # the norm of the difference, which sees what a gap of norms does
        # not: an error at right angles to the gradient
        per = []
        for k in keys:
            a, b = np.asarray(prog["first_grad"][k], np.float64), \
                np.asarray(ref["first_grad"][k], np.float64)
            d = float(np.sqrt(np.sum(np.square(a - b)))) / max(ref["grad_norms"][k], med)
            per.append((d if math.isfinite(d) else NOT_A_NUMBER, k))
        out["grad_diff"], where["grad_diff"] = max(per)
        out["grad_diff_median_leaf"] = statistics.median(d for d, _ in per)
    return out, where


def verdict(values, limits):
    """[(name, value, limit, ok)] for every number that has a limit."""
    rows = []
    for name, limit in limits.items():
        v = values.get(name, NOT_A_NUMBER)
        rows.append((name, v, limit, bool(v <= limit)))
    return rows
