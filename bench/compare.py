"""The comparison that decides ``correct``: what the timed path produced in
its first steps against the plain reference's run of the same steps.

Both sides hand over the same record: the parameters before the first step
and after each step (one step is one segment request), and the per-round
diagnostics the round reports (client gradient norms, update norm).
Every number is a relative gap; larger is worse.
"""
from __future__ import annotations

import math
from typing import Callable, Dict, Mapping

import numpy as np

GNORM_KEYS = ("grad_norm_mean", "grad_norm_min", "grad_norm_max")
# a leaf whose reference change is under this share of the median leaf's
# moves by round-off alone and is left out of the change numbers
STILL_LEAF = 1e-3


def _leaf_norms(before, after) -> Dict[str, float]:
    return {k: float(np.linalg.norm(np.asarray(after[k], np.float64)
                                    - np.asarray(before[k], np.float64)))
            for k in before}


def leaf_change_gaps(prog_before, prog_after, ref_before,
                     ref_after) -> Dict[str, float]:
    """Per leaf, the gap between the program's and the reference's norm of
    the parameter change, over the larger of that leaf's reference norm and
    the median leaf's; leaves that move by round-off alone are left out."""
    got = _leaf_norms(prog_before, prog_after)
    want = _leaf_norms(ref_before, ref_after)
    med = float(np.median(list(want.values())))
    return {k: abs(got[k] - w) / max(w, med) for k, w in want.items()
            if w >= STILL_LEAF * med}


def _rel(got, want) -> np.ndarray:
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    return np.abs(got - want) / np.maximum(np.abs(want), 1e-30)


def numbers(prog: Mapping, ref: Mapping,
            loss: Callable[[Mapping], float]) -> Dict[str, float]:
    """The compared numbers.  ``loss`` is the reference's held-out loss of a
    parameter set.  The change after the first and the third segment is
    given by its worst leaf and by its median leaf."""
    steps = len(ref["params"])
    first, third = (leaf_change_gaps(prog["params0"], prog["params"][s],
                                     ref["params0"], ref["params"][s])
                    for s in (0, -1))
    return {
        "loss": max(abs(loss(prog["params"][s]) - loss(ref["params"][s]))
                    / abs(loss(ref["params"][s])) for s in range(steps)),
        "update1": max(first.values()),
        "update1_med": float(np.median(list(first.values()))),
        "change3": max(third.values()),
        "change3_med": float(np.median(list(third.values()))),
        "gnorm1": max(float(_rel(prog["diag"][k][0], ref["diag"][k][0]))
                      for k in GNORM_KEYS),
        "gnorm": max(float(np.max(_rel(prog["diag"][k], ref["diag"][k])))
                     for k in GNORM_KEYS),
        "unorm": float(np.max(_rel(prog["diag"]["update_norm"],
                                   ref["diag"]["update_norm"]))),
    }


def verdict(nums: Mapping[str, float], limits: Mapping[str, float]) -> bool:
    """Correct when every limited number is finite and within its limit."""
    return all(math.isfinite(nums[k]) and nums[k] <= lim
               for k, lim in limits.items())
