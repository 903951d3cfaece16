"""The comparison that decides ``correct`` for a training cell.

Three numbers, each the worst case of its kind, compare the program's
first steps with the reference's:

* ``loss_gap``: the largest relative gap of a step's loss;
* ``grad_gap``: over every leaf (each layer's arrays one by one, the
  embedding and the head), the gap between the norm of the first
  gradient as the program's optimizer got it and the reference's,
  over the larger of the reference's norm of that leaf and of the median
  leaf;
* ``change_gap``: the same for the norm of each leaf's change over the
  compared steps.  A leaf whose reference gradient is under a thousandth
  of the median leaf's (as a key bias's is where softmax cancels it)
  moves under Adam by round-off alone and is left out, by that rule and
  not by name.

A leaf that one side has and the other lacks is a failure, as is a number
that is not finite.
"""
from __future__ import annotations

import math
import statistics

NAMES = ("loss_gap", "grad_gap", "change_gap")
NEGLIGIBLE_GRAD = 1e-3


def _leaf_gaps(got: dict, want: dict, keep) -> list:
    if set(got) != set(want):
        missing = sorted(set(want) ^ set(got))[:5]
        raise ValueError(f"leaves differ between program and reference: "
                         f"{missing}")
    floor = statistics.median(want.values())
    return [abs(got[k] - want[k]) / max(want[k], floor, 1e-30)
            for k in want if keep(k)]


def _worst(values) -> float:
    """The largest value; infinite where any is not finite."""
    values = list(values)
    if not all(math.isfinite(v) for v in values):
        return math.inf
    return max(values, default=0.0)


def gaps(got: dict, want: dict) -> dict:
    """The three numbers, from two sets of readings (``losses``,
    ``grad_norms``, ``change_norms``)."""
    lg = (_worst(abs(a - b) / abs(b) for a, b in zip(got["losses"],
                                                      want["losses"]))
          if len(got["losses"]) == len(want["losses"]) else math.inf)
    g_ref = want["grad_norms"]
    g_med = statistics.median(g_ref.values())
    grad = _leaf_gaps(got["grad_norms"], g_ref, lambda k: True)
    change = _leaf_gaps(got["change_norms"], want["change_norms"],
                        lambda k: g_ref[k] >= NEGLIGIBLE_GRAD * g_med)
    return {"loss_gap": lg, "grad_gap": _worst(grad),
            "change_gap": _worst(change)}


def verdict(found: dict, limits: dict) -> tuple:
    """(correct, [(name, value, limit)])."""
    rows = [(k, found[k], limits[k]) for k in NAMES]
    ok = all(math.isfinite(v) and v <= lim for _, v, lim in rows)
    return ok, rows
