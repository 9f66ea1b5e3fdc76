"""The comparison that decides ``correct``.

Training: three numbers, each with a limit of its own from the cell's file.

- ``loss_gap``: the worst of the first steps' ``|program - reference|``
  losses, relative to the reference's.
- ``grad_norm_gap``: over the leaves, the worst gap between the program's
  and the reference's norm of the first gradient (NOT the norm of the
  difference), measured against the reference's norm of that leaf or of the
  median leaf, whichever is larger.
- ``change_norm_gap``: the same for the norm of each leaf's change after
  the last followed step; leaves whose reference gradient is under a
  thousandth of the median leaf's are left out (they move under Adam by
  round-off alone — a key's bias under softmax).

Serving: ``logit_gap``, the widest gap by which a served greedy token's
reference logit lies below the reference's best at that position.
"""
from __future__ import annotations

import math
import statistics

ZERO_GRADIENT_RULE = 1e-3


def _worst_leaf(prog: dict, ref: dict, leaves):
    floor = statistics.median(ref[k] for k in ref)
    worst, where = 0.0, None
    for k in leaves:
        gap = abs(prog.get(k, 0.0) - ref[k]) / max(ref[k], floor, 1e-30)
        if not gap <= worst:           # NaN counts as the worst
            worst, where = gap, k
    return worst, where


def training_numbers(prog: dict, ref: dict) -> dict:
    """{name: (value, leaf or step where it is worst)}."""
    loss_gaps = [abs(p - r) / abs(r)
                 for p, r in zip(prog["loss"], ref["loss"])]
    if len(prog["loss"]) != len(ref["loss"]) or not loss_gaps:
        loss_gaps = [math.inf]
    worst_step = max(range(len(loss_gaps)),
                     key=lambda i: (math.isnan(loss_gaps[i]), loss_gaps[i]))
    leaves = sorted(ref["grad_norm"])
    g_floor = ZERO_GRADIENT_RULE * statistics.median(
        ref["grad_norm"].values())
    moved = [k for k in leaves if ref["grad_norm"][k] >= g_floor]
    return {
        "loss_gap": (loss_gaps[worst_step], f"step{worst_step + 1}"),
        "grad_norm_gap": _worst_leaf(prog["grad_norm"], ref["grad_norm"],
                                     leaves),
        "change_norm_gap": _worst_leaf(prog["change_norm"],
                                       ref["change_norm"], moved),
    }


def judge(numbers: dict, limits: dict):
    """(correct, {name: [value, limit]}) — every number under its limit;
    a number with no limit in the cell's file is reported and not held."""
    compared, ok = {}, True
    for name, (value, _where) in numbers.items():
        limit = limits.get(name)
        compared[name] = [value, limit]
        if limit is not None and not value <= limit:
            ok = False
    return ok, compared
