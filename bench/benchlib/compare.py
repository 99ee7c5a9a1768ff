"""The numbers that decide `correct`, each beside its limit.

A training cell compares what the timed step produced over its first
rounds with the plain reference on the same weights and tasks:

  loss_gap         the largest relative gap of a round's mean query loss
  grad_norm_gap    the first round's meta-gradient as the optimizer got
                   it: by the worst leaf, |‖g‖ - ‖g_ref‖| over the larger
                   of ‖g_ref‖ of that leaf and of the median leaf
  update_norm_gap  the same measure for each leaf's change over the
                   checked rounds, |θ_k - θ_0|

Leaves whose reference gradient is nought to rounding (under a
thousandth of the median leaf's) move under Adam by round-off alone, so
they are left out of the change (by that rule, never by name).
"""
from __future__ import annotations

import math

import numpy as np

QUIET_LEAF = 1e-3


def rel_gap(got: float, want: float) -> float:
    return abs(got - want) / abs(want)


def loss_gap(got: list[float], want: list[float]) -> float:
    if len(got) < len(want):
        return math.inf
    return max(rel_gap(g, w) for g, w in zip(got, want))


def norm_gap(got: dict, want: dict, leaves=None) -> tuple[float, str]:
    """-> (worst gap, its leaf)."""
    leaves = sorted(want) if leaves is None else sorted(leaves)
    if not leaves or set(leaves) - set(got):
        return math.inf, ",".join(sorted(set(leaves) - set(got)))
    median = float(np.median([want[k] for k in leaves]))
    worst, where = -1.0, ""
    for k in leaves:
        gap = abs(got[k] - want[k]) / max(want[k], median)
        if not math.isfinite(gap):
            return math.inf, k
        if gap > worst:
            worst, where = gap, k
    return worst, where


def moving_leaves(ref_grad_norms: dict) -> list[str]:
    median = float(np.median(list(ref_grad_norms.values())))
    return [k for k, v in ref_grad_norms.items() if v >= QUIET_LEAF * median]


def training_checks(prog: dict, ref: dict, limits: dict) -> list[dict]:
    """prog, ref: {"losses", "grad_norms", "delta_norms"} -> the checks,
    each {"name", "value", "limit"} (and the worst leaf, where one)."""
    grad, grad_leaf = norm_gap(prog["grad_norms"], ref["grad_norms"])
    upd, upd_leaf = norm_gap(prog["delta_norms"], ref["delta_norms"],
                             moving_leaves(ref["grad_norms"]))
    return [
        {"name": "loss_gap", "value": loss_gap(prog["losses"], ref["losses"]),
         "limit": limits["loss_gap"]},
        {"name": "grad_norm_gap", "value": grad,
         "limit": limits["grad_norm_gap"], "leaf": grad_leaf},
        {"name": "update_norm_gap", "value": upd,
         "limit": limits["update_norm_gap"], "leaf": upd_leaf},
    ]


def passed(checks: list[dict]) -> bool:
    return all(math.isfinite(c["value"]) and c["value"] <= c["limit"]
               for c in checks)
