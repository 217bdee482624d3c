"""How ``correct`` is decided: the program's first chunk of rounds
against the plain reference's run of the same rounds and cohorts.

The numbers a cell's ``bench/limits/<cell>.json`` names are compared,
each against its limit:

- ``loss_gap``: over the first :data:`FIRST_ROUNDS` rounds, the largest
  relative gap between the program's eval loss after the round and the
  reference's, ``|L_p - L_r| / |L_r|``.  Later rounds are left out: at
  low participation FedDANE's loss swings by tens of times from round
  to round, and rounding differences grow with it (on the population
  cell sound runs read 1e-4 after round 1 and 0.1 to 0.7 by round 32);
- ``change_gap``: over the params' leaves, the largest gap between the
  norm of the program's change over the chunk and the reference's,
  ``| |dp| - |dr| | / max(|dr|, median leaf |dr|)``.  A leaf whose
  first-round change in the reference is under a thousandth of the
  median leaf's moves by round-off alone and is left out.

A number that is not finite fails.
"""
from __future__ import annotations

from typing import Dict, Tuple

import numpy as np

#: Rounds whose losses are compared.
FIRST_ROUNDS = 3


def _norms(tree: dict) -> Dict[str, float]:
    return {k: float(np.linalg.norm(np.asarray(v, np.float64)))
            for k, v in sorted(tree.items())}


def loss_gap(losses, loss_rounds, ref_losses) -> float:
    """``losses`` after the 1-based rounds ``loss_rounds``; the
    reference's ``ref_losses`` after every round."""
    rounds = np.asarray(loss_rounds)
    first = rounds <= FIRST_ROUNDS
    if not first.any():
        return float("inf")
    lp = np.asarray(losses, np.float64)[first]
    lr = np.asarray(ref_losses, np.float64)[rounds[first] - 1]
    return float(np.max(np.abs(lp - lr) / np.abs(lr)))


def change_gap(params0: dict, params: dict, ref_params: dict,
               ref_first: dict) -> Tuple[float, Dict[str, float]]:
    """The worst leaf's gap, and each counted leaf's."""
    dp = _norms({k: np.asarray(params[k], np.float64)
                 - np.asarray(params0[k], np.float64) for k in params0})
    dr = _norms({k: np.asarray(ref_params[k], np.float64)
                 - np.asarray(params0[k], np.float64) for k in params0})
    first = _norms(ref_first)
    med_first = float(np.median(list(first.values())))
    keep = [k for k in dr if first[k] >= 1e-3 * med_first]
    med = float(np.median([dr[k] for k in keep]))
    gaps = {k: abs(dp[k] - dr[k]) / max(dr[k], med) for k in keep}
    return max(gaps.values()), gaps


def judge(readings: Dict[str, float], limits: dict):
    """``(correct, checks)``: every number within its limit, and each
    number beside its limit."""
    checks = {}
    correct = True
    for name in sorted(limits):
        value = float(readings.get(name, float("nan")))
        limit = float(limits[name]["limit"])
        ok = bool(np.isfinite(value) and value <= limit)
        correct &= ok
        checks[name] = {"value": value, "limit": limit}
    return correct, checks
