"""The numbers that decide ``correct`` for a training cell.

Each is a gap between the program's first rounds and the reference's,
as a share of the reference:

* ``loss_rel_gap`` — the largest |ELBO_prog - ELBO_ref| / |ELBO_ref|
  over every local step of the first rounds;
* ``grad_norm_gap`` — per leaf of every persistent Adam state after
  round 1, | ||m_prog|| - ||m_ref|| | over the larger of ||m_ref|| and
  the median leaf's (m is the first moment, an average of the
  gradients as the optimizer got them);
* ``change_norm_gap`` — the same for the change of every parameter
  leaf from its initial value to its value after the last round
  compared.

Leaves whose gradient at the first step is under a thousandth of the
median leaf's move under Adam by round-off alone and are left out of
the two norm gaps, by that rule and not by name.
"""
from __future__ import annotations

import statistics
from typing import Dict

import numpy as np

from pbench.reference import leaf_norms

RULE = 1e-3


def moving_leaves(grad0: Dict[str, float]):
    med = statistics.median(grad0.values())
    return {k for k, v in grad0.items() if v >= RULE * med}


def norm_gap(prog: Dict[str, float], ref: Dict[str, float], keep) -> float:
    keys = [k for k in ref if k in keep]
    if not keys:
        return float("nan")
    med = statistics.median(ref[k] for k in keys)
    return max(abs(prog[k] - ref[k]) / max(ref[k], med) for k in keys)


def change(after, before):
    import jax

    return jax.tree_util.tree_map(
        lambda a, b: np.asarray(a, np.float64) - np.asarray(b, np.float64),
        after, before)


def training_gaps(prog, ref, init) -> Dict[str, float]:
    """``prog`` and ``ref`` hold ``elbo`` (rounds, K), ``m1`` and
    ``params``; ``init`` the parameters both started from."""
    e_p = np.asarray(prog["elbo"], np.float64)
    e_r = np.asarray(ref["elbo"], np.float64)
    loss = float(np.max(np.abs(e_p - e_r) / np.abs(e_r)))
    keep = moving_leaves(ref["grad0"])
    grad = norm_gap(leaf_norms(prog["m1"]), leaf_norms(ref["m1"]), keep)
    chg = norm_gap(leaf_norms(change(prog["params"], init)),
                   leaf_norms(change(ref["params"], init)), keep)
    return {"loss_rel_gap": loss, "grad_norm_gap": grad,
            "change_norm_gap": chg}
