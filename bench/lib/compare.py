"""The numbers that decide ``correct`` for a training cell.

The program's first three optimizer steps, taken in set-up through the
timed step, are compared with the reference's first three:

``loss_gap``    the largest |loss - loss_ref| / |loss_ref| over the steps.
``grad_gap``    the first gradient as the optimizer got it (from its state
                after one step), by the worst leaf: the gap between the
                program's and the reference's norm of the leaf, over the
                reference's norm of that leaf or of the median leaf,
                whichever is larger.
``update_gap``  the parameters' change over the three steps, by the worst
                leaf in the same way.  Leaves whose reference gradient is
                under a thousandth of the median leaf's move by round-off
                under Adam and are left out.
"""
from __future__ import annotations

import numpy as np

NOUGHT = 1e-3   # share of the median leaf's gradient norm


def _norms(tree_leaves):
    return np.array([float(np.linalg.norm(np.asarray(x, np.float64)))
                     for x in tree_leaves])


def worst_leaf_gap(prog_leaves, ref_leaves, keep=None) -> float:
    p, r = _norms(prog_leaves), _norms(ref_leaves)
    if keep is not None:
        p, r = p[keep], r[keep]
    denom = np.maximum(r, np.median(r))
    return float(np.max(np.abs(p - r) / denom))


def readings(prog: dict, ref: dict) -> dict:
    """``prog``/``ref``: {"losses": [3 floats], "grad1": [leaves],
    "delta": [leaves]} with leaves in one tree order."""
    lp = np.asarray(prog["losses"], np.float64)
    lr = np.asarray(ref["losses"], np.float64)
    if not (np.all(np.isfinite(lp)) and np.all(np.isfinite(lr))):
        return {"loss_gap": float("inf"), "grad_gap": float("inf"),
                "update_gap": float("inf")}
    g_ref = _norms(ref["grad1"])
    keep = g_ref >= NOUGHT * np.median(g_ref)
    out = {
        "loss_gap": float(np.max(np.abs(lp - lr) / np.abs(lr))),
        "grad_gap": worst_leaf_gap(prog["grad1"], ref["grad1"]),
        "update_gap": worst_leaf_gap(prog["delta"], ref["delta"], keep),
    }
    return {k: (v if np.isfinite(v) else float("inf")) for k, v in out.items()}


def judge(values: dict, limits: dict) -> tuple[bool, dict]:
    """Each number against its limit; a missing or non-finite number
    fails.  Returns (correct, {name: {"value", "limit"}})."""
    checks = {k: {"value": values.get(k, float("inf")), "limit": lim}
              for k, lim in limits.items()}
    ok = all(np.isfinite(c["value"]) and c["value"] <= c["limit"]
             for c in checks.values())
    return ok, checks
