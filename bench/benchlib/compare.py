"""The numbers that decide ``correct``.

* ``loss``: the largest relative gap between the program's round loss and
  the reference's over the first three rounds.
* ``update1``, ``update3``: the change of the weights after round 1 (the
  first aggregated update, as the server optimizer gets it) and after
  round 3, leaf by leaf: the gap between the program's norm and the
  reference's, over the larger of the reference's norm of that leaf and of
  the median leaf, worst leaf.  Leaves whose reference gradient is under a
  thousandth of the median leaf's move by round-off alone and are left out.
"""
from __future__ import annotations

import numpy as np

GRAD_FLOOR = 1e-3


def _norm_diff(a, b, chunk=1 << 24) -> float:
    a, b = a.ravel(), b.ravel()
    total = 0.0
    for i in range(0, a.size, chunk):
        d = (a[i:i + chunk].astype(np.float64)
             - b[i:i + chunk].astype(np.float64))
        total += float(np.dot(d, d))
    return total ** 0.5


def program_readings(p0: dict, p1: dict, p3: dict, losses) -> dict:
    return {"losses": list(losses),
            "norms1": {k: _norm_diff(p1[k], p0[k]) for k in p0},
            "norms3": {k: _norm_diff(p3[k], p0[k]) for k in p0}}


def counted_leaves(grad_norms: dict) -> list:
    med = float(np.median(list(grad_norms.values())))
    return sorted(k for k, g in grad_norms.items() if g >= GRAD_FLOOR * med)


def leaf_gap(prog: dict, ref: dict, leaves) -> tuple:
    """(worst gap, its leaf)."""
    med = float(np.median([ref[k] for k in leaves]))
    gaps = {k: abs(prog[k] - ref[k]) / max(ref[k], med, 1e-30)
            for k in leaves}
    worst = max(gaps, key=gaps.get)
    return gaps[worst], worst


def numbers(prog: dict, ref: dict, log=None) -> dict:
    lp, lr = np.asarray(prog["losses"]), np.asarray(ref["losses"])
    leaves = counted_leaves(ref["grad_norms"])
    u1, w1 = leaf_gap(prog["norms1"], ref["norms1"], leaves)
    u3, w3 = leaf_gap(prog["norms3"], ref["norms3"], leaves)
    loss = float(np.max(np.abs(lp - lr) / np.maximum(np.abs(lr), 1e-30)))
    if log is not None:
        log(f"[bench] losses program {lp.tolist()} reference {lr.tolist()}; "
            f"worst leaf after round 1 {w1}, after round 3 {w3}; leaves "
            f"left out {sorted(set(ref['grad_norms']) - set(leaves))}")
    return {"loss": loss, "update1": float(u1), "update3": float(u3)}
