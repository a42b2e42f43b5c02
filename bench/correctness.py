"""The numbers that decide ``correct``: what the timed path produced in
its first master update and its first two evaluations, against the
plain reference's.

  * ``init_gap``    — the largest difference between the program's and
    the reference's starting master, over the largest weight of the
    reference's (both are made from the seed).
  * ``update1_gap`` — the first master update (Algorithm 4's parents
    trained and fill-aggregated), leaf by leaf: the gap between the
    norm of the program's change and the norm of the reference's, over
    the larger of the reference's norm for that leaf and the median
    leaf's.  The worst leaf counts.
  * ``err_gap``     — the largest difference of any evaluated key's
    test error rate, over both evaluations, between the fitness
    program's answer and the reference's scoring of the same master
    (the program's, as the program held it before that evaluation): a
    measure of the fitness program alone.

Leaves whose change is nought to rounding in the reference and in the
program (under a thousandth of the median leaf's: the identity
branches' placeholder, branches no key selected) are left out of the
update gap.  A leaf that either side moves counts, so a program that
moves a leaf the reference leaves alone, or leaves unmoved one the
reference moves, reads a gap of about 1.
"""
from __future__ import annotations

from typing import Dict, List

import jax
import numpy as np

NEGLIGIBLE = 1e-3


def _by_path(tree) -> Dict[str, np.ndarray]:
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    return {jax.tree_util.keystr(p): np.asarray(x, np.float64)
            for p, x in flat}


def init_gap(prog0, ref0) -> float:
    p, r = _by_path(prog0), _by_path(ref0)
    if p.keys() != r.keys():
        return float("inf")
    scale = max(float(np.max(np.abs(x))) for x in r.values())
    return max(float(np.max(np.abs(p[k] - r[k]))) for k in r) / scale


def _leaf_gaps(prog_a, prog_b, ref_a, ref_b):
    """Per counted leaf, (gap, program norm, reference norm) of the
    change from ``a`` to ``b``, and the median leaf's reference norm;
    None where the trees differ or the reference moved nothing."""
    pa, pb, ra, rb = (_by_path(t) for t in (prog_a, prog_b, ref_a, ref_b))
    if not (pa.keys() == pb.keys() == ra.keys() == rb.keys()):
        return None, None
    n_ref = {k: float(np.linalg.norm(rb[k] - ra[k])) for k in ra}
    n_prog = {k: float(np.linalg.norm(pb[k] - pa[k])) for k in pa}
    moved = [v for v in n_ref.values() if v > 0]
    if not moved:
        return None, None
    med = float(np.median(moved))
    gaps = {k: (abs(n_prog[k] - n_ref[k]) / max(n_ref[k], med), n_prog[k],
                n_ref[k]) for k in n_ref
            if max(n_ref[k], n_prog[k]) >= NEGLIGIBLE * med}
    return gaps, med


def update_gap(prog_a, prog_b, ref_a, ref_b) -> float:
    """The worst leaf's gap between the norms of change from ``a`` to
    ``b`` (see the module docstring)."""
    gaps, _ = _leaf_gaps(prog_a, prog_b, ref_a, ref_b)
    return max(g for g, _, _ in gaps.values()) if gaps else float("inf")


def worst_leaves(prog_masters, ref_masters, n=3) -> Dict:
    """For reading a run: the leaves with the largest gaps after the
    first update, and the median leaf's gap."""
    gaps, med = _leaf_gaps(prog_masters[0], prog_masters[1],
                           ref_masters[0], ref_masters[1])
    worst = sorted(gaps.items(), key=lambda kv: -kv[1][0])
    return {"median_gap": float(np.median([g[0] for g in gaps.values()])),
            "worst": [(k,) + v for k, v in worst[:n]],
            "median_norm": med}


def err_gap(prog_errs: List[np.ndarray], ref_errs: List[np.ndarray]) -> float:
    if len(prog_errs) != len(ref_errs):
        return float("inf")
    return max(float(np.max(np.abs(np.asarray(p) - np.asarray(r))))
               for p, r in zip(prog_errs, ref_errs))


def compare(prog_masters, prog_errs, ref_masters, ref_errs) -> Dict:
    """Every compared number, by name.  ``prog_masters`` hold the master
    before the first update and after each captured one,
    ``ref_masters`` the reference's before and after the first."""
    return {"init_gap": init_gap(prog_masters[0], ref_masters[0]),
            "update1_gap": update_gap(prog_masters[0], prog_masters[1],
                                      ref_masters[0], ref_masters[1]),
            "err_gap": err_gap(prog_errs, ref_errs)}
