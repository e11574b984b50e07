"""Passes over X, operations and bytes of a dense GLM fit by trust-region
Newton (TRON), from the shape and the counts the program reports.

Kept with the benchmark, beside ``work_model.py`` (whose item size it
uses), so that no PR that claims a gain can change the yardstick. A solve
with the margin-cached Hessian-vector product NEEDS, from its counts alone
(``OptimizerResult.attempted_iterations`` T, ``.cg_iterations`` K):

- the first value and gradient: X c and X^T u, 2 passes, 4 n d FLOPs;
- an attempted outer step: the margins at its point for the curvature
  weights (1 pass, 2 n d) and the trial point's value and gradient
  (2 passes, 4 n d);
- a CG step: one Hessian-vector product, X v and X^T (D * X v) (2 passes,
  4 n d);

and the coordinate descent scores the model once a sweep (1 pass, 2 n d).
Whatever the program moves besides (a copy of X, a padded layout, a pass
recomputed) counts against it.
"""

from __future__ import annotations

from benchmark.work_model import ITEM


def passes(solves: int, attempted: int, cg: int, sweeps: int) -> float:
    """Reads of X a job needs: ``2 + 3 T + 2 K`` a solve, one a sweep."""
    return float(2 * solves + 3 * attempted + 2 * cg + sweeps)


def hvp_passes(cg: int) -> float:
    """Reads of X the CG steps need: two a Hessian-vector product."""
    return 2.0 * cg


def hvp_bytes(n: int, d: int, cg: float) -> float:
    """Bytes the CG steps must read: X twice a Hessian-vector product."""
    return hvp_passes(cg) * n * d * ITEM


def job_flops(n: int, d: int, solves: int, attempted: int, cg: int,
              sweeps: int) -> float:
    """FLOPs of one job: 4 n d a value and gradient (the first of every
    solve and one an attempted step), 2 n d a margin pass (one an attempted
    step, and the scoring pass of every sweep), 4 n d a Hessian-vector
    product (one a CG step). The pointwise loss is left out."""
    nd = float(n) * d
    return (4.0 * nd * (solves + attempted) + 2.0 * nd * (attempted + sweeps)
            + 4.0 * nd * cg)


def shape_of(config: dict):
    """``(n, d)`` of a configuration of the ``dense_tron`` recipe."""
    return int(config["n_rows"]), int(config["fixed"]["d"])
