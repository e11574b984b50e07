"""Solver dispatch: GLMOptimizationConfiguration -> the right minimizer.

Mirrors the reference's optimizer selection
(ml/optimization/OptimizerFactory.scala + GeneralizedLinearOptimizationProblem
construction): TRON for twice-differentiable objectives, OWL-QN whenever the
L1 weight is positive, L-BFGS otherwise. The L2 part always rides inside the
objective; L1 is handled by OWL-QN's orthant machinery (same split as the
reference, where L1 lives in Breeze's OWLQN and L2 in the objective mixins).
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from photon_ml_tpu.ops.glm_objective import GLMBatch, GLMObjective
from photon_ml_tpu.optimization.config import (
    GLMOptimizationConfiguration,
    OptimizerType,
)
from photon_ml_tpu.optimization.convergence import OptimizerResult
from photon_ml_tpu.optimization.glm_lbfgs import minimize_lbfgs_glm
from photon_ml_tpu.optimization.lbfgs import minimize_lbfgs
from photon_ml_tpu.optimization.owlqn import minimize_owlqn
from photon_ml_tpu.optimization.tron import minimize_tron

Array = jax.Array


def solve_glm(
    objective: GLMObjective,
    batch: GLMBatch,
    config: GLMOptimizationConfiguration,
    coef0: Array,
    lower_bounds: Optional[Array] = None,
    upper_bounds: Optional[Array] = None,
    track_coefficients: bool = False,
) -> OptimizerResult:
    """One GLM solve. Pure: jit/vmap-safe given consistent static config."""
    lam = config.regularization_weight
    rc = config.regularization_context
    l1 = rc.l1_weight(lam)
    l2 = rc.l2_weight(lam)

    # jit-cache discipline: ``objective.value`` is the static fun (stable for
    # a persistent objective instance); the batch AND the l2 weight are
    # traced args, so λ-grid sweeps and repeated coordinate updates reuse one
    # compiled solver.
    fun = objective.value
    l2_arr = jnp.asarray(l2, coef0.dtype)

    if config.optimizer_type == OptimizerType.TRON:
        if not objective.loss.twice_differentiable:
            raise ValueError(
                f"TRON requires a twice-differentiable loss, got "
                f"{objective.loss.name}")
        if l1 > 0:
            raise ValueError("TRON does not support L1 regularization")
        # Note: an exact-Newton fast path for small d (optimization/newton.py)
        # was measured and NOT auto-routed here: batched tiny linalg.solve
        # lowers to slow unrolled LU on TPU (~400ms vs ~0.2ms for the vmapped
        # L-BFGS on the 5k-entity benchmark block), so CG/quasi-Newton wins
        # on device. minimize_newton remains available for explicit use
        # (fast and robust on CPU f64).
        # Margin-cached GLM Hessian-vector products: one matvec+rmatvec
        # per CG step instead of jvp-of-grad's ~2x. Without bounds the
        # loop carries the margins: the curvature weights and a trial's
        # value come from them, not from passes over X.
        bounded = lower_bounds is not None or upper_bounds is not None
        return minimize_tron(
            fun, coef0, args=(batch, l2_arr), max_iter=config.max_iterations,
            tol=config.tolerance, lower_bounds=lower_bounds,
            upper_bounds=upper_bounds, track_coefficients=track_coefficients,
            make_hvp=(objective.make_tron_hvp if bounded
                      else objective.make_tron_hvp_at_margins),
            margins_value_and_grad=(
                None if bounded else objective.margins_value_and_grad))
    if l1 > 0:
        if lower_bounds is not None or upper_bounds is not None:
            raise ValueError(
                "box constraints with L1 regularization are not supported")
        return minimize_owlqn(
            fun, coef0, args=(batch, l2_arr), l1_weight=l1,
            max_iter=config.max_iterations, tol=config.tolerance,
            track_coefficients=track_coefficients)
    if lower_bounds is None and upper_bounds is None:
        # Margin-cached fast path: line-search trials cost O(n) instead of a
        # matvec+rmatvec pair (see optimization/glm_lbfgs.py). Box
        # constraints break the affine-margin identity, so bounded solves
        # use the generic projected L-BFGS below.
        return minimize_lbfgs_glm(
            objective, batch, coef0, l2_arr,
            max_iter=config.max_iterations, tol=config.tolerance,
            track_coefficients=track_coefficients)
    return minimize_lbfgs(
        fun, coef0, args=(batch, l2_arr), max_iter=config.max_iterations,
        tol=config.tolerance, lower_bounds=lower_bounds,
        upper_bounds=upper_bounds, track_coefficients=track_coefficients)


