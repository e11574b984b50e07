"""GLM training over a regularization-weight grid with warm starts.

Reference: ml/ModelTraining.scala:54-214 — the λ grid is sorted descending
and each solve warm-starts from the previous λ's model (fold at :182-207).
Because the regularization weight is a *traced* argument of our solvers, the
whole grid reuses one compiled kernel.
"""

from __future__ import annotations

import dataclasses
import logging
from typing import Dict, List, Optional, Sequence

import jax.numpy as jnp

from photon_ml_tpu.data.normalization import NormalizationContext
from photon_ml_tpu.models.coefficients import Coefficients
from photon_ml_tpu.models.glm import GeneralizedLinearModel, model_for_task
from photon_ml_tpu.models.tracking import ModelTracker
from photon_ml_tpu.ops.features import (
    DENSE_DENSITY_THRESHOLD,
    features_to_device,
)
from photon_ml_tpu.ops.glm_objective import GLMObjective, make_batch
from photon_ml_tpu.ops.losses import loss_for_task
from photon_ml_tpu.optimization.config import (
    GLMOptimizationConfiguration,
    OptimizerType,
    RegularizationContext,
    RegularizationType,
)
from photon_ml_tpu.optimization.convergence import OptimizerResult
from photon_ml_tpu.optimization.solver import solve_glm
from photon_ml_tpu.types import TaskType

logger = logging.getLogger(__name__)


@dataclasses.dataclass
class TrainedGLM:
    reg_weight: float
    model: GeneralizedLinearModel
    result: OptimizerResult
    # Populated when training ran with track_models=True
    # (reference: ml/supervised/model/ModelTracker.scala).
    tracker: Optional["ModelTracker"] = None


def device_batch(features, labels, offsets=None, weights=None,
                 dtype=jnp.float32,
                 dense_threshold: float = DENSE_DENSITY_THRESHOLD,
                 storage_dtype=None, sparse_layout=None):
    """Host arrays -> device GLMBatch, choosing dense vs sparse layout.
    ``storage_dtype=jnp.bfloat16`` halves dense feature HBM traffic
    (f32 accumulation — see DenseFeatures); ``sparse_layout`` names the
    below-threshold layout ("csr" | "bucketed_ell" | "sort_permute_ell");
    None leaves it to the program's chooser (see features_to_device)."""
    feats = features_to_device(features, dtype, dense_threshold,
                               storage_dtype=storage_dtype,
                               sparse_layout=sparse_layout)
    return make_batch(
        feats, jnp.asarray(labels, dtype),
        None if offsets is None else jnp.asarray(offsets, dtype),
        None if weights is None else jnp.asarray(weights, dtype))


def train_glm_models(
    features,
    labels,
    task: TaskType,
    regularization_weights: Sequence[float],
    # L2 by default, matching the reference driver (ml/Params.scala:66-91) —
    # a NONE default would silently ignore the caller's λ grid.
    regularization_context: RegularizationContext = RegularizationContext(
        RegularizationType.L2),
    optimizer_type: OptimizerType = OptimizerType.LBFGS,
    max_iterations: int = 80,
    tolerance: float = 1e-6,
    offsets=None,
    weights=None,
    normalization: Optional[NormalizationContext] = None,
    lower_bounds=None,
    upper_bounds=None,
    warm_start: bool = True,
    compute_variances: bool = False,
    dtype=jnp.float64,
    storage_dtype=None,
    initial_model: Optional[GeneralizedLinearModel] = None,
    track_models: bool = False,
) -> List[TrainedGLM]:
    """Train one GLM per λ, descending, warm-started. Returns grid order
    as given (the reference reports models keyed by λ).
    ``storage_dtype=jnp.bfloat16`` stores dense features at half width
    (solver-dtype accumulation — see DenseFeatures)."""
    batch = device_batch(features, labels, offsets, weights, dtype=dtype,
                         storage_dtype=storage_dtype)
    d = batch.features.num_features
    objective = GLMObjective(loss_for_task(task), normalization)
    glm_cls = model_for_task(task)

    # Box constraints clamp the SOLVE-SPACE iterate — the reference's
    # semantics exactly: its optimization variable is the normalized-
    # space vector (effectiveCoefficients = coef :* factors inside the
    # aggregators, ValueAndGradientAggregator.scala:100-120) and
    # projectCoefficientsToHypercube clamps it against the raw
    # constraint values (LBFGS.scala:77).
    lb = None if lower_bounds is None else jnp.asarray(lower_bounds, dtype)
    ub = None if upper_bounds is None else jnp.asarray(upper_bounds, dtype)

    order = sorted(regularization_weights, reverse=True)
    coef = jnp.zeros((d,), dtype)
    if initial_model is not None:
        coef = jnp.asarray(initial_model.coefficients.means, dtype)
        if normalization is not None:
            coef = normalization.model_to_normalized_space(coef)

    by_weight: Dict[float, TrainedGLM] = {}
    for lam in order:
        config = GLMOptimizationConfiguration(
            max_iterations=max_iterations, tolerance=tolerance,
            regularization_weight=lam,
            optimizer_type=optimizer_type,
            regularization_context=regularization_context)
        result = solve_glm(objective, batch, config, coef, lb, ub,
                           track_coefficients=track_models)
        if warm_start:
            coef = result.x
        variances = None
        if compute_variances:
            l2 = regularization_context.l2_weight(lam)
            variances = objective.coefficient_variances(result.x, batch, l2)
        out_coef = result.x
        if normalization is not None:
            out_coef = normalization.model_to_original_space(out_coef)
        model = glm_cls(Coefficients(out_coef, variances))
        tracker = (ModelTracker.from_result(result, task, normalization)
                   if track_models else None)
        by_weight[lam] = TrainedGLM(lam, model, result, tracker)
        logger.info(
            "lambda=%g: value=%.6f iters=%d reason=%s", lam,
            float(result.value), int(result.iterations),
            result.reason_enum().summary)

    return [by_weight[lam] for lam in regularization_weights]
