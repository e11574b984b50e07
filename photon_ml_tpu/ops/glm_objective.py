"""The GLM objective: value / gradient / Hessian-vector / Hessian-diagonal.

This single module replaces the reference's entire objective-function layer —
the ObjectiveFunction/DiffFunction/TwiceDiffFunction hierarchy, the
ValueAndGradient/HessianVector/HessianDiagonal aggregators, and the L2
regularization mixins (reference: ml/function/ObjectiveFunction.scala:25,
ml/function/ValueAndGradientAggregator.scala:34-221,
ml/function/HessianVectorAggregator.scala, ml/function/L2Regularization.scala:25-181).

On TPU there is no distributed/single-node split: the same pure function runs

- single-device (local solves),
- `vmap`-batched over an entity axis (random effects — the analog of the
  reference's SingleNodeObjectiveFunction running inside executor tasks), and
- sharded over a device mesh (fixed effects — `jnp.sum` over a batch-sharded
  axis compiles to an ICI all-reduce; the analog of RDD.treeAggregate with
  the coefficient broadcast replaced by replicated-in-HBM params).

The L2 weight is a runtime scalar so a λ-grid sweep never recompiles
(the reference mutates the weight on a live objective for the same reason,
ml/optimization/DistributedOptimizationProblem.scala:59-70).
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from photon_ml_tpu.ops.features import FeatureMatrix
from photon_ml_tpu.ops.losses import PointwiseLoss
from photon_ml_tpu.data.normalization import NormalizationContext
from photon_ml_tpu.telemetry import scopes

Array = jax.Array


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass(frozen=True)
class GLMBatch:
    """Struct-of-arrays training shard resident in HBM.

    The TPU counterpart of RDD[LabeledPoint] (ml/data/LabeledPoint.scala:29-63):
    row order is frozen at ingest, so scores/offsets are plain dense vectors
    and the reference's join-based score exchange becomes elementwise math.

    weights may additionally encode masking: padded rows carry weight 0, which
    removes them from every sum (loss, gradient, Hessian). This is how ragged
    entity blocks and down-sampling are expressed on device.
    """

    features: FeatureMatrix
    labels: Array  # f[n]
    offsets: Array  # f[n]
    weights: Array  # f[n]

    @property
    def num_rows(self) -> int:
        return self.labels.shape[-1]

    def with_offsets(self, offsets: Array) -> "GLMBatch":
        return GLMBatch(self.features, self.labels, offsets, self.weights)

    def tree_flatten(self):
        return (self.features, self.labels, self.offsets, self.weights), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)


def make_batch(features, labels, offsets=None, weights=None) -> GLMBatch:
    labels = jnp.asarray(labels)
    n = labels.shape[-1]
    if offsets is None:
        offsets = jnp.zeros_like(labels)
    if weights is None:
        weights = jnp.ones_like(labels)
    return GLMBatch(features, labels, jnp.asarray(offsets), jnp.asarray(weights))


@dataclasses.dataclass(frozen=True, eq=False)
class GLMObjective:
    """value(coef) = sum_i w_i * l(margin_i, y_i) + l2/2 ||coef||^2.

    NOTE eq=False: objectives hash by identity so that bound methods
    (``objective.value``) are stable jit static arguments — construct ONE
    objective per coordinate/problem and reuse it, or every solve recompiles.

    margin_i = eff . x_i + offset_i - eff . shift, with
    eff = coef .* normalization.factors (see data/normalization.py).

    All methods are pure jnp and close over only static config (loss choice,
    normalization arrays), so they can be jitted / vmapped / pjitted freely.
    ``l2_weight`` is a traced scalar argument.

    Note on the regularization term: like the reference
    (ml/function/L2Regularization.scala:75), L2 applies to ALL coefficients,
    including the intercept, in the (normalized) optimization space.
    """

    loss: PointwiseLoss
    normalization: Optional[NormalizationContext] = None

    # -- margins ----------------------------------------------------------

    def margins(self, coef: Array, batch: GLMBatch) -> Array:
        norm = self.normalization
        if norm is not None:
            eff = norm.effective_coefficients(coef)
            shift = norm.margin_shift(coef)
        else:
            eff, shift = coef, 0.0
        return batch.features.matvec(eff) + batch.offsets + shift

    # -- value / gradient -------------------------------------------------

    def value(self, coef: Array, batch: GLMBatch, l2_weight: Array | float = 0.0
              ) -> Array:
        z = self.margins(coef, batch)
        data_term = jnp.sum(batch.weights * self.loss.loss(z, batch.labels))
        return data_term + 0.5 * l2_weight * jnp.vdot(coef, coef)

    def value_and_grad(
        self, coef: Array, batch: GLMBatch, l2_weight: Array | float = 0.0
    ) -> Tuple[Array, Array]:
        """Fused single-pass value+gradient (XLA fuses loss into the matmul).

        Counterpart of ValueAndGradientAggregator.calculateValueAndGradient
        (ml/function/ValueAndGradientAggregator.scala:243-274) — AD derives
        exactly the factor/shift algebra the reference hand-codes.
        """
        return jax.value_and_grad(self.value)(coef, batch, l2_weight)

    def gradient(self, coef, batch, l2_weight=0.0) -> Array:
        return self.value_and_grad(coef, batch, l2_weight)[1]

    def margin_direction(self, direction: Array, batch: GLMBatch) -> Array:
        """Directional margins: margins are affine in coef, so
        margins(coef + t d) = margins(coef) + t * margin_direction(d).
        This is what lets a line search re-price trial points in O(n)
        (see optimization/glm_lbfgs.py)."""
        return self.margins(direction, batch) - batch.offsets

    def value_from_margins(self, z: Array, coef_sq_norm,
                           batch: GLMBatch, l2_weight) -> Array:
        """Objective value given precomputed margins — no feature contraction."""
        return (jnp.sum(batch.weights * self.loss.loss(z, batch.labels))
                + 0.5 * l2_weight * coef_sq_norm)

    def _jt_product(self, u: Array, batch: GLMBatch) -> Array:
        """J^T u where J = dz/dcoef — the normalization chain rule shared
        by the gradient and the margin-cached Hessian-vector product
        (mirrors ValueAndGradientAggregator.scala:133-154)."""
        r = batch.features.rmatvec(u)
        norm = self.normalization
        if norm is not None:
            if norm.shifts is not None:
                r = r - jnp.sum(u) * norm.shifts
            if norm.factors is not None:
                r = r * norm.factors
        return r

    def gradient_from_margins(
        self, coef: Array, z: Array, batch: GLMBatch,
        l2_weight: Array | float = 0.0,
    ) -> Array:
        """Gradient given precomputed margins: one feature contraction
        (X^T u) instead of the matvec+rmatvec pair jax.grad(value) issues."""
        u = batch.weights * self.loss.d1(z, batch.labels)
        return self._jt_product(u, batch) + l2_weight * coef

    def margins_value_and_grad(
        self, coef: Array, batch: GLMBatch, l2_weight: Array | float = 0.0,
        z: Optional[Array] = None,
    ) -> Tuple[Array, Array, Array]:
        """``(z, value, gradient)`` at ``coef``: its margins (one matvec,
        unless given as ``z``) and the value and gradient from them (one
        rmatvec). The fused TRON's ``margins_value_and_grad`` hook: it
        carries ``z`` through its loop and hands a trial point its margins
        ``z + X s``."""
        if z is None:
            z = self.margins(coef, batch)
        value = self.value_from_margins(z, jnp.vdot(coef, coef), batch,
                                        l2_weight)
        return z, value, self.gradient_from_margins(coef, z, batch,
                                                    l2_weight)

    def curvature_from_margins(self, z: Array, batch: GLMBatch) -> Array:
        """d2_i = w_i l''(z_i, y_i) — the Gauss-Newton curvature weights,
        computed ONCE per outer TRON iteration and reused by every inner
        CG Hessian-vector product (the reference recomputes the margin
        pass inside each HessianVectorAggregator treeAggregate)."""
        return batch.weights * self.loss.d2(z, batch.labels)

    def hessian_vector_from_margins(
        self, vec: Array, d2: Array, batch: GLMBatch,
        l2_weight: Array | float = 0.0,
    ) -> Array:
        """H @ vec with precomputed curvature weights: exactly one
        matvec + one rmatvec (J v is affine: margin_direction), vs the
        ~2x cost of jvp-of-grad which also re-derives the margin pass.
        Traced under ``photon.fe.hvp``: TRON's CG runs one a step."""
        return self.hessian_vector_and_margins(vec, d2, batch, l2_weight)[0]

    def hessian_vector_and_margins(
        self, vec: Array, d2: Array, batch: GLMBatch,
        l2_weight: Array | float = 0.0,
    ) -> Tuple[Array, Array]:
        """``(H @ vec, J vec)``: `hessian_vector_from_margins` and the
        direction's margins it made on the way (``margin_direction``), for
        a caller that sums them (TRON's CG: ``X s`` beside ``s``)."""
        with jax.named_scope(scopes.FE_HVP):
            jv = self.margin_direction(vec, batch)
            return self._jt_product(d2 * jv, batch) + l2_weight * vec, jv

    def make_tron_hvp(self, x: Array, batch: GLMBatch,
                      l2_weight: Array | float = 0.0):
        """Hessian-vector factory for minimize_tron's ``make_hvp`` hook:
        margins + curvature computed once per outer iteration (under the
        solve's own scope), each inner CG product costs one matvec + one
        rmatvec (under ``photon.fe.hvp``). (Bound methods hash by
        (instance, function), so this is a stable jit static argument for
        a persistent objective.)"""
        z = self.margins(x, batch)
        d2 = self.curvature_from_margins(z, batch)
        return lambda v: self.hessian_vector_from_margins(
            v, d2, batch, l2_weight)

    def make_tron_hvp_at_margins(self, z: Array, batch: GLMBatch,
                                 l2_weight: Array | float = 0.0):
        """`make_tron_hvp` from the margins at the point, which the fused
        TRON carries (its ``make_hvp`` where it has
        ``margins_value_and_grad``): no margin pass; each product returns
        the direction's margins too, ``v -> (H v, X v)``."""
        d2 = self.curvature_from_margins(z, batch)
        return lambda v: self.hessian_vector_and_margins(
            v, d2, batch, l2_weight)

    # -- second-order -----------------------------------------------------

    def hessian_vector(
        self, coef: Array, vec: Array, batch: GLMBatch,
        l2_weight: Array | float = 0.0,
    ) -> Array:
        """Gauss-Newton/Hessian product H @ vec via jvp-of-grad.

        Counterpart of HessianVectorAggregator.calcHessianVector
        (ml/function/HessianVectorAggregator.scala) — one distributed product
        per CG step inside TRON.
        """
        grad_fn = lambda c: jax.value_and_grad(self.value)(c, batch, l2_weight)[1]
        return jax.jvp(grad_fn, (coef,), (vec,))[1]

    def hessian_diagonal(
        self, coef: Array, batch: GLMBatch, l2_weight: Array | float = 0.0
    ) -> Array:
        """diag(H) = sum_i w_i l''(z_i) x'_i^2 + l2 — for coefficient variances.

        Counterpart of HessianDiagonalAggregator.calcHessianDiagonal
        (ml/function/HessianDiagonalAggregator.scala). The normalized square
        x'_j^2 = factor_j^2 (x_j - shift_j)^2 expands into the three
        aggregations below so sparsity/batching is preserved.
        """
        z = self.margins(coef, batch)
        d = self.curvature_from_margins(z, batch)
        feats = batch.features
        sq_sum = feats.sq_rmatvec(d)  # sum d_i x_ij^2
        norm = self.normalization
        if norm is not None and (norm.factors is not None or norm.shifts is not None):
            factors = norm.factors
            shifts = norm.shifts
            out = sq_sum
            if shifts is not None:
                lin_sum = feats.rmatvec(d)  # sum d_i x_ij
                total = jnp.sum(d)
                out = sq_sum - 2.0 * shifts * lin_sum + shifts * shifts * total
            if factors is not None:
                out = factors * factors * out
        else:
            out = sq_sum
        return out + l2_weight

    def coefficient_variances(
        self, coef: Array, batch: GLMBatch, l2_weight: Array | float = 0.0,
        epsilon: float = 1e-12,
    ) -> Array:
        """var = 1 / (diag(H) + eps).

        Reference: GeneralizedLinearOptimizationProblem variance computation
        (ml/optimization/GeneralizedLinearOptimizationProblem.scala:39-174,
        ml/optimization/DistributedOptimizationProblem.scala:79-93).
        """
        return 1.0 / (self.hessian_diagonal(coef, batch, l2_weight) + epsilon)
