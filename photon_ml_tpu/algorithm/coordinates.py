"""Coordinates: the per-block solvers of GAME coordinate descent.

Reference: ml/algorithm/Coordinate.scala:26-82, FixedEffectCoordinate.scala,
RandomEffectCoordinate.scala. The residual-fitting contract is identical —
each coordinate solves against offsets augmented with the *other*
coordinates' scores — but the execution is TPU-native:

- FixedEffectCoordinate: one distributed GLM solve; batch rows (and the CSR
  nnz stream) shard over the mesh's data axis, coefficients replicate, and
  the gradient reduction compiles to an ICI all-reduce (vs. the reference's
  broadcast + treeAggregate per L-BFGS evaluation).
- RandomEffectCoordinate: per-bucket `vmap`-batched solves over the entity
  axis (vs. the reference's per-entity Breeze solves inside mapValues tasks);
  scores come back through a gather by row instead of RDD joins.

Scores here, as in the reference (GameEstimator score semantics), are raw
margins x.coef — offsets are NOT included (they are added by evaluators /
objective computations as needed).
"""

from __future__ import annotations

import contextlib
import dataclasses
import functools
from typing import List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from photon_ml_tpu.data.game_data import GameDataset
from photon_ml_tpu.data.random_effect import EntityBlock, RandomEffectDataset
from photon_ml_tpu.data.sampling import down_sample_weights
from photon_ml_tpu.models.fixed_effect import FixedEffectModel
from photon_ml_tpu.models.glm import model_for_task
from photon_ml_tpu.models.random_effect import RandomEffectModel
from photon_ml_tpu.ops.features import KroneckerFeatures, layout_counts
from photon_ml_tpu.ops.glm_objective import GLMBatch, GLMObjective
from photon_ml_tpu.ops.losses import loss_for_task
from photon_ml_tpu.optimization.config import GLMOptimizationConfiguration
from photon_ml_tpu.optimization.convergence import OptimizerResult
from photon_ml_tpu.optimization.solver import solve_glm
from photon_ml_tpu.telemetry import scopes
from photon_ml_tpu.types import TaskType

Array = jax.Array


def scores_zero_by_construction(initialize_model):
    """Marks an ``initialize_model`` whose model scores zero on every row
    whatever the data: all that multiplies a feature is zero
    (``Coordinate.zero_start`` reads the mark; no frame is added)."""
    initialize_model.scores_zero = True
    return initialize_model


class Coordinate:
    """Interface: update_model(model, residual_scores) and score(model).

    Coordinates additionally expose a PURE functional face used by the
    coordinate-descent driver to fuse a whole coordinate update (residual
    reduce -> solve -> re-score -> objective) into ONE jitted dispatch —
    the TPU answer to the reference's per-phase RDD jobs, and the fix for
    per-dispatch latency dominating small iterations:

    - ``step_data()``     -> pytree of device data, passed explicitly to the
                             jitted step so large arrays are arguments, not
                             baked trace constants;
    - ``params_of(model)``/``model_of(params, model)`` convert between the
                             model object and its trainable pytree;
    - ``pure_update(data, params, residual, key)`` -> (params', tracker);
    - ``pure_score(data, params)``                 -> dense score vector;
    - ``pure_penalties(params)``                   -> (coef, l1, l2) triples.
    All pure_* methods are traceable (no host syncs, fixed shapes).
    """

    name: str

    # whether the coordinate's classes are solved at a latent width and
    # counted under ``training.mf.*`` (and then it has ``factored_work``)
    factored = False
    # whether it is a fixed effect solved by TRON, counted under
    # ``training.fe.cg_steps`` / ``.tron_steps`` / ``.passes`` (and then it
    # has ``tron_work`` and ``tron_passes``)
    tron = False

    @property
    def zero_start(self) -> bool:
        """Whether ``initialize_model()`` is KNOWN to score zero on every
        row (``scores_zero_by_construction``): ``CoordinateDescent.run``
        then builds a cold start's initial scores, and computes them
        (``pure_score``) otherwise. A property of the function that builds
        the model and of nothing else: a class that brings its own
        ``initialize_model`` starts without it."""
        return getattr(type(self).initialize_model, "scores_zero", False)

    def update_model(self, model, residual_scores: Optional[Array], rng_key):
        raise NotImplementedError

    def score(self, model) -> Array:
        raise NotImplementedError

    def initialize_model(self):
        raise NotImplementedError

    def penalties(self, model) -> List[Tuple[Array, Array, Array]]:
        """(coefficients, l1, l2) triples in the optimization space — the
        coordinate's contribution to the coordinate-descent objective
        (CoordinateDescent.scala:203-212). l1/l2 are python floats that
        constant-fold into the jitted objective."""
        raise NotImplementedError

    # -- pure functional face (fused coordinate-descent path) --------------

    def step_data(self):
        raise NotImplementedError

    def params_of(self, model):
        raise NotImplementedError

    def model_of(self, params, model):
        raise NotImplementedError

    def pure_update(self, data, params, residual: Optional[Array], rng_key):
        raise NotImplementedError

    def pure_score(self, data, params) -> Array:
        raise NotImplementedError

    def param_shardings(self):
        """The shardings the fused block takes ``params_of(model)`` with, as
        a pytree of ``NamedSharding`` like the params, where the coordinate's
        data lies over a mesh and the layout follows from that data alone;
        None otherwise (one device, or a layout only the compiler decides).
        ``CoordinateDescent`` places a run's parameters with it once, so no
        dispatch of the block moves them."""
        return None

    def sparse_work(self, trackers=()):
        """``(counts, products)``: what the sparse chooser counted and chose
        for this coordinate's matrix (``ops.features.LayoutCounts``; None
        where no matrix of its came from the chooser), and the sparse
        products the solves of ``trackers`` ran. ``CoordinateDescent`` sums
        these into the ``training.fe.*`` gauges and counter."""
        return None, 0

    def penalty_data(self):
        """Device data the penalty needs beyond the params (e.g. the
        normalization context's factor/shift arrays). Passed back into
        ``pure_penalties`` as an argument so it is never captured as a
        trace constant."""
        return None

    def pure_penalties(self, params,
                       pdata=None) -> List[Tuple[Array, Array, Array]]:
        raise NotImplementedError


def _l1_l2(config: GLMOptimizationConfiguration) -> Tuple[float, float]:
    lam = config.regularization_weight
    rc = config.regularization_context
    return rc.l1_weight(lam), rc.l2_weight(lam)


@dataclasses.dataclass
class FixedEffectCoordinate(Coordinate):
    """Global GLM coordinate (ml/algorithm/FixedEffectCoordinate.scala:34-166)."""

    name: str
    data: GameDataset
    feature_shard_id: str
    task_type: TaskType
    config: GLMOptimizationConfiguration
    lower_bounds: Optional[Array] = None
    upper_bounds: Optional[Array] = None
    normalization: Optional[object] = None  # NormalizationContext
    dtype: object = jnp.float32
    mesh: Optional[object] = None  # jax.sharding.Mesh: shard rows over it
    # Feature-dimension ("model parallel") sharding: coefficients and
    # feature columns shard over the mesh's model axis (falling back to
    # the data axis on a 1-D mesh); on a 2-D (data x model) mesh rows
    # shard over the data axis SIMULTANEOUSLY — the reference's
    # >200k-feature regime (GameEstimator.scala:330-334) composed with
    # its #examples axis. Coefficients are zero-padded to the sharded
    # width inside the update/score dispatches and unpadded on the way
    # out; models always live at the true feature count.
    feature_sharding: bool = False

    def __post_init__(self):
        self._batch = self.data.fixed_effect_batch(
            self.feature_shard_id, dtype=self.dtype)
        self._d = self.data.feature_shards[self.feature_shard_id].shape[1]
        self._d_pad = self._d
        if self.mesh is not None:
            from photon_ml_tpu.parallel import (
                DATA_AXIS,
                MODEL_AXIS,
                shard_batch,
                shard_batch_feature_dim,
            )

            if self.feature_sharding:
                two_d = MODEL_AXIS in self.mesh.shape
                self._batch = shard_batch_feature_dim(
                    self._batch, self.mesh,
                    col_axis=MODEL_AXIS if two_d else DATA_AXIS,
                    row_axis=DATA_AXIS if two_d else None)
                self._d_pad = self._batch.features.shape[-1]
            else:
                self._batch = shard_batch(self._batch, self.mesh)
        norm_solve = self.normalization
        if norm_solve is not None and self._d_pad != self._d:
            # Padded feature columns need inert normalization entries
            # (factor 1 / shift 0) so the padded coordinates stay zero.
            pad = self._d_pad - self._d
            norm_solve = dataclasses.replace(
                norm_solve,
                factors=(None if norm_solve.factors is None else jnp.pad(
                    norm_solve.factors, (0, pad), constant_values=1.0)),
                shifts=(None if norm_solve.shifts is None else jnp.pad(
                    norm_solve.shifts, (0, pad))))
        self._norm_solve = norm_solve
        self._objective = GLMObjective(
            loss_for_task(self.task_type), norm_solve)
        # Penalty scalars as PYTHON floats: they constant-fold into the
        # jitted objective. (Never capture device arrays in hot jitted
        # closures: they are re-staged on every call.)
        self._l1, self._l2 = _l1_l2(self.config)

    def sparse_work(self, trackers=()):
        counts = layout_counts(self._batch.features)
        if counts is None:
            return None, 0
        if self.tron:
            return counts, self.tron_passes(trackers)
        bounded = (self.lower_bounds is not None
                   or self.upper_bounds is not None)
        # A margin-cached L-BFGS solve of ``it`` iterations is ``it + 1``
        # matvec and ``it + 1`` rmatvec whatever its line search does; of
        # an OWL-QN or bounded solve the iterations are not products.
        if self._l1 or bounded:
            return counts, 0
        return counts, sum(2 * (int(np.asarray(tr.iterations)) + 1)
                           for tr in trackers)

    @property
    def tron(self) -> bool:
        return self.config.optimizer_type.name == "TRON"

    def tron_work(self, trackers=()):
        """``(cg_steps, attempted)``: what the trust-region solves of
        ``trackers`` ran, from the solver's own counts (``OptimizerResult.
        cg_iterations`` / ``.attempted_iterations``)."""
        counted = [tr for tr in trackers if tr.cg_iterations is not None]
        return (sum(int(np.asarray(tr.cg_iterations)) for tr in counted),
                sum(int(np.asarray(tr.attempted_iterations))
                    for tr in counted))

    def tron_passes(self, trackers=()) -> int:
        """The reads of X the trust-region solves of ``trackers`` made,
        from the solver's own count (``OptimizerResult.feature_passes``)."""
        return sum(int(np.asarray(tr.feature_passes)) for tr in trackers
                   if tr.feature_passes is not None)

    def _pad_d(self, arr, fill=0.0):
        """Zero-pad a [d] vector to the feature-sharded width (no-op
        without feature sharding)."""
        if arr is None or self._d_pad == self._d:
            return arr
        return jnp.pad(jnp.asarray(arr), (0, self._d_pad - self._d),
                       constant_values=fill)

    @scores_zero_by_construction
    def initialize_model(self) -> FixedEffectModel:
        d = self.data.feature_shards[self.feature_shard_id].shape[1]
        glm_cls = model_for_task(self.task_type)
        from photon_ml_tpu.models.coefficients import Coefficients
        return FixedEffectModel(
            glm_cls(Coefficients.zeros(d, self.dtype)), self.feature_shard_id)

    def update_model(
        self, model: FixedEffectModel, residual_scores: Optional[Array],
        rng_key,
    ) -> Tuple[FixedEffectModel, object]:
        # Models live in the ORIGINAL feature space; the solve happens in the
        # normalized space (reference: the estimator converts trained
        # coefficients back through the NormalizationContext). Residual
        # padding, down-sampling, the space transforms and the solve all run
        # as one jitted dispatch.
        coef, result = self.pure_update(
            self.step_data(), self.params_of(model), residual_scores,
            rng_key)
        from photon_ml_tpu.models.coefficients import Coefficients
        new_glm = model.glm.update_coefficients(Coefficients(coef))
        return model.update_model(new_glm), result

    def score(self, model: FixedEffectModel) -> Array:
        # Original-space coefficients against raw features — consistent with
        # host-side scoring (FixedEffectModel.score_numpy). The batch may be
        # row-padded for sharding; scores are truncated to the true row count
        # so they align with other coordinates' score vectors. One jitted
        # dispatch (matvec + slice fused).
        return _fe_score_impl(self._pad_d(model.glm.coefficients.means),
                              self._batch.features,
                              n_rows=self.data.num_rows)

    def penalties(self, model: FixedEffectModel):
        # The penalty applies in the optimization (normalized) space.
        return self.pure_penalties(model.glm.coefficients.means,
                                   self.normalization)

    # -- pure functional face ----------------------------------------------

    def step_data(self):
        # _norm_solve (padded to the sharded width when feature sharding
        # is on) is what the solve-space transforms inside _solve_fixed
        # must use. Bounds clamp the solve-space iterate directly —
        # reference semantics (the Breeze iterate IS the normalized-space
        # vector; projectCoefficientsToHypercube clamps it raw,
        # LBFGS.scala:77). Penalties on unpadded params use
        # self.normalization.
        return (self._batch, self._norm_solve, self.lower_bounds,
                self.upper_bounds)

    def params_of(self, model: FixedEffectModel) -> Array:
        return model.glm.coefficients.means

    def model_of(self, params: Array, model: FixedEffectModel):
        from photon_ml_tpu.models.coefficients import Coefficients
        return model.update_model(
            model.glm.update_coefficients(Coefficients(params)))

    def param_shardings(self):
        # Rows shard, coefficients replicate. Feature-sharded coefficients
        # are padded inside the block, so their layout is the compiler's.
        if self.mesh is None or self.feature_sharding:
            return None
        from jax.sharding import NamedSharding, PartitionSpec

        return NamedSharding(self.mesh, PartitionSpec())

    def pure_update(self, data, params, residual, rng_key):
        batch, normalization, lb, ub = data
        result, coef = _solve_fixed(
            self._objective, self.config, self.task_type.is_classification,
            batch, residual, rng_key, self._pad_d(params),
            self._pad_d(lb, -jnp.inf), self._pad_d(ub, jnp.inf),
            normalization)
        if self._d_pad != self._d:
            coef = coef[: self._d]
        return coef, result

    def pure_score(self, data, params) -> Array:
        batch = data[0]
        return _fe_score_impl(self._pad_d(params), batch.features,
                              n_rows=self.data.num_rows)

    def penalty_data(self):
        return self.normalization

    def pure_penalties(self, params, pdata=None):
        coef = params
        if pdata is not None:
            coef = pdata.model_to_normalized_space(coef)
        return [(coef, self._l1, self._l2)]


@dataclasses.dataclass
class StreamingFixedEffectCoordinate:
    """Out-of-core fixed-effect solver over a device shard cache — the
    spill-mode (`--hbm-budget`) counterpart of FixedEffectCoordinate.

    Where FixedEffectCoordinate holds ONE device batch and solves inside
    a fused `lax.while_loop`, this coordinate accumulates (value,
    gradient, Hessian-vector) per-shard over a
    :class:`~photon_ml_tpu.data.shard_cache.DeviceShardCache`
    (ops/sharded_objective.py) and drives the solve from the host
    (optimization/glm_lbfgs.py `minimize_lbfgs_glm_streaming` /
    optimization/tron.py `minimize_tron_streaming`) — the treeAggregate
    shape of the reference's distributed solve, with HBM as the
    partition cache tier.

    Scope (enforced): L-BFGS or TRON with L2 only — no L1/OWL-QN, box
    constraints, normalization context, or down-sampling (< 1.0). Those
    configurations stream-train through the resident assembled path,
    which reuses the full one-shot machinery.

    ``mesh`` (`--mesh-devices` / `--mesh-shape`) activates the device
    fold: the cache must be placed on the same devices
    (`DeviceShardCache.from_stream(devices=...)`); per-shard partials
    accumulate on their own device and combine in fixed shard order, so
    the solved model is bit-identical for every mesh size
    (ops/sharded_objective.py). A 2-D (data x model) mesh
    (`make_mesh_2d(R, C)`, C > 1) additionally shards the coefficient
    dimension: the cache must then be built with ``col_blocks=C``, and
    the solved model stays bitwise-identical across mesh shapes
    {1x1, 2x1, 1x2, 2x2} (sharded_objective module docstring; the
    solver-facing convergence state stays full-width on the host).
    """

    name: str
    cache: object  # DeviceShardCache
    feature_shard_id: str
    task_type: TaskType
    config: GLMOptimizationConfiguration
    dtype: object = jnp.float32
    tracing_guard: Optional[object] = None
    # Reuse a previously built ShardedGLMObjective (λ-grid sweeps: the l2
    # weight is a traced argument, so sharing the objective shares every
    # compiled accumulate kernel across grid points — the same
    # no-recompile contract as the resident solvers).
    sharded_objective: Optional[object] = None
    mesh: Optional[object] = None  # 1-D or 2-D jax.sharding.Mesh (device fold)

    def __post_init__(self):
        from photon_ml_tpu.optimization.config import OptimizerType
        from photon_ml_tpu.ops.sharded_objective import ShardedGLMObjective

        l1, l2 = _l1_l2(self.config)
        if l1 > 0:
            raise ValueError(
                "streaming fixed-effect solves support L2 only; "
                "L1/elastic-net needs the resident (assembled) path")
        if self.config.down_sampling_rate < 1.0:
            raise ValueError(
                "down-sampling is not supported with --hbm-budget "
                "streaming solves (per-row randomness is defined on the "
                "full batch); use the resident path")
        if self.config.optimizer_type not in (OptimizerType.LBFGS,
                                              OptimizerType.TRON):
            raise ValueError(
                f"streaming fixed-effect solves support LBFGS/TRON, got "
                f"{self.config.optimizer_type}")
        self._l2 = l2
        if self.sharded_objective is not None:
            if self.sharded_objective.cache is not self.cache:
                raise ValueError(
                    "shared sharded_objective must wrap the same cache")
            want = None
            if self.mesh is not None:
                from photon_ml_tpu.parallel import mesh_fold_devices

                devs = mesh_fold_devices(self.mesh)
                want = devs if len(devs) > 1 else None
            if self.sharded_objective.devices != want:
                raise ValueError(
                    "shared sharded_objective must use the same mesh "
                    f"(objective devices {self.sharded_objective.devices}, "
                    f"coordinate mesh devices {want})")
            self._sharded = self.sharded_objective
            self._objective = self._sharded.objective
        else:
            self._objective = GLMObjective(loss_for_task(self.task_type))
            self._sharded = ShardedGLMObjective(
                self._objective, self.cache,
                tracing_guard=self.tracing_guard, mesh=self.mesh)
            # Expose the built objective through the same field callers
            # pass it back in with (grid sweeps share compiled kernels).
            self.sharded_objective = self._sharded

    def initialize_model(self) -> FixedEffectModel:
        from photon_ml_tpu.models.coefficients import Coefficients

        glm_cls = model_for_task(self.task_type)
        return FixedEffectModel(
            glm_cls(Coefficients.zeros(self.cache.n_features, self.dtype)),
            self.feature_shard_id)

    def solve(self, model: Optional[FixedEffectModel] = None,
              trace_ctx=None, convergence_ring=None, margins_out=None
              ) -> Tuple[FixedEffectModel, OptimizerResult]:
        """One full-batch GLM solve by streamed accumulation (warm-started
        from ``model`` when given). ``trace_ctx`` — the solve's trace
        context (telemetry/tracectx.py; the streaming driver mints one
        per λ-grid point), threaded into the host-driven solver for
        per-iteration events and divergence-watchdog tagging.
        ``convergence_ring`` / ``margins_out`` — the ``--distmon``
        distribution-observability hooks, threaded through to the
        host-driven solvers (see ``minimize_lbfgs_glm_streaming``)."""
        from photon_ml_tpu.optimization.config import OptimizerType
        from photon_ml_tpu.optimization.glm_lbfgs import (
            minimize_lbfgs_glm_streaming,
        )
        from photon_ml_tpu.optimization.tron import minimize_tron_streaming

        if model is None:
            model = self.initialize_model()
        coef0 = jnp.asarray(model.glm.coefficients.means, self.dtype)
        if self.config.optimizer_type == OptimizerType.TRON:
            if not self._objective.loss.twice_differentiable:
                raise ValueError(
                    f"TRON requires a twice-differentiable loss, got "
                    f"{self._objective.loss.name}")
            result = minimize_tron_streaming(
                self._sharded, coef0, self._l2,
                max_iter=self.config.max_iterations,
                tol=self.config.tolerance, trace_ctx=trace_ctx,
                convergence_ring=convergence_ring,
                margins_out=margins_out)
        else:
            result = minimize_lbfgs_glm_streaming(
                self._sharded, coef0, self._l2,
                max_iter=self.config.max_iterations,
                tol=self.config.tolerance, trace_ctx=trace_ctx,
                convergence_ring=convergence_ring,
                margins_out=margins_out)
        self._sharded.assert_trace_budget()
        from photon_ml_tpu.models.coefficients import Coefficients

        new_glm = model.glm.update_coefficients(Coefficients(result.x))
        return model.update_model(new_glm), result


def grid_batchable(configs) -> Tuple[bool, str]:
    """Can this λ-grid run as ONE batched streamed solve
    (:func:`solve_fixed_effect_grid`)? True only when every point is a
    streamable L2 solve and the points are homogeneous in everything
    but ``regularization_weight`` — the batched solvers share one
    candidate schedule / trust-region recipe across rows, so only the
    λ row may vary. Returns ``(ok, why_not)``."""
    from photon_ml_tpu.optimization.config import OptimizerType

    configs = list(configs)
    if not configs:
        return False, "empty grid"
    base = configs[0]
    if base.optimizer_type not in (OptimizerType.LBFGS,
                                   OptimizerType.TRON):
        return False, (f"streaming grid solves support LBFGS/TRON, got "
                       f"{base.optimizer_type}")
    for cfg in configs:
        l1, _ = _l1_l2(cfg)
        if l1 > 0:
            return False, ("L1/elastic-net grid points need the "
                           "resident path")
        if cfg.down_sampling_rate < 1.0:
            return False, ("down-sampling is not supported by streamed "
                           "solves")
        if (cfg.optimizer_type != base.optimizer_type
                or cfg.max_iterations != base.max_iterations
                or cfg.tolerance != base.tolerance):
            return False, (
                "grid points must share optimizer type, max_iterations "
                "and tolerance to batch — only the regularization "
                "weight may vary across rows")
    return True, ""


def solve_fixed_effect_grid(
    coordinate: StreamingFixedEffectCoordinate,
    configs,
    models=None,
    trace_ctxs=None,
    convergence_rings=None,
    margins_out=None,
) -> List[Tuple[FixedEffectModel, OptimizerResult]]:
    """Solve a whole λ-grid in ONE batched streamed sweep: coefficients
    stack to ``[G, d]`` and every feature pass of the underlying grid
    solver (optimization/glm_lbfgs.py `minimize_lbfgs_glm_grid_streaming`
    / tron.py `minimize_tron_grid_streaming`) advances all G points —
    a sweep costs the slowest row's pass count instead of the sum over
    rows (~G× less decode+H2D traffic).

    ``coordinate`` supplies the cache/objective/task (its own config
    must be one of the homogeneous grid's shapes); ``configs`` is the
    λ-grid (validated via :func:`grid_batchable` — ValueError with the
    reason when not batchable). ``models`` warm-starts per row
    (row-aligned list, entries may be None). ``trace_ctxs`` /
    ``convergence_rings`` / ``margins_out`` thread through to the grid
    solver (per-row observability; ``margins_out`` receives the
    ``[G, rows]`` per-shard margins — slice rows out with
    ``ShardedGLMObjective.grid_row_margins``).

    Returns a row-aligned list of ``(FixedEffectModel, OptimizerResult)``
    — the same pairs G sequential ``coordinate.solve`` calls produce.
    G=1 delegates to the scalar streamed solver inside the grid solver
    (bitwise gate), so this entry point is safe for any grid size.
    """
    from photon_ml_tpu.models.coefficients import Coefficients
    from photon_ml_tpu.optimization.config import OptimizerType
    from photon_ml_tpu.optimization.glm_lbfgs import (
        minimize_lbfgs_glm_grid_streaming,
    )
    from photon_ml_tpu.optimization.tron import minimize_tron_grid_streaming

    configs = list(configs)
    ok, why = grid_batchable(configs)
    if not ok:
        raise ValueError(f"λ-grid is not batchable: {why}")
    G = len(configs)
    if models is None:
        models = [None] * G
    models = [m if m is not None else coordinate.initialize_model()
              for m in models]
    if len(models) != G:
        raise ValueError(
            f"models must be row-aligned with the grid (G={G}), got "
            f"{len(models)}")

    dtype = coordinate.dtype
    x0s = jnp.stack([jnp.asarray(m.glm.coefficients.means, dtype)
                     for m in models])
    l2s = np.asarray([_l1_l2(cfg)[1] for cfg in configs],
                     np.dtype(dtype))
    base = configs[0]
    if base.optimizer_type == OptimizerType.TRON:
        if not coordinate._objective.loss.twice_differentiable:
            raise ValueError(
                f"TRON requires a twice-differentiable loss, got "
                f"{coordinate._objective.loss.name}")
        results = minimize_tron_grid_streaming(
            coordinate._sharded, x0s, l2s,
            max_iter=base.max_iterations, tol=base.tolerance,
            trace_ctxs=trace_ctxs, convergence_rings=convergence_rings,
            margins_out=margins_out)
    else:
        results = minimize_lbfgs_glm_grid_streaming(
            coordinate._sharded, x0s, l2s,
            max_iter=base.max_iterations, tol=base.tolerance,
            trace_ctxs=trace_ctxs, convergence_rings=convergence_rings,
            margins_out=margins_out)
    coordinate._sharded.assert_trace_budget()

    out = []
    for model, result in zip(models, results):
        new_glm = model.glm.update_coefficients(Coefficients(result.x))
        out.append((model.update_model(new_glm), result))
    return out


@dataclasses.dataclass
class RandomEffectCoordinate(Coordinate):
    """Entity-sharded coordinate
    (ml/algorithm/RandomEffectCoordinate.scala:36-201).

    ``normalization`` (a NormalizationContext over the GLOBAL feature
    space) and ``lower_bounds``/``upper_bounds`` (global [d] arrays)
    mirror the reference's per-problem normalization + constraintMap
    (RandomEffectOptimizationProblem.scala:105-125,
    OptimizationUtils.scala:53): both are gathered into each block's
    local feature space through feat_idx at construction, ride along
    as device data, and fold into the fused Pallas kernel (or the
    vmapped fallback) — no silent perf cliff for normalized/bounded
    configs. Models stay in the ORIGINAL space; solves happen in the
    normalized space with per-entity transforms on the way in/out.

    ``mesh`` shards every block's entity axis over the mesh's ``data``
    axis, and the coordinate then runs per device over its own entities:
    the solves (the kernel under ``shard_map``, the vmapped solver
    partitioned by entity) and the score exchange with the row-sharded
    n-vectors. What crosses the devices: one all-reduce of the residual
    each update, which makes it whole (``_whole_residual``) for each device
    to gather its own slots from, and one all-gather of the flat margins
    each scoring, from which each device gathers its own row range
    (``_scores_by_row``). Nothing of a block's ``[E, r]`` shape crosses
    devices, and the scores are bitwise the one-device ones.

    The scores come back to row order through ``slot_of_row``, an index
    built here, once, from the blocks' own ``row_ids`` (``_slot_of_row``):
    a row sits in one slot of one block, and a dataset in which one sits
    in two is refused."""

    name: str
    dataset: RandomEffectDataset
    task_type: TaskType
    config: GLMOptimizationConfiguration
    mesh: Optional[object] = None  # jax.sharding.Mesh: shard entities over it
    lower_bounds: Optional[Array] = None  # global feature space, [d]
    upper_bounds: Optional[Array] = None
    normalization: Optional[object] = None  # NormalizationContext (global)

    def __post_init__(self):
        if self.mesh is not None:
            self.dataset = _shard_re_dataset(self.dataset, self.mesh)
        self._objective = GLMObjective(loss_for_task(self.task_type))
        self._l1, self._l2 = _l1_l2(self.config)
        if (self.normalization is not None
                and self.dataset.projection is not None):
            raise ValueError(
                "normalization on a projected random-effect dataset is "
                "not supported — latent columns are not global features")
        self._norm_blocks = tuple(
            _gather_block_normalization(self.normalization, b)
            for b in self.dataset.blocks)
        # Bounds clamp the SOLVE-SPACE (normalized) coefficients — the
        # reference's exact semantics: its optimizer iterate is the
        # normalized-space vector (the aggregators compute margins via
        # effectiveCoefficients = coef :* factors,
        # ValueAndGradientAggregator.scala:100-120) and
        # projectCoefficientsToHypercube clamps that iterate against the
        # raw constraint values (LBFGS.scala:77,
        # OptimizationUtils.scala:53). No space conversion.
        self._bounds_blocks = tuple(
            _gather_block_bounds(self.lower_bounds, self.upper_bounds, b)
            for b in self.dataset.blocks)
        self._slot_of_row, self.unslotted_rows = _slot_of_row(
            self.dataset, self.mesh)

    def routing(self) -> List[dict]:
        """Per bucket, what the solve will do with it: the size class
        (``rows``), its entities and slots, and the path the guard picks
        (``kernel``, or ``vmapped`` with the guard's ``reason``). Decided
        from shapes and configuration alone, as the trace decides it."""
        return _class_routing(self.dataset.blocks, [
            _kernel_refusal(self._objective, self.config, block.x,
                            norm=norm, bounds=bounds)
            for block, norm, bounds in zip(self.dataset.blocks,
                                           self._norm_blocks,
                                           self._bounds_blocks)])

    def true_rows(self) -> int:
        """Rows that are data and not padding, over all buckets (one small
        device reduction a bucket: for set-up and reports, not the loop)."""
        return _true_rows(self.dataset)

    @scores_zero_by_construction
    def initialize_model(self) -> RandomEffectModel:
        dt = (self.dataset.blocks[0].x.dtype if self.dataset.blocks
              else jnp.float32)
        return RandomEffectModel.zeros_like_dataset(self.dataset, dtype=dt)

    def update_model(
        self, model: RandomEffectModel, residual_scores: Optional[Array],
        rng_key,
    ) -> Tuple[RandomEffectModel, List[object]]:
        """vmap-batched per-entity solves, one kernel per bucket
        (the TPU analog of the activeData.join(problems).join(models)
        mapValues solve, RandomEffectCoordinate.scala:104-113)."""
        params, trackers = self.pure_update(
            self.step_data(), self.params_of(model), residual_scores,
            rng_key)
        return self.model_of(params, model), trackers

    def score(self, model: RandomEffectModel) -> Array:
        """All bucket margins + the gather by row as ONE jitted dispatch
        (the eager per-block einsum/concatenate/gather chain costs several
        dispatches per call)."""
        return _re_score_impl(
            tuple(self.dataset.blocks), tuple(self.dataset.passive_blocks),
            tuple(model.local_coefs), self._slot_of_row,
            n_rows=self.dataset.n_rows, mesh=self.mesh)

    def penalties(self, model: RandomEffectModel):
        return self.pure_penalties(tuple(model.local_coefs),
                                   self.penalty_data())

    # -- pure functional face ----------------------------------------------

    def step_data(self):
        return (tuple(self.dataset.blocks),
                tuple(self.dataset.passive_blocks),
                self._norm_blocks, self._bounds_blocks, self._slot_of_row)

    def params_of(self, model: RandomEffectModel):
        return tuple(model.local_coefs)

    def model_of(self, params, model: RandomEffectModel):
        return model.with_coefs(list(params))

    def param_shardings(self):
        # each size class's [E, d] split by entity, like its block
        if self.mesh is None:
            return None
        from jax.sharding import NamedSharding, PartitionSpec

        from photon_ml_tpu.parallel import DATA_AXIS

        sharding = NamedSharding(self.mesh, PartitionSpec(DATA_AXIS))
        return tuple(sharding for _ in self.dataset.blocks)

    def pure_update(self, data, params, residual, rng_key):
        # All bucket solves trace into the caller's single dispatch (vs one
        # dispatch per size-class bucket when called eagerly). Original-
        # space warm starts convert to the solve (normalized) space, and
        # solutions convert back (GameEstimator-side semantics in the
        # reference; here per entity via the gathered transforms).
        from photon_ml_tpu.data.normalization import (
            gathered_to_normalized_space,
            gathered_to_original_space,
        )

        blocks, _, norm_blocks, bounds_blocks, _ = data
        if self.mesh is not None:
            residual = _whole_residual(residual, self.mesh)
        new_coefs, results = [], []
        for block, c0, norm, bounds in zip(blocks, params, norm_blocks,
                                           bounds_blocks):
            if norm is not None:
                with _re_solve_scope(block):
                    c0 = gathered_to_normalized_space(c0, *norm)
            result = _solve_block(
                self._objective, self.config, block, residual, c0,
                mesh=self.mesh, norm=norm, bounds=bounds)
            coef = result.x
            if norm is not None:
                with _re_solve_scope(block):
                    coef = gathered_to_original_space(coef, *norm)
            new_coefs.append(coef)
            results.append(result)
        return tuple(new_coefs), results

    def pure_score(self, data, params) -> Array:
        blocks, pblocks, slot_of_row = data[0], data[1], data[-1]
        return _re_score_impl(blocks, pblocks, tuple(params), slot_of_row,
                              n_rows=self.dataset.n_rows, mesh=self.mesh)

    def penalty_data(self):
        return self._norm_blocks

    def pure_penalties(self, params, pdata=None):
        # The penalty applies in the optimization (normalized) space,
        # like the fixed effect (L2Regularization.scala:75).
        from photon_ml_tpu.data.normalization import (
            gathered_to_normalized_space,
        )

        norm_blocks = pdata if pdata is not None else (None,) * len(params)
        out = []
        for c, norm in zip(params, norm_blocks):
            if norm is not None:
                c = gathered_to_normalized_space(c, *norm)
            out.append((c, self._l1, self._l2))
        return out


def _class_routing(blocks, refusals) -> List[dict]:
    """``routing()``'s rows: a size class a block, with the guard's word
    on it (``_kernel_refusal``'s result, None where the kernel takes it)."""
    out = []
    for block, refusal in zip(blocks, refusals):
        e, r, _ = block.x.shape
        out.append({"rows": int(r), "entities": int(e),
                    "slots": int(e) * int(r),
                    "path": "kernel" if refusal is None else "vmapped",
                    "reason": refusal and refusal[0]})
    return out


def _true_rows(dataset: RandomEffectDataset) -> int:
    return sum(int(jnp.sum(b.row_ids < dataset.n_rows))
               for b in dataset.blocks)


def _gather_block_normalization(normalization, block: EntityBlock):
    """(factors, shifts, intercept_mask) in the block's local feature
    space, or None when no normalization is active (see
    data/normalization.py gather_normalization)."""
    if normalization is None:
        return None
    from photon_ml_tpu.data.normalization import gather_normalization

    factors, shifts, mask = gather_normalization(normalization,
                                                 block.feat_idx)
    if factors is None and shifts is None:
        return None
    dt = block.x.dtype
    conv = lambda a: None if a is None else a.astype(dt)
    return conv(factors), conv(shifts), mask.astype(dt)


def _gather_block_bounds(lower, upper, block: EntityBlock):
    """(lower, upper) [E, d] in the block's local feature space, or None.
    Padding columns (feat_idx == -1) are unbounded — their coefficients
    are driven to zero by L2 and never touch data."""
    if lower is None and upper is None:
        return None
    dt = block.x.dtype
    safe = jnp.maximum(block.feat_idx, 0)
    pad = block.feat_idx < 0

    def gather(vec, default):
        if vec is None:
            return jnp.full(block.feat_idx.shape, default, dt)
        return jnp.where(pad, default, jnp.asarray(vec, dt)[safe])

    return gather(lower, -jnp.inf), gather(upper, jnp.inf)


def _shard_re_dataset(dataset: RandomEffectDataset, mesh
                      ) -> RandomEffectDataset:
    """Shard every (active + passive) bucket's entity axis over the mesh."""
    from photon_ml_tpu.parallel import shard_block

    return dataclasses.replace(
        dataset,
        blocks=[shard_block(b, mesh, sentinel_row=dataset.n_rows)
                for b in dataset.blocks],
        passive_blocks=[
            None if b is None else
            shard_block(b, mesh, sentinel_row=dataset.n_rows)
            for b in dataset.passive_blocks],
    )


@dataclasses.dataclass
class StreamingFactoredRandomEffectCoordinate:
    """Out-of-core factored random effect (matrix factorization) — the
    streamed/sharded counterpart of :class:`FactoredRandomEffectCoordinate`,
    built on `ops/mf_alternating.py` + `data/factor_cache.py` (PAPERS.md
    "ALX: Large Scale Matrix Factorization on TPUs"): factor tables live
    in a budgeted `DeviceFactorCache` (pow-2 observation-count bucketing,
    replay-aware eviction, f32/bf16/redecode spill tiers), observations
    stream through `BlockGameStream` batches re-decoded per feature pass,
    the per-entity gamma half-step is an exact streamed ridge ALS
    (batched per-bucket normal-equation solves), and the projection
    refit reuses `minimize_lbfgs_glm_streaming` over the duck-typed
    Kronecker-margin objective. Factor tables larger than
    ``hbm_budget_bytes`` train to completion out-of-core.

    Scope (enforced): LINEAR_REGRESSION (squared loss — the alternating
    half-steps are least squares; other GLM losses alternate IRLS
    in-core), L2-only with a strictly positive gamma ridge (λ₂ = 0
    normal equations are singular for low-observation entities), no
    down-sampling, L-BFGS latent refits. Everything else trains through
    the in-core coordinate.

    Plugs into coordinate descent behind the existing residual-fitting
    contract: ``solve(model, residual_scores=...)`` folds the other
    coordinates' scores into the streamed offsets, and ``score(model)``
    returns raw margins γᵀ B x. Each alternating sweep runs under its
    own minted `TraceContext` (kind ``mf_sweep`` — slow sweeps land on
    /tracez) and the per-sweep objective is checked by
    `check_solver_finite`, so a NaN/Inf alternating solve raises a typed
    :class:`~photon_ml_tpu.optimization.convergence.SolverDivergedError`
    with a trace-tagged flight dump, like the streamed L-BFGS/TRON
    paths.

    ``mf_objective`` shares the built `StreamedMFObjective` (plan +
    factor cache + compiled kernels) across λ-grid points with the same
    ``num_factors`` — the same no-recompile sharing contract as
    `StreamingFixedEffectCoordinate.sharded_objective`.
    """

    name: str
    make_stream: object  # () -> iterable of GameDataset batches
    feature_shard_id: str
    random_effect_type: str
    task_type: TaskType
    config: GLMOptimizationConfiguration  # per-entity gamma ridge
    latent_config: GLMOptimizationConfiguration  # projection refit
    mf_config: "MFOptimizationConfiguration"
    n_features: Optional[int] = None  # settled by the planning pass
    hbm_budget_bytes: Optional[int] = None
    spill_dtype: str = "f32"
    spill_source: str = "buffer"
    entities_per_shard: int = 512
    seed: int = 7
    tracing_guard: Optional[object] = None
    mf_objective: Optional[object] = None  # shared StreamedMFObjective
    random_access: Optional[object] = None  # BlockRandomAccess hook

    def __post_init__(self):
        from photon_ml_tpu.optimization.config import OptimizerType

        if self.task_type != TaskType.LINEAR_REGRESSION:
            raise ValueError(
                "streamed MF alternating least squares is defined for "
                "LINEAR_REGRESSION (squared loss); other tasks train "
                "through the in-core FactoredRandomEffectCoordinate")
        l1, l2 = _l1_l2(self.config)
        ll1, self._ll2 = _l1_l2(self.latent_config)
        if l1 > 0 or ll1 > 0:
            raise ValueError(
                "streamed MF supports L2 only; L1/elastic-net factors "
                "need the in-core path")
        if l2 <= 0:
            raise ValueError(
                "streamed MF needs a strictly positive gamma L2 weight "
                "(the per-entity ridge normal equations are singular at "
                "λ₂ = 0 for low-observation entities)")
        if self.config.down_sampling_rate < 1.0 \
                or self.latent_config.down_sampling_rate < 1.0:
            raise ValueError(
                "down-sampling is not supported with streamed MF "
                "solves; use the in-core path")
        if self.latent_config.optimizer_type != OptimizerType.LBFGS:
            raise ValueError(
                f"streamed MF latent refits support LBFGS, got "
                f"{self.latent_config.optimizer_type}")
        self._l2 = l2
        k = self.mf_config.num_factors
        if self.mf_objective is not None:
            if self.mf_objective.k != k:
                raise ValueError(
                    f"shared mf_objective was built for num_factors="
                    f"{self.mf_objective.k}, coordinate asks for {k}")
            self._obj = self.mf_objective
        else:
            from photon_ml_tpu.data.factor_cache import (
                DeviceFactorCache,
                count_stream_entities,
                plan_factors,
            )
            from photon_ml_tpu.ops.mf_alternating import (
                StreamedMFObjective,
            )

            with _telemetry_span("factor_plan"):
                vocab, counts, n_rows, d_by_shard = count_stream_entities(
                    self.make_stream(), self.random_effect_type)
            if self.feature_shard_id not in d_by_shard:
                raise KeyError(
                    f"stream carries no feature shard "
                    f"{self.feature_shard_id!r} "
                    f"(have {sorted(d_by_shard)})")
            d = d_by_shard[self.feature_shard_id]
            if self.n_features is not None and self.n_features != d:
                raise ValueError(
                    f"stream decodes {d} features for shard "
                    f"{self.feature_shard_id!r}, coordinate expected "
                    f"{self.n_features}")
            self.n_features = d
            plan = plan_factors(vocab, counts,
                                entities_per_shard=self.entities_per_shard)
            cache = DeviceFactorCache(
                plan, k, hbm_budget_bytes=self.hbm_budget_bytes,
                spill_dtype=self.spill_dtype,
                spill_source=self.spill_source)
            self._obj = StreamedMFObjective(
                self.make_stream, self.feature_shard_id,
                self.random_effect_type, plan, cache, d,
                loss_for_task(self.task_type),
                tracing_guard=self.tracing_guard,
                random_access=self.random_access)
            self._obj.n_rows = n_rows
            self.mf_objective = self._obj
        self.n_features = self._obj.d

    @property
    def cache(self):
        """The factor cache (live /statusz residency provider)."""
        return self._obj.cache

    @property
    def plan(self):
        return self._obj.plan

    def initialize_model(self):
        """Zero factors + the SAME seeded Gaussian projection init as
        the in-core coordinate, so streamed-vs-in-core parity starts
        from identical B₀."""
        from photon_ml_tpu.models.factored_random_effect import (
            FactoredRandomEffectModel,
        )
        from photon_ml_tpu.projector.projectors import ProjectionMatrix

        k = self.mf_config.num_factors
        d = self.n_features
        plan = self._obj.plan
        b0 = ProjectionMatrix.gaussian(k, d, intercept_col=None,
                                       seed=self.seed)
        latent = RandomEffectModel(
            random_effect_type=self.random_effect_type,
            feature_shard_id=self.feature_shard_id,
            local_coefs=[jnp.zeros((s.n_entities, k), jnp.float32)
                         for s in plan.shards],
            feat_idx=[jnp.tile(jnp.arange(k), (s.n_entities, 1))
                      for s in plan.shards],
            entity_codes=[s.codes.astype(np.int32) for s in plan.shards],
            vocabulary=plan.vocabulary,
            num_global_features=d,
            projection=b0,
        )
        return FactoredRandomEffectModel(latent, self.mf_config)

    def solve(self, model=None, residual_scores=None, trace_ctx=None):
        """``mf_config.max_iterations`` alternating sweeps (streamed
        ridge gamma pass + streamed L-BFGS projection refit), warm-
        starting B from ``model``. Returns ``(model, trackers)`` with
        one OptimizerResult per sweep — the in-core coordinate's
        tracker shape."""
        from photon_ml_tpu import telemetry
        from photon_ml_tpu.optimization.glm_lbfgs import (
            minimize_lbfgs_glm_streaming,
        )
        from photon_ml_tpu.optimization.convergence import (
            check_solver_finite,
        )

        if model is None:
            model = self.initialize_model()
        b_mat = jnp.asarray(model.projection_matrix, jnp.float32)
        self._obj.set_residual(residual_scores)
        trackers = []
        for sweep in range(self.mf_config.max_iterations):
            # One trace context per alternating sweep: slow sweeps land
            # on /tracez, and a divergence fault carries the sweep's
            # trace_id into the flight dump (PR-11 watchdog parity).
            ctx = telemetry.mint("mf_sweep")
            ctx.annotate(coordinate=self.name, sweep=sweep,
                         num_factors=self.mf_config.num_factors,
                         reg_weight=self.config.regularization_weight)
            if trace_ctx is not None:
                trace_ctx.event("mf_sweep")
            ctx.event("gamma_pass")
            self._obj.gamma_pass(b_mat, self._l2)
            ctx.event("latent_refit")
            result = minimize_lbfgs_glm_streaming(
                self._obj, jnp.reshape(b_mat, (-1,)), self._ll2,
                max_iter=self.latent_config.max_iterations,
                tol=self.latent_config.tolerance, trace_ctx=ctx)
            b_mat = jnp.reshape(result.x, b_mat.shape)
            # Per-sweep watchdog: the refit's own iterations are already
            # host-checked inside the streamed L-BFGS; re-assert on the
            # sweep boundary so a NaN that rode the FACTOR tables into
            # the refit fails fast under the MF label.
            check_solver_finite(
                "streaming-mf-alternating", sweep,
                np.asarray(result.value)[()],
                np.asarray(result.grad_norm)[()], ctx)
            ctx.finish("ok")
            trackers.append(result)
        self._obj.assert_trace_budget()
        tables = self._obj.factor_tables()
        return model.with_update(list(tables), np.asarray(b_mat)), trackers

    def score(self, model) -> Array:
        """Raw margins γᵀ B x per global row (offsets excluded, like
        every coordinate score) — one streamed pass over the
        observations. Scores the MODEL's factor tables, not the
        objective's internal solve state (a later λ-grid point sharing
        the objective may have overwritten it)."""
        return jnp.asarray(self._obj.score_pass(
            np.asarray(model.projection_matrix, np.float32),
            tables=model.latent.local_coefs))


def _telemetry_span(stage: str):
    from photon_ml_tpu.telemetry import span

    return span(stage)


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass(frozen=True)
class FactoredAlternationResult(OptimizerResult):
    """One alternation of a factored update: the refit's
    ``OptimizerResult`` (``x`` is B, flat), and with it the iterations every
    entity's latent solve took before it, ``i32[E]`` a size class."""

    latent_iterations: Tuple[Array, ...] = ()

    def tree_flatten(self):
        return super().tree_flatten()[0] + (self.latent_iterations,), None


@dataclasses.dataclass
class FactoredRandomEffectCoordinate(Coordinate):
    """Matrix-factorization-flavored random effect
    (ml/algorithm/FactoredRandomEffectCoordinate.scala:39-289).

    Entity e's coefficients are γ_eᵀ B with γ_e ∈ R^k per entity and a
    shared, learned B ∈ R^{k×d}. Each update alternates (reference loop at
    :103-151):

    1. per-entity latent solves — features projected through the current B
       on device (one einsum per bucket), then the same vmap-batched solve
       as RandomEffectCoordinate;
    2. refit of B as a single GLM over all rows whose virtual features are
       x_i ⊗ γ_entity(i) (reference :229-287 materializes the Kronecker
       product per datum and shuffles it; here KroneckerFeatures contracts
       it lazily via einsum — nothing is materialized).

    The dataset must be built with the IDENTITY projector so blocks carry
    global-width features (B itself is the dimension reduction).
    """

    name: str
    dataset: RandomEffectDataset
    task_type: TaskType
    config: GLMOptimizationConfiguration  # per-entity latent solves
    latent_config: GLMOptimizationConfiguration  # projection-matrix refit
    mf_config: "MFOptimizationConfiguration"
    seed: int = 7
    mesh: Optional[object] = None

    factored = True  # no annotation: a class attribute, not a field

    def __post_init__(self):
        if self.dataset.projection is not None:
            raise ValueError(
                "FactoredRandomEffectCoordinate learns its own projection — "
                "build the dataset with projector_type=IDENTITY")
        d = self.dataset.num_global_features
        for b in self.dataset.blocks:
            if b.d_pad < d:
                raise ValueError(
                    "factored random effects need global-width blocks "
                    f"(d_pad {b.d_pad} < num_global_features {d}); build "
                    "the dataset with projector_type=IDENTITY")
        if self.mesh is not None:
            self.dataset = _shard_re_dataset(self.dataset, self.mesh)
        self._objective = GLMObjective(loss_for_task(self.task_type))
        self._l1, self._l2 = _l1_l2(self.config)
        self._ll1, self._ll2 = _l1_l2(self.latent_config)
        self._slot_of_row, self.unslotted_rows = _slot_of_row(
            self.dataset, self.mesh)

    @property
    def _dtype(self):
        return self.dataset.blocks[0].x.dtype

    def routing(self) -> List[dict]:
        """``RandomEffectCoordinate.routing`` for the latent solves: a row
        a size class, whose solve is over the PROJECTED features
        ``[E, r, k]``, so the guard decides by ``r x k`` and admits
        classes it refuses at the blocks' own width."""
        k = self.mf_config.num_factors
        return _class_routing(self.dataset.blocks, [
            _kernel_refusal(
                self._objective, self.config, jax.ShapeDtypeStruct(
                    block.x.shape[:2] + (k,), block.x.dtype))
            for block in self.dataset.blocks])

    def true_rows(self) -> int:
        """``RandomEffectCoordinate.true_rows``."""
        return _true_rows(self.dataset)

    def factored_work(self, trackers):
        """``(alternations, refit iterations)`` the updates of ``trackers``
        ran; ``CoordinateDescent`` sums these into the ``training.mf.*``
        counters."""
        refits = [tr for update in trackers for tr in update]
        return len(refits), sum(int(np.asarray(tr.iterations))
                                for tr in refits)

    @scores_zero_by_construction  # B is Gaussian, every latent factor zero
    def initialize_model(self):
        from photon_ml_tpu.models.factored_random_effect import (
            FactoredRandomEffectModel,
        )
        from photon_ml_tpu.projector.projectors import ProjectionMatrix

        ds = self.dataset
        k = self.mf_config.num_factors
        b0 = ProjectionMatrix.gaussian(
            k, ds.num_global_features, intercept_col=None, seed=self.seed)
        latent = RandomEffectModel(
            random_effect_type=ds.config.random_effect_type,
            feature_shard_id=ds.config.feature_shard_id,
            local_coefs=[jnp.zeros((b.num_entities, k), self._dtype)
                         for b in ds.blocks],
            feat_idx=[jnp.tile(jnp.arange(k), (b.num_entities, 1))
                      for b in ds.blocks],
            entity_codes=list(ds.entity_codes),
            vocabulary=ds.vocabulary,
            num_global_features=ds.num_global_features,
            projection=b0,
        )
        return FactoredRandomEffectModel(latent, self.mf_config)

    def update_model(self, model, residual_scores: Optional[Array], rng_key):
        params, trackers = self.pure_update(
            self.step_data(), self.params_of(model), residual_scores, rng_key)
        return self.model_of(params, model), trackers

    def score(self, model) -> Array:
        return self.pure_score(self.step_data(), self.params_of(model))

    def penalties(self, model):
        return self.pure_penalties(self.params_of(model))

    # -- pure functional face ----------------------------------------------

    def step_data(self):
        return (tuple(self.dataset.blocks),
                tuple(self.dataset.passive_blocks), self._slot_of_row)

    def params_of(self, model):
        dt = self._dtype
        return (tuple(jnp.asarray(g, dt) for g in model.latent.local_coefs),
                jnp.asarray(model.projection_matrix, dt))

    def model_of(self, params, model):
        import numpy as np

        gammas, B = params
        return model.with_update(list(gammas), np.asarray(B))

    def pure_update(self, data, params, residual, rng_key):
        blocks = data[0]
        gammas, B = list(params[0]), params[1]
        d = self.dataset.num_global_features
        if self.mesh is not None:
            residual = _whole_residual(residual, self.mesh)
        residuals = [_gather_residual(residual, b, self.mesh)
                     for b in blocks]
        # Row-major view of x/labels/offsets/weights is iteration-invariant;
        # only the per-row gammas change across alternations.
        x_flat, y_flat, off_flat, w_flat = _flatten_factored_static(
            blocks, residuals, d)
        trackers = []
        for _ in range(self.mf_config.max_iterations):
            latent = [
                _solve_factored_block(
                    self._objective, self.config, block, B, extra, g0, d,
                    mesh=self.mesh)
                for block, extra, g0 in zip(blocks, residuals, gammas)]
            gammas = [r.x for r in latent]
            batch = GLMBatch(
                KroneckerFeatures(x_flat, _flatten_gammas(blocks, gammas)),
                y_flat, off_flat, w_flat)
            result = _solve_latent_matrix(
                self._objective, self.latent_config, batch, B.reshape(-1))
            B = result.x.reshape(B.shape)
            trackers.append(FactoredAlternationResult(
                *result.tree_flatten()[0],
                latent_iterations=tuple(r.iterations for r in latent)))
        return (tuple(gammas), B), trackers

    def pure_score(self, data, params) -> Array:
        blocks, pblocks, slot_of_row = data
        gammas, B = params
        return _fre_score_impl(
            blocks, pblocks, tuple(gammas), B, slot_of_row,
            n_rows=self.dataset.n_rows, d=self.dataset.num_global_features,
            mesh=self.mesh)

    def pure_penalties(self, params, pdata=None):
        gammas, B = params
        out = [(g, self._l1, self._l2) for g in gammas]
        out.append((B, self._ll1, self._ll2))
        return out


@functools.partial(
    jax.jit,
    static_argnames=("objective", "config", "d", "mesh"))
def _solve_factored_block(
    objective: GLMObjective, config: GLMOptimizationConfiguration,
    block: EntityBlock, B, extra_offsets, gamma0, d: int, mesh=None,
):
    """Per-entity latent solves against the current B: one projection einsum
    for the whole bucket, then the batched solve (fused Pallas kernel on
    TPU — the latent bucket has the same shape contract as the
    random-effect one, see _solve_block; with a mesh the kernel runs per
    device over the entity-sharded bucket via shard_map, B replicated)."""
    with jax.named_scope(scopes.MF_PROJECT):
        # a true matrix product: exact float32 products, not the MXU's
        # bfloat16 default (``KroneckerFeatures`` says why)
        lat = jnp.einsum("end,kd->enk", block.x[..., :d], B,
                         precision=KroneckerFeatures.PRECISION)
    offsets = block.offsets
    if extra_offsets is not None:
        with jax.named_scope(scopes.RE_GATHER):
            offsets = offsets + extra_offsets.astype(offsets.dtype)

    use_kernel = _use_pallas_entity_solver(objective, config, lat)

    with jax.named_scope(scopes.MF_LATENT), \
            jax.named_scope(scopes.re_size_class(lat.shape[1])):
        if use_kernel and mesh is not None:
            return _shard_mapped_pallas_solver(
                objective, config, mesh, lat, block.labels, offsets,
                block.weights, gamma0)

        if use_kernel:
            return _dispatch_pallas_solver(objective, config, lat,
                                           block.labels, offsets,
                                           block.weights, gamma0)

        def fit_one(g0, x_lat, y, off, w):
            from photon_ml_tpu.ops.features import DenseFeatures
            batch = GLMBatch(DenseFeatures(x_lat), y, off, w)
            return solve_glm(objective, batch, config, g0)

        return jax.vmap(fit_one)(gamma0, lat, block.labels, offsets,
                                 block.weights)


@jax.named_scope(scopes.MF_FLATTEN)
def _flatten_factored_static(blocks, residuals, d: int):
    """All active rows across buckets in row-major order — the
    iteration-invariant half of the latent-matrix refit batch (replaces the
    reference's partitionBy-uid Kronecker shuffle,
    FactoredRandomEffectCoordinate.scala:269-287)."""
    xs, ys, offs, ws = [], [], [], []
    for block, extra in zip(blocks, residuals):
        xs.append(block.x[..., :d].reshape(-1, d))
        ys.append(block.labels.reshape(-1))
        off = block.offsets if extra is None else \
            block.offsets + extra.astype(block.offsets.dtype)
        offs.append(off.reshape(-1))
        ws.append(block.weights.reshape(-1))
    return (jnp.concatenate(xs), jnp.concatenate(ys),
            jnp.concatenate(offs), jnp.concatenate(ws))


@jax.named_scope(scopes.MF_REFIT)
def _flatten_gammas(blocks, gammas) -> Array:
    """Per-row latent factors aligned with _flatten_factored_static's rows."""
    gs = []
    for block, gamma in zip(blocks, gammas):
        e, n_pad = block.labels.shape
        k = gamma.shape[-1]
        gs.append(jnp.broadcast_to(gamma[:, None, :], (e, n_pad, k))
                  .reshape(-1, k))
    return jnp.concatenate(gs)


@functools.partial(jax.jit, static_argnames=("objective", "config"))
@jax.named_scope(scopes.MF_REFIT)
def _solve_latent_matrix(
    objective: GLMObjective, config: GLMOptimizationConfiguration,
    batch: GLMBatch, coef0,
):
    return solve_glm(objective, batch, config, coef0)


@jax.named_scope(scopes.RE_GATHER)
def _gather_residual(residual_scores: Optional[Array],
                     block: EntityBlock, mesh=None) -> Optional[Array]:
    """Per-row residual for a block: a zero sentinel slot is appended so
    padding rows (row_ids == n_rows) gather 0.

    With a mesh ``residual_scores`` is what ``_whole_residual`` made (every
    device holds all of it, sentinel slot included) and each device reads
    the slots of its own shard of the block's entities: the result is
    entity-sharded like the block, and no ``[E, r]`` array crosses devices."""
    if residual_scores is None:
        return None
    if mesh is not None:
        from jax.sharding import PartitionSpec as P

        slots = P("data", None)
        return jax.shard_map(
            lambda ext, row_ids: ext[row_ids], mesh=mesh,
            in_specs=(P(), slots), out_specs=slots,
        )(residual_scores, block.row_ids)
    ext = jnp.concatenate(
        [residual_scores,
         jnp.zeros((1,), residual_scores.dtype)])
    return ext[block.row_ids]


@jax.named_scope(scopes.RE_GATHER)
def _whole_residual(residual_scores: Optional[Array], mesh
                    ) -> Optional[Array]:
    """What a mesh fit's blocks gather from: the row-sharded residual made
    whole on every device, ONE collective on the n-vector per coordinate
    update whatever the number of size classes, with the rows filled up to
    a multiple of the mesh's ``data`` size and the zero sentinel slot
    behind them (``_end_to_end``). Spelt out, and not a sharding
    constraint, because a constraint leaves the partitioner free to make
    the residual's producer whole instead: it gathered X for it."""
    if residual_scores is None:
        return None
    from jax.sharding import PartitionSpec as P

    k = mesh.shape["data"]
    n_rows = residual_scores.shape[0]
    own_rows = -(-n_rows // k)
    if own_rows * k != n_rows:
        residual_scores = jnp.pad(residual_scores,
                                  (0, own_rows * k - n_rows))

    return jax.shard_map(
        functools.partial(_end_to_end, k=k, spare=1), mesh=mesh,
        in_specs=P("data"), out_specs=P(),
    )(residual_scores)


def _end_to_end(own, k: int, spare: int = 0):
    """Under ``shard_map`` over the ``k`` devices of the ``data`` axis:
    every device's vector ``own`` laid end to end in device order, with
    ``spare`` zeros behind, whole on every device. An all-gather, spelt as
    the v5e's compiler lowers one of these sizes (every device writes its
    own into a zero vector, an all-reduce sums them), because that lowering
    drops the scope's name from the collective: compiled for a described
    v5e:2x2, ``jax.lax.all_gather`` of the flat margins is an ``all-reduce``
    with no ``op_name``, and a trace counts it under no scope."""
    whole = jax.lax.dynamic_update_slice(
        jnp.zeros((own.shape[0] * k + spare,), own.dtype), own,
        (jax.lax.axis_index("data") * own.shape[0],))
    return jax.lax.psum(whole, "data")


@contextlib.contextmanager
def _re_solve_scope(block: EntityBlock):
    """``photon.re.solve/r<rows>``: the bucket's solve (kernel or vmapped,
    normalisation transforms included), one child per size class."""
    with jax.named_scope(scopes.RE_SOLVE), \
            jax.named_scope(scopes.re_size_class(block.x.shape[1])):
        yield


def _dispatch_pallas_solver(objective, config, x, labels, offsets,
                            weights, coef0, norm=None, bounds=None):
    """Shared kernel dispatch for the random-effect and factored-latent
    bucket solves — one place owns the l1/l2 derivation and the kernel
    call so the two paths cannot diverge. l1 > 0 selects the kernel's
    OWL-QN mode (matching solve_glm's routing to minimize_owlqn);
    ``norm``/``bounds`` are the gathered per-entity arrays folded into
    the kernel."""
    from photon_ml_tpu.ops.pallas_entity_solver import pallas_entity_lbfgs

    from photon_ml_tpu.optimization.config import OptimizerType

    rc = config.regularization_context
    l1 = rc.l1_weight(config.regularization_weight) if rc else 0.0
    l2 = rc.l2_weight(config.regularization_weight) if rc else 0.0
    mode = ("tron" if config.optimizer_type == OptimizerType.TRON
            else "owlqn" if l1 > 0 else "lbfgs")
    factors, shifts = (norm[0], norm[1]) if norm is not None else (None,
                                                                   None)
    lower, upper = bounds if bounds is not None else (None, None)
    return pallas_entity_lbfgs(
        objective.loss, x, labels, offsets, weights, coef0, l2, l1,
        factors=factors, shifts=shifts, lower=lower, upper=upper,
        max_iter=config.max_iterations, tol=config.tolerance,
        mode=mode, interpret=_pallas_interpret())


def _shard_mapped_pallas_solver(objective, config, mesh, x, labels,
                                offsets, weights, coef0, norm=None,
                                bounds=None):
    """Entity-sharded kernel dispatch: one fused kernel per device over
    its shard of the entity axis, results reassembled under the same
    sharding. One implementation for the random-effect and
    factored-latent paths (same non-divergence contract as
    _dispatch_pallas_solver). The gathered normalization/bounds arrays
    shard along the entity axis like everything else."""
    from jax.sharding import PartitionSpec as P

    s2, s3 = P("data", None), P("data", None, None)
    out_specs = OptimizerResult(
        x=s2, value=P("data"), grad_norm=P("data"),
        iterations=P("data"), reason=P("data"),
        value_history=None, grad_norm_history=None, coef_history=None)
    norm_specs = None if norm is None else tuple(
        None if a is None else s2 for a in norm)
    bounds_specs = None if bounds is None else (s2, s2)

    def local_solve(x_l, labels_l, off_l, w_l, c0_l, norm_l, bounds_l):
        return _dispatch_pallas_solver(objective, config, x_l, labels_l,
                                       off_l, w_l, c0_l, norm=norm_l,
                                       bounds=bounds_l)

    return jax.shard_map(
        local_solve, mesh=mesh,
        in_specs=(s3, s2, s2, s2, s2, norm_specs, bounds_specs),
        out_specs=out_specs,
        # pallas_call's out_shapes carry no varying-mesh-axes info
        check_vma=False,
    )(x, labels, offsets, weights, coef0, norm, bounds)


def _pallas_interpret() -> bool:
    """PHOTON_ML_TPU_PALLAS_INTERPRET=1 forces the Pallas entity solver
    (interpreter mode) on any backend — an end-to-end drive of the kernel
    code path without TPU hardware. Trace-time, like NO_PALLAS."""
    import os

    return os.environ.get("PHOTON_ML_TPU_PALLAS_INTERPRET") == "1"


_FALLBACK_WARNED: set = set()


def _warn_fallback(reason: str):
    """One warning per distinct reason when a TPU run silently loses the
    fused-kernel path — surfacing what used to be an invisible perf
    cliff."""
    if reason not in _FALLBACK_WARNED:
        _FALLBACK_WARNED.add(reason)
        import logging

        logging.getLogger(__name__).warning(
            "random-effect solve falling back to the vmapped path (%s); "
            "the fused Pallas kernel does not cover this configuration",
            reason)


def _use_pallas_entity_solver(objective, config, x, norm=None,
                              bounds=None) -> bool:
    """Whether this bucket's solve goes to the fused Pallas kernel; warns
    once per distinct reason where a TPU run loses it (see
    ``_kernel_refusal`` for the rules)."""
    refusal = _kernel_refusal(objective, config, x, norm, bounds)
    if refusal is not None and refusal[1]:
        _warn_fallback(refusal[0])
    return refusal is None


def _kernel_refusal(objective, config, x, norm=None,
                    bounds=None) -> Optional[Tuple[str, bool]]:
    """Why this bucket's solve does NOT go to the fused Pallas kernel, as
    ``(reason, loud)``, or None where it does; loud where a TPU run
    silently loses the kernel (the caller warns).

    The fused Pallas kernel covers the random-effect solve
    configurations: TPU backend, L-BFGS (L2, box constraints via
    projected trials) or OWL-QN (L1/elastic-net) or TRON
    (twice-differentiable losses, L2-only, box constraints via
    projected trust-region trials), with or without per-entity
    normalization, dense blocks that fit the kernel's VMEM working
    set. Blocks entity-sharded over a mesh are kernel-eligible by the
    same rules: _solve_block wraps the kernel in shard_map (one kernel
    per device over its entity shard).

    All checks here use only static information (config, shapes,
    backend), so the decision is stable for a given jit cache entry.
    PHOTON_ML_TPU_NO_PALLAS=1 disables the kernel; the flag is read when
    a solve first TRACES, so set it before building coordinates, not
    mid-run (jit-cached entries keep the path they were traced with)."""
    import os

    from photon_ml_tpu.optimization.config import OptimizerType
    from photon_ml_tpu.ops.pallas_entity_solver import (
        VMEM_GUARD_BYTES,
        entity_solver_vmem_bytes,
    )

    def refuse(reason: str, loud: bool = False):
        return reason, loud

    if os.environ.get("PHOTON_ML_TPU_NO_PALLAS") == "1":
        return refuse("PHOTON_ML_TPU_NO_PALLAS=1")
    on_tpu = jax.default_backend() == "tpu" or _pallas_interpret()
    if not on_tpu:  # interpret: kernel on any backend
        return refuse(f"backend {jax.default_backend()}")
    rc = config.regularization_context
    l1 = rc.l1_weight(config.regularization_weight) if rc else 0.0
    if config.optimizer_type not in (OptimizerType.LBFGS,
                                     OptimizerType.TRON):
        return refuse(f"optimizer {config.optimizer_type}", True)
    if config.optimizer_type == OptimizerType.TRON:
        # solve_glm raises for TRON + L1 or a once-differentiable loss;
        # the vmapped fallback preserves those error contracts.
        if l1 > 0 or not objective.loss.twice_differentiable:
            return refuse("TRON with L1 or a once-differentiable loss")
    if bounds is not None and l1 > 0:
        # solve_glm raises for L1 + bounds; preserve the error contract.
        return refuse("L1 with bounds")
    if objective.normalization is not None:
        # Objective-level (global-context) normalization is the fixed
        # effect's path; per-entity normalization reaches the kernel via
        # the gathered ``norm`` arrays instead.
        return refuse("objective-level normalization context", True)
    # VMEM working set per 128-entity grid step, from the same constants
    # the kernel dispatch uses (ops/pallas_entity_solver.py); oversize
    # buckets keep the vmapped path.
    e, r, d = x.shape
    itemsize = np.dtype(x.dtype).itemsize
    vmem = entity_solver_vmem_bytes(
        r, d, itemsize, normalized=norm is not None,
        bounded=bounds is not None)
    if vmem >= VMEM_GUARD_BYTES:
        return refuse(
            f"bucket working set ~{vmem >> 20} MiB exceeds the VMEM "
            f"budget (r={r}, d={d})", True)
    return None


@functools.partial(
    jax.jit, static_argnames=("objective", "config", "mesh"))
def _solve_block(
    objective: GLMObjective, config: GLMOptimizationConfiguration,
    block: EntityBlock, residual_scores, coefs0, mesh=None, norm=None,
    bounds=None,
):
    """One batched solve over the bucket's entity axis, jitted so the whole
    batched solve (trace included) is cached across coordinate-descent
    iterations. ``objective`` hashes by identity and ``config`` by value —
    both stable for a persistent coordinate. The residual gather (the
    reference's addScoresToOffsets join) fuses into the same dispatch.
    ``norm`` = gathered (factors, shifts, intercept_mask), ``bounds`` =
    gathered (lower, upper) — both per-entity local-space arrays; coef0
    and the returned coefficients are in the SOLVE space (normalized
    when ``norm`` is set; the coordinate owns the space transforms).

    On TPU the standard random-effect configurations (L-BFGS/L2 incl.
    box constraints, OWL-QN elastic-net, and TRON — all with optional
    normalization) route to the fused Pallas kernel
    (ops/pallas_entity_solver.py) — the whole per-entity solve as one
    kernel, ~5x over the vmapped op-by-op path. With a mesh, the kernel
    runs per device over the entity-sharded bucket via ``shard_map``
    (each device solves its own 1/n of the entities — entity sharding
    composed with the kernel; sentinel padding entities converge
    instantly). Remaining fallbacks (oversize VMEM, CPU) use the
    portable vmapped solver."""
    offsets = block.offsets
    extra = _gather_residual(residual_scores, block, mesh)
    if extra is not None:
        with jax.named_scope(scopes.RE_GATHER):
            offsets = offsets + extra.astype(offsets.dtype)

    use_kernel = _use_pallas_entity_solver(
        objective, config, block.x, norm=norm, bounds=bounds)

    with _re_solve_scope(block):
        if use_kernel and mesh is not None:
            return _shard_mapped_pallas_solver(
                objective, config, mesh, block.x, block.labels, offsets,
                block.weights, coefs0, norm=norm, bounds=bounds)

        if use_kernel:
            return _dispatch_pallas_solver(objective, config, block.x,
                                           block.labels, offsets,
                                           block.weights, coefs0, norm=norm,
                                           bounds=bounds)

        def fit_one(coef0, x, y, off, w, norm_e, bounds_e):
            from photon_ml_tpu.ops.features import DenseFeatures

            if norm_e is not None:
                fac, shf, _ = norm_e
                # Normalize by rewriting the entity's dense rows inside
                # the jitted solve (a fusion, not a persistent HBM copy)
                # — the solve then runs in the normalized space directly,
                # exactly like the kernel's in-VMEM x' transform.
                if shf is not None:
                    x = x - shf[None, :]
                if fac is not None:
                    x = x * fac[None, :]
            lb, ub = bounds_e if bounds_e is not None else (None, None)
            batch = GLMBatch(DenseFeatures(x), y, off, w)
            return solve_glm(objective, batch, config, coef0, lb, ub)

        return jax.vmap(fit_one)(coefs0, block.x, block.labels, offsets,
                                 block.weights, norm, bounds)


@functools.partial(
    jax.jit, static_argnames=("objective", "config", "is_classification"))
@jax.named_scope(scopes.FE_SOLVE)
def _solve_fixed(
    objective: GLMObjective, config: GLMOptimizationConfiguration,
    is_classification: bool, batch: GLMBatch, residual_scores, rng_key,
    coef0, lower_bounds, upper_bounds, normalization,
):
    """The full fixed-effect update as one dispatch: residual->offsets,
    down-sampling, normalized-space solve, back-transform."""
    if residual_scores is not None:
        # The batch may be row-padded for sharding; pad the residual with
        # zeros to match (padding rows have weight 0, so the value added
        # there is irrelevant).
        pad = batch.num_rows - residual_scores.shape[0]
        if pad:
            residual_scores = jnp.concatenate(
                [residual_scores, jnp.zeros((pad,), residual_scores.dtype)])
        batch = batch.with_offsets(
            batch.offsets + residual_scores.astype(batch.offsets.dtype))
    weights = down_sample_weights(
        rng_key, batch.labels, batch.weights, config.down_sampling_rate,
        is_classification)
    batch = GLMBatch(batch.features, batch.labels, batch.offsets, weights)
    if normalization is not None:
        coef0 = normalization.model_to_normalized_space(coef0)
    result = solve_glm(objective, batch, config, coef0,
                       lower_bounds, upper_bounds)
    coef = result.x
    if normalization is not None:
        coef = normalization.model_to_original_space(coef)
    return result, coef


@functools.partial(jax.jit, static_argnames=("n_rows",))
@jax.named_scope(scopes.FE_SCORE)
def _fe_score_impl(coef, feats, n_rows: int):
    return feats.matvec(coef)[:n_rows]


@jax.named_scope(scopes.RE_SCATTER)
def _scores_by_row(margins, slot_of_row, n_rows: int, dtype, mesh):
    """Scores in row order, ``f[n_rows]``, from every scored block's
    ``[E, r]`` margins (``_scored_blocks``' order): the margins' way back is
    a gather by row. A real row sits in one slot of one block, so the
    scatter of the slots into the rows is a permutation, read here from the
    rows' side: the margins are flattened and laid end to end with one zero
    behind them, and row i reads position ``slot_of_row[i]`` of that
    (``_index_rows`` made it when the coordinate was built; a row in no
    slot reads the zero). n indices and no padding among them, where a
    scatter-add walks every slot.

    With a mesh (static: the coordinate's, whose blocks are entity-sharded
    over it) each device lays the margins of its OWN entities end to end,
    ONE all-gather per scoring makes the flat vector whole on every device
    (``_end_to_end``), and each device gathers its own row range through
    its piece of the row-sharded ``slot_of_row``, whose entries are
    positions in the all-gathered order: the scores come back row-sharded
    like the fixed effect's batch, and no ``[E, r]`` array crosses
    devices."""
    def own_rows(margins_l, slot_l):
        flat = jnp.concatenate(
            [m.reshape(-1) for m in margins_l]
            + [jnp.zeros((1,), dtype)]).astype(dtype)
        if mesh is not None:
            flat = _end_to_end(flat, mesh.shape["data"])
        # in bounds by construction: no clamp, no fill
        return flat.at[slot_l].get(mode="promise_in_bounds")

    if mesh is None:
        return own_rows(margins, slot_of_row)
    from jax.sharding import PartitionSpec as P

    scores = jax.shard_map(
        own_rows, mesh=mesh,
        in_specs=([P("data", None)] * len(margins), P("data")),
        out_specs=P("data"),
    )(margins, slot_of_row)
    return scores if scores.shape[0] == n_rows else scores[:n_rows]


@functools.partial(jax.jit, static_argnames=("n_rows", "mesh"))
def _index_rows(row_ids, n_rows: int, mesh=None):
    """``slot_of_row`` from every scored block's ``row_ids``: for each row
    the position of its slot in the flat margins ``_scores_by_row`` gathers
    from, and the position of the appended zero for a row that sits in no
    slot. Also the most slots any row sits in, a row that does, and the
    number of rows in no slot. One program at set-up, two scatter-adds over
    the slots (the positions, and a count); padding slots (``row_ids ==
    n_rows``) write nothing.

    With a mesh each device writes the positions of the slots of its own
    entity shards, as they will lie in the all-gathered vector (each
    device's margins with a zero behind them: the first device's zero is the
    one a row in no slot reads), into a private vector over the row range
    filled up to a multiple of the mesh's ``data`` size; one all-reduce
    sums the vectors and each device keeps its row range: ``slot_of_row``
    comes back row-sharded, ``i32[own_rows * k]``."""
    k = 1 if mesh is None else mesh.shape["data"]
    own_rows = -(-n_rows // k)

    def own_slots(row_ids_l):
        flat = jnp.concatenate([r.reshape(-1) for r in row_ids_l]
                               + [jnp.zeros((0,), jnp.int32)])
        slots = flat.shape[0]
        chip = 0 if mesh is None else jax.lax.axis_index("data")
        real = flat < n_rows
        # a position less the first zero's: a row nobody writes reads it
        at = jnp.arange(slots, dtype=jnp.int32) + (chip * (slots + 1) - slots)
        rows = jnp.zeros((2, own_rows * k), jnp.int32)
        rows = rows.at[0, flat].add(jnp.where(real, at, 0), mode="drop")
        rows = rows.at[1, flat].add(real.astype(jnp.int32), mode="drop")
        if mesh is not None:
            rows = jax.lax.psum(rows, "data")
        index, count = rows[0] + slots, rows[1]
        found = (jnp.max(count), jnp.argmax(count),
                 jnp.sum(count[:n_rows] == 0))
        if mesh is not None:
            index = jax.lax.dynamic_slice(
                index, (chip * own_rows,), (own_rows,))
        return (index,) + found

    if mesh is None:
        return own_slots(row_ids)
    from jax.sharding import PartitionSpec as P

    return jax.shard_map(
        own_slots, mesh=mesh, in_specs=((P("data", None),) * len(row_ids),),
        out_specs=(P("data"), P(), P(), P()),
    )(row_ids)


def _slot_of_row(dataset: RandomEffectDataset, mesh) -> Tuple[Array, int]:
    """(``slot_of_row``, rows in no slot) for a coordinate over ``dataset``
    (already laid over ``mesh`` where there is one), derived from the
    blocks' own ``row_ids`` on the device. Refuses a dataset in which a row
    sits in two slots: its scores would not be a gather."""
    index, most, row, unslotted = _index_rows(
        tuple(b.row_ids for b, _ in _scored_blocks(
            dataset.blocks, dataset.passive_blocks, dataset.blocks)),
        n_rows=dataset.n_rows, mesh=mesh)
    most, row, unslotted = (
        int(v) for v in jax.device_get((most, row, unslotted)))
    if most > 1:
        raise ValueError(
            f"row {row} sits in {most} slots of the random-effect "
            "dataset's blocks: a row belongs to one entity, among its "
            "active rows or its passive ones, once")
    return index, unslotted


@jax.named_scope(scopes.RE_MARGINS)
def _local_margins(block, coefs):
    return block.local_margins(coefs)


def _scored_blocks(blocks, pblocks, params):
    """(block, its entities' parameters) in scoring order: the active
    blocks, then the passive blocks there are."""
    return list(zip(blocks, params)) + [
        (b, p) for b, p in zip(pblocks, params) if b is not None]


@functools.partial(jax.jit, static_argnames=("n_rows", "mesh"))
def _re_score_impl(blocks, pblocks, coefs, slot_of_row, n_rows: int,
                   mesh=None):
    """A random effect's scores: every block's margins, read back in row
    order (``_scores_by_row`` says what a mesh does to it)."""
    return _scores_by_row(
        [_local_margins(block, c)
         for block, c in _scored_blocks(blocks, pblocks, coefs)],
        slot_of_row, n_rows, coefs[0].dtype if coefs else jnp.float32, mesh)


@functools.partial(jax.jit, static_argnames=("n_rows", "d", "mesh"))
def _fre_score_impl(blocks, pblocks, gammas, B, slot_of_row, n_rows: int,
                    d: int, mesh=None):
    """``_re_score_impl`` where entity e's coefficients are
    ``gamma_e @ B``."""
    @jax.named_scope(scopes.RE_MARGINS)
    def block_margins(block, gamma):
        # [E, d]; at the MXU's bfloat16 default the scores were 5e-4 from
        # what the float32 model scores (PR 37's chip run)
        coefs = jnp.matmul(gamma, B, precision=KroneckerFeatures.PRECISION)
        pad = block.d_pad - d
        if pad:
            coefs = jnp.pad(coefs, ((0, 0), (0, pad)))
        return block.local_margins(coefs)

    return _scores_by_row(
        [block_margins(block, g)
         for block, g in _scored_blocks(blocks, pblocks, gammas)],
        slot_of_row, n_rows, B.dtype, mesh)
