"""Parity: the fused Pallas per-entity solver vs the vmapped jnp path.

Runs the kernel in interpreter mode (no TPU needed) on the same buckets
the random-effect coordinate builds, and checks solutions match the
portable solver to solver tolerance.
"""

import functools

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from tests.conftest import gold
from photon_ml_tpu.ops.glm_objective import GLMBatch, GLMObjective
from photon_ml_tpu.ops.features import DenseFeatures
from photon_ml_tpu.ops.losses import loss_for_task
from photon_ml_tpu.ops.pallas_entity_solver import pallas_entity_lbfgs
from photon_ml_tpu.optimization.config import (
    GLMOptimizationConfiguration,
    OptimizerType,
    RegularizationContext,
    RegularizationType,
)
from photon_ml_tpu.optimization.solver import solve_glm
from photon_ml_tpu.types import TaskType


@pytest.fixture
def rng():
    return np.random.default_rng(11)


def _bucket(rng, e, r, d, dtype):
    x = rng.normal(0, 1, (e, r, d)).astype(dtype)
    x[:, :, 0] = 1.0
    w_true = rng.normal(0, 0.5, (e, d))
    z = np.einsum("erd,ed->er", x, w_true)
    y = (rng.random((e, r)) < 1 / (1 + np.exp(-z))).astype(dtype)
    off = rng.normal(0, 0.1, (e, r)).astype(dtype)
    w = np.ones((e, r), dtype)
    return x, y, off, w


@pytest.mark.parametrize("task", [TaskType.LOGISTIC_REGRESSION,
                                  TaskType.POISSON_REGRESSION])
def test_pallas_solver_matches_vmapped(rng, task):
    dtype = np.float64 if jax.config.jax_enable_x64 else np.float32
    e, r, d = 37, 6, 5  # e deliberately not a multiple of 128 (pad lanes)
    x, y, off, w = _bucket(rng, e, r, d, dtype)
    if task == TaskType.POISSON_REGRESSION:
        y = rng.poisson(2.0, (e, r)).astype(dtype)
    loss = loss_for_task(task)
    obj = GLMObjective(loss)
    cfg = GLMOptimizationConfiguration(
        max_iterations=40, tolerance=1e-8, regularization_weight=0.7,
        regularization_context=RegularizationContext(RegularizationType.L2))
    coef0 = np.zeros((e, d), dtype)

    res_k = pallas_entity_lbfgs(
        loss, jnp.asarray(x), jnp.asarray(y), jnp.asarray(off),
        jnp.asarray(w), jnp.asarray(coef0), 0.7,
        max_iter=40, tol=1e-8, interpret=True)

    def fit_one(c0, xe, ye, oe, we):
        return solve_glm(obj, GLMBatch(DenseFeatures(xe), ye, oe, we),
                         cfg, c0)

    res_v = jax.vmap(fit_one)(jnp.asarray(coef0), jnp.asarray(x),
                              jnp.asarray(y), jnp.asarray(off),
                              jnp.asarray(w))

    np.testing.assert_allclose(np.asarray(res_k.value),
                               np.asarray(res_v.value),
                               rtol=gold(1e-8, f32_floor=1e-4))
    np.testing.assert_allclose(np.asarray(res_k.x), np.asarray(res_v.x),
                               atol=gold(1e-5, f32_floor=5e-3))
    assert res_k.x.shape == (e, d)
    # Both paths agree on which entities converged.
    assert np.array_equal(np.asarray(res_k.converged),
                          np.asarray(res_v.converged))


def test_pallas_solver_zero_weight_entities(rng):
    """All-zero-weight (padding-style) entities converge immediately at
    coef0 and report GRADIENT_CONVERGED with 0 iterations."""
    dtype = np.float64 if jax.config.jax_enable_x64 else np.float32
    e, r, d = 5, 4, 3
    x, y, off, w = _bucket(rng, e, r, d, dtype)
    w[2] = 0.0
    loss = loss_for_task(TaskType.LOGISTIC_REGRESSION)
    res = pallas_entity_lbfgs(
        loss, jnp.asarray(x), jnp.asarray(y), jnp.asarray(off),
        jnp.asarray(w), jnp.zeros((e, d), dtype), 0.0,
        max_iter=20, tol=1e-7, interpret=True)
    assert int(res.iterations[2]) == 0
    np.testing.assert_array_equal(np.asarray(res.x[2]), 0.0)


def test_solve_block_routes_through_kernel(monkeypatch, rng):
    """PHOTON_ML_TPU_PALLAS_INTERPRET=1 routes _solve_block through the
    fused kernel on any backend (interpreter mode) — the end-to-end drive
    of the routing layer without TPU hardware. The kernel path is
    distinguishable by its untracked histories (value_history is None)."""
    from photon_ml_tpu.algorithm.coordinates import _solve_block
    from photon_ml_tpu.data.random_effect import EntityBlock
    from photon_ml_tpu.ops.glm_objective import GLMObjective as Obj

    dtype = np.float64 if jax.config.jax_enable_x64 else np.float32
    e, r, d = 23, 5, 4
    x, y, off, w = _bucket(rng, e, r, d, dtype)
    block = EntityBlock(
        x=jnp.asarray(x), labels=jnp.asarray(y), offsets=jnp.asarray(off),
        weights=jnp.asarray(w),
        row_ids=np.zeros((e, r), np.int32),
        feat_idx=np.broadcast_to(np.arange(d, dtype=np.int32), (e, d)))
    obj = Obj(loss_for_task(TaskType.LOGISTIC_REGRESSION))
    c0 = jnp.zeros((e, d), dtype)

    def cfg(tol):
        # distinct tolerances force distinct jit cache entries — the
        # routing env vars are read at trace time
        return GLMOptimizationConfiguration(
            max_iterations=25, tolerance=tol, regularization_weight=0.4,
            regularization_context=RegularizationContext(
                RegularizationType.L2))

    monkeypatch.setenv("PHOTON_ML_TPU_PALLAS_INTERPRET", "1")
    res_k = _solve_block(obj, cfg(1e-7), block, None, c0)
    assert res_k.value_history is None  # kernel path ran
    monkeypatch.delenv("PHOTON_ML_TPU_PALLAS_INTERPRET")
    monkeypatch.setenv("PHOTON_ML_TPU_NO_PALLAS", "1")  # backend-independent
    res_v = _solve_block(obj, cfg(1.001e-7), block, None, c0)
    assert res_v.value_history is not None  # vmapped path ran
    np.testing.assert_allclose(np.asarray(res_k.value),
                               np.asarray(res_v.value),
                               rtol=gold(1e-6, f32_floor=1e-4))
    np.testing.assert_allclose(np.asarray(res_k.x), np.asarray(res_v.x),
                               atol=gold(1e-5, f32_floor=5e-3))


def test_pallas_solver_deep_backtracking_tail(rng):
    """Force the tiered line search past tier 1 (8 candidates): Poisson
    with large-scale features makes early trial margins overflow exp, so
    the first finite+Armijo step sits deep in the backtracking schedule.
    The kernel must agree with the vmapped solver (same candidate set)."""
    dtype = np.float64 if jax.config.jax_enable_x64 else np.float32
    e, r, d = 9, 8, 3
    x = (rng.normal(0, 1, (e, r, d)) * 30.0).astype(dtype)
    y = rng.poisson(3.0, (e, r)).astype(dtype)
    off = np.zeros((e, r), dtype)
    w = np.ones((e, r), dtype)
    loss = loss_for_task(TaskType.POISSON_REGRESSION)
    obj = GLMObjective(loss)
    cfg = GLMOptimizationConfiguration(
        max_iterations=30, tolerance=1e-8, regularization_weight=0.1,
        regularization_context=RegularizationContext(RegularizationType.L2))

    res_k = pallas_entity_lbfgs(
        loss, jnp.asarray(x), jnp.asarray(y), jnp.asarray(off),
        jnp.asarray(w), jnp.zeros((e, d), dtype), 0.1,
        max_iter=30, tol=1e-8, interpret=True)

    def fit_one(c0, xe, ye, oe, we):
        return solve_glm(obj, GLMBatch(DenseFeatures(xe), ye, oe, we),
                         cfg, c0)

    res_v = jax.vmap(fit_one)(jnp.zeros((e, d), dtype), jnp.asarray(x),
                              jnp.asarray(y), jnp.asarray(off),
                              jnp.asarray(w))
    np.testing.assert_allclose(np.asarray(res_k.value),
                               np.asarray(res_v.value),
                               rtol=gold(1e-7, f32_floor=2e-4))
    np.testing.assert_allclose(np.asarray(res_k.x), np.asarray(res_v.x),
                               atol=gold(1e-4, f32_floor=1e-2))


def test_factored_latent_solve_routes_through_kernel(monkeypatch, rng):
    """The factored coordinate's latent (gamma) bucket solve routes
    through the kernel too — drive _solve_factored_block both ways and
    check solution parity (the projection einsum feeds the kernel a
    [E, r, k] latent design)."""
    from photon_ml_tpu.algorithm.coordinates import _solve_factored_block
    from photon_ml_tpu.data.random_effect import EntityBlock
    from photon_ml_tpu.ops.glm_objective import GLMObjective as Obj

    dtype = np.float64 if jax.config.jax_enable_x64 else np.float32
    e, r, d, k = 17, 6, 5, 2
    x, y, off, w = _bucket(rng, e, r, d, dtype)
    block = EntityBlock(
        x=jnp.asarray(x), labels=jnp.asarray(y), offsets=jnp.asarray(off),
        weights=jnp.asarray(w),
        row_ids=np.zeros((e, r), np.int32),
        feat_idx=np.broadcast_to(np.arange(d, dtype=np.int32), (e, d)))
    B = jnp.asarray(rng.normal(0, 0.5, (k, d)).astype(dtype))
    g0 = jnp.zeros((e, k), dtype)
    obj = Obj(loss_for_task(TaskType.LOGISTIC_REGRESSION))

    def cfg(tol):
        return GLMOptimizationConfiguration(
            max_iterations=20, tolerance=tol, regularization_weight=0.3,
            regularization_context=RegularizationContext(
                RegularizationType.L2))

    monkeypatch.setenv("PHOTON_ML_TPU_PALLAS_INTERPRET", "1")
    res_k = _solve_factored_block(obj, cfg(1e-7), block, B, None, g0, d)
    assert res_k.value_history is None  # kernel path ran
    monkeypatch.delenv("PHOTON_ML_TPU_PALLAS_INTERPRET")
    monkeypatch.setenv("PHOTON_ML_TPU_NO_PALLAS", "1")
    res_v = _solve_factored_block(obj, cfg(1.001e-7), block, B, None, g0, d)
    assert res_v.value_history is not None
    np.testing.assert_allclose(np.asarray(res_k.value),
                               np.asarray(res_v.value),
                               rtol=gold(1e-6, f32_floor=1e-4))
    np.testing.assert_allclose(np.asarray(res_k.x), np.asarray(res_v.x),
                               atol=gold(1e-5, f32_floor=5e-3))


@pytest.mark.parametrize("e,r,d", [(1, 1, 1), (1, 3, 2), (129, 2, 1),
                                   (128, 4, 7), (40, 1, 5)])
def test_pallas_solver_edge_shapes(rng, e, r, d):
    """Degenerate shapes: single entity, single row, single feature, and
    entity counts straddling the 128-lane boundary."""
    dtype = np.float64 if jax.config.jax_enable_x64 else np.float32
    x, y, off, w = _bucket(rng, e, r, d, dtype)
    loss = loss_for_task(TaskType.LOGISTIC_REGRESSION)
    obj = GLMObjective(loss)
    cfg = GLMOptimizationConfiguration(
        max_iterations=15, tolerance=1e-7, regularization_weight=0.6,
        regularization_context=RegularizationContext(RegularizationType.L2))
    res_k = pallas_entity_lbfgs(
        loss, jnp.asarray(x), jnp.asarray(y), jnp.asarray(off),
        jnp.asarray(w), jnp.zeros((e, d), dtype), 0.6,
        max_iter=15, tol=1e-7, interpret=True)

    def fit_one(c0, xe, ye, oe, we):
        return solve_glm(obj, GLMBatch(DenseFeatures(xe), ye, oe, we),
                         cfg, c0)

    res_v = jax.vmap(fit_one)(jnp.zeros((e, d), dtype), jnp.asarray(x),
                              jnp.asarray(y), jnp.asarray(off),
                              jnp.asarray(w))
    assert res_k.x.shape == (e, d)
    np.testing.assert_allclose(np.asarray(res_k.value),
                               np.asarray(res_v.value),
                               rtol=gold(1e-7, f32_floor=1e-4))


@pytest.mark.slow
def test_pallas_owlqn_matches_vmapped(rng):
    """Elastic-net (OWL-QN) kernel mode vs the vmapped minimize_owlqn
    path through solve_glm — values, coefficients, and the SPARSITY
    pattern (which coordinates are exactly zero) must agree."""
    dtype = np.float64 if jax.config.jax_enable_x64 else np.float32
    e, r, d = 31, 8, 6
    x, y, off, w = _bucket(rng, e, r, d, dtype)
    loss = loss_for_task(TaskType.LOGISTIC_REGRESSION)
    obj = GLMObjective(loss)
    lam, alpha = 1.5, 0.5  # strong l1 so real zeros appear
    cfg = GLMOptimizationConfiguration(
        max_iterations=60, tolerance=1e-9, regularization_weight=lam,
        regularization_context=RegularizationContext(
            RegularizationType.ELASTIC_NET, alpha))

    res_k = pallas_entity_lbfgs(
        loss, jnp.asarray(x), jnp.asarray(y), jnp.asarray(off),
        jnp.asarray(w), jnp.zeros((e, d), dtype),
        (1 - alpha) * lam, alpha * lam,
        max_iter=60, tol=1e-9, mode="owlqn", interpret=True)

    def fit_one(c0, xe, ye, oe, we):
        return solve_glm(obj, GLMBatch(DenseFeatures(xe), ye, oe, we),
                         cfg, c0)

    res_v = jax.vmap(fit_one)(jnp.zeros((e, d), dtype), jnp.asarray(x),
                              jnp.asarray(y), jnp.asarray(off),
                              jnp.asarray(w))
    np.testing.assert_allclose(np.asarray(res_k.value),
                               np.asarray(res_v.value),
                               rtol=gold(1e-7, f32_floor=1e-4))
    np.testing.assert_allclose(np.asarray(res_k.x), np.asarray(res_v.x),
                               atol=gold(1e-6, f32_floor=5e-3))
    # exact-zero sets agree (the orthant method's signature behavior)
    zk = np.asarray(res_k.x) == 0.0
    zv = np.asarray(res_v.x) == 0.0
    assert zk.any()  # the l1 weight is strong enough to produce zeros
    assert np.array_equal(zk, zv)


@pytest.mark.slow
def test_solve_block_routes_elastic_net_through_kernel(monkeypatch, rng):
    """_solve_block routes ELASTIC_NET configs to the kernel's OWL-QN
    mode (previously an automatic fallback to the vmapped path)."""
    from photon_ml_tpu.algorithm.coordinates import _solve_block
    from photon_ml_tpu.data.random_effect import EntityBlock

    dtype = np.float64 if jax.config.jax_enable_x64 else np.float32
    e, r, d = 19, 5, 4
    x, y, off, w = _bucket(rng, e, r, d, dtype)
    block = EntityBlock(
        x=jnp.asarray(x), labels=jnp.asarray(y), offsets=jnp.asarray(off),
        weights=jnp.asarray(w),
        row_ids=np.zeros((e, r), np.int32),
        feat_idx=np.broadcast_to(np.arange(d, dtype=np.int32), (e, d)))
    obj = GLMObjective(loss_for_task(TaskType.LOGISTIC_REGRESSION))
    c0 = jnp.zeros((e, d), dtype)

    def cfg(tol):
        return GLMOptimizationConfiguration(
            max_iterations=40, tolerance=tol, regularization_weight=0.8,
            regularization_context=RegularizationContext(
                RegularizationType.ELASTIC_NET, 0.5))

    monkeypatch.setenv("PHOTON_ML_TPU_PALLAS_INTERPRET", "1")
    res_k = _solve_block(obj, cfg(1e-8), block, None, c0)
    assert res_k.value_history is None  # kernel path ran
    monkeypatch.delenv("PHOTON_ML_TPU_PALLAS_INTERPRET")
    monkeypatch.setenv("PHOTON_ML_TPU_NO_PALLAS", "1")
    res_v = _solve_block(obj, cfg(1.001e-8), block, None, c0)
    np.testing.assert_allclose(np.asarray(res_k.value),
                               np.asarray(res_v.value),
                               rtol=gold(1e-6, f32_floor=1e-4))
    np.testing.assert_allclose(np.asarray(res_k.x), np.asarray(res_v.x),
                               atol=gold(1e-5, f32_floor=5e-3))


@pytest.mark.parametrize("task", [TaskType.LOGISTIC_REGRESSION,
                                  TaskType.POISSON_REGRESSION,
                                  TaskType.LINEAR_REGRESSION])
def test_pallas_tron_matches_vmapped(rng, task):
    """TRON kernel mode vs the vmapped minimize_tron path through
    solve_glm (LIBLINEAR trust-region rules, truncated CG)."""
    dtype = np.float64 if jax.config.jax_enable_x64 else np.float32
    e, r, d = 29, 7, 5
    x, y, off, w = _bucket(rng, e, r, d, dtype)
    if task == TaskType.POISSON_REGRESSION:
        y = rng.poisson(2.0, (e, r)).astype(dtype)
    elif task == TaskType.LINEAR_REGRESSION:
        y = rng.normal(0, 1, (e, r)).astype(dtype)
    loss = loss_for_task(task)
    obj = GLMObjective(loss)
    cfg = GLMOptimizationConfiguration(
        max_iterations=15, tolerance=1e-7, regularization_weight=0.5,
        regularization_context=RegularizationContext(RegularizationType.L2),
        optimizer_type=OptimizerType.TRON)

    res_k = pallas_entity_lbfgs(
        loss, jnp.asarray(x), jnp.asarray(y), jnp.asarray(off),
        jnp.asarray(w), jnp.zeros((e, d), dtype), 0.5,
        max_iter=15, tol=1e-7, mode="tron", interpret=True)

    def fit_one(c0, xe, ye, oe, we):
        return solve_glm(obj, GLMBatch(DenseFeatures(xe), ye, oe, we),
                         cfg, c0)

    res_v = jax.vmap(fit_one)(jnp.zeros((e, d), dtype), jnp.asarray(x),
                              jnp.asarray(y), jnp.asarray(off),
                              jnp.asarray(w))
    np.testing.assert_allclose(np.asarray(res_k.value),
                               np.asarray(res_v.value),
                               rtol=gold(1e-7, f32_floor=2e-4))
    np.testing.assert_allclose(np.asarray(res_k.x), np.asarray(res_v.x),
                               atol=gold(1e-4, f32_floor=1e-2))


@pytest.mark.slow
def test_solve_block_routes_tron_through_kernel(monkeypatch, rng):
    """TRON random-effect configs reach the kernel; once-differentiable
    losses keep the vmapped fallback (which raises solve_glm's error)."""
    from photon_ml_tpu.algorithm.coordinates import (
        _solve_block,
        _use_pallas_entity_solver,
    )
    from photon_ml_tpu.data.random_effect import EntityBlock

    dtype = np.float64 if jax.config.jax_enable_x64 else np.float32
    e, r, d = 13, 4, 3
    x, y, off, w = _bucket(rng, e, r, d, dtype)
    block = EntityBlock(
        x=jnp.asarray(x), labels=jnp.asarray(y), offsets=jnp.asarray(off),
        weights=jnp.asarray(w),
        row_ids=np.zeros((e, r), np.int32),
        feat_idx=np.broadcast_to(np.arange(d, dtype=np.int32), (e, d)))
    obj = GLMObjective(loss_for_task(TaskType.LOGISTIC_REGRESSION))
    c0 = jnp.zeros((e, d), dtype)

    def cfg(tol):
        return GLMOptimizationConfiguration(
            max_iterations=12, tolerance=tol, regularization_weight=0.4,
            regularization_context=RegularizationContext(
                RegularizationType.L2),
            optimizer_type=OptimizerType.TRON)

    monkeypatch.setenv("PHOTON_ML_TPU_PALLAS_INTERPRET", "1")
    res_k = _solve_block(obj, cfg(1e-7), block, None, c0)
    assert res_k.value_history is None  # kernel path ran
    monkeypatch.delenv("PHOTON_ML_TPU_PALLAS_INTERPRET")
    monkeypatch.setenv("PHOTON_ML_TPU_NO_PALLAS", "1")
    res_v = _solve_block(obj, cfg(1.001e-7), block, None, c0)
    np.testing.assert_allclose(np.asarray(res_k.value),
                               np.asarray(res_v.value),
                               rtol=gold(1e-6, f32_floor=1e-4))

    # Guard: TRON + once-differentiable loss never routes to the kernel.
    hinge_obj = GLMObjective(
        loss_for_task(TaskType.SMOOTHED_HINGE_LOSS_LINEAR_SVM))
    assert not _use_pallas_entity_solver(hinge_obj, cfg(1e-7), block.x)


@pytest.mark.parametrize("mode", ["tron", "owlqn"])
@pytest.mark.slow
def test_pallas_solver_overflow_trials_stay_finite(rng, mode):
    """Rejected trial steps whose margins overflow exp must not poison
    the retained iterate (the arithmetic keep-old select computes
    b + m*(a-b), and 0*inf is NaN): Poisson with huge feature scale
    forces non-finite trial values; results must stay finite and match
    the vmapped solver."""
    dtype = np.float64 if jax.config.jax_enable_x64 else np.float32
    e, r, d = 7, 6, 3
    x = (rng.normal(0, 1, (e, r, d)) * 300.0).astype(dtype)
    y = rng.poisson(3.0, (e, r)).astype(dtype)
    off = np.zeros((e, r), dtype)
    w = np.ones((e, r), dtype)
    loss = loss_for_task(TaskType.POISSON_REGRESSION)
    obj = GLMObjective(loss)
    reg = (RegularizationContext(RegularizationType.L2) if mode == "tron"
           else RegularizationContext(RegularizationType.ELASTIC_NET, 0.5))
    cfg = GLMOptimizationConfiguration(
        max_iterations=12, tolerance=1e-7, regularization_weight=0.5,
        regularization_context=reg,
        optimizer_type=(OptimizerType.TRON if mode == "tron"
                        else OptimizerType.LBFGS))
    l1 = 0.25 if mode == "owlqn" else 0.0
    l2 = 0.5 if mode == "tron" else 0.25

    res_k = pallas_entity_lbfgs(
        loss, jnp.asarray(x), jnp.asarray(y), jnp.asarray(off),
        jnp.asarray(w), jnp.zeros((e, d), dtype), l2, l1,
        max_iter=12, tol=1e-7, mode=mode, interpret=True)
    assert np.isfinite(np.asarray(res_k.x)).all()
    assert np.isfinite(np.asarray(res_k.value)).all()

    def fit_one(c0, xe, ye, oe, we):
        return solve_glm(obj, GLMBatch(DenseFeatures(xe), ye, oe, we),
                         cfg, c0)

    res_v = jax.vmap(fit_one)(jnp.zeros((e, d), dtype), jnp.asarray(x),
                              jnp.asarray(y), jnp.asarray(off),
                              jnp.asarray(w))
    np.testing.assert_allclose(np.asarray(res_k.value),
                               np.asarray(res_v.value),
                               rtol=gold(1e-6, f32_floor=2e-4))


def test_kernel_composes_with_entity_sharding(monkeypatch, rng):
    """Mesh-sharded buckets run the kernel PER DEVICE via shard_map (each
    device solves its own entity shard); results match the unsharded
    kernel for real entities and padding entities stay zero."""
    from photon_ml_tpu.algorithm.coordinates import _solve_block
    from photon_ml_tpu.data.random_effect import EntityBlock
    from photon_ml_tpu.parallel import make_mesh, shard_block

    dtype = np.float64 if jax.config.jax_enable_x64 else np.float32
    e, r, d = 21, 5, 4  # pads to 24 entities over 8 devices
    x, y, off, w = _bucket(rng, e, r, d, dtype)
    block = EntityBlock(
        x=jnp.asarray(x), labels=jnp.asarray(y), offsets=jnp.asarray(off),
        weights=jnp.asarray(w),
        row_ids=np.zeros((e, r), np.int32),
        feat_idx=np.broadcast_to(np.arange(d, dtype=np.int32), (e, d)))
    obj = GLMObjective(loss_for_task(TaskType.LOGISTIC_REGRESSION))

    def cfg(tol):
        return GLMOptimizationConfiguration(
            max_iterations=25, tolerance=tol, regularization_weight=0.4,
            regularization_context=RegularizationContext(
                RegularizationType.L2))

    monkeypatch.setenv("PHOTON_ML_TPU_PALLAS_INTERPRET", "1")
    plain = _solve_block(obj, cfg(1e-8), block, None,
                         jnp.zeros((e, d), dtype))
    assert plain.value_history is None  # kernel path

    mesh = make_mesh()
    sblock = shard_block(block, mesh, sentinel_row=1000)
    ep = sblock.num_entities
    assert ep == 24
    sharded = _solve_block(obj, cfg(1.001e-8), sblock, None,
                           jnp.zeros((ep, d), dtype),
                           mesh=mesh)
    assert sharded.value_history is None  # kernel ran under shard_map
    np.testing.assert_allclose(np.asarray(sharded.x[:e]),
                               np.asarray(plain.x),
                               atol=gold(1e-6, f32_floor=5e-3))
    np.testing.assert_allclose(np.asarray(sharded.value[:e]),
                               np.asarray(plain.value),
                               rtol=gold(1e-7, f32_floor=1e-4))
    # padding entities (weight 0) converge instantly at zero
    np.testing.assert_array_equal(np.asarray(sharded.x[e:]), 0.0)
    np.testing.assert_array_equal(np.asarray(sharded.iterations[e:]), 0)


def test_factored_kernel_composes_with_entity_sharding(monkeypatch, rng):
    """The factored-latent kernel also composes with entity sharding via
    shard_map (B replicated, latent designs sharded)."""
    from photon_ml_tpu.algorithm.coordinates import _solve_factored_block
    from photon_ml_tpu.data.random_effect import EntityBlock
    from photon_ml_tpu.parallel import make_mesh, shard_block

    dtype = np.float64 if jax.config.jax_enable_x64 else np.float32
    e, r, d, k = 13, 4, 5, 2  # pads to 16 entities over 8 devices
    x, y, off, w = _bucket(rng, e, r, d, dtype)
    block = EntityBlock(
        x=jnp.asarray(x), labels=jnp.asarray(y), offsets=jnp.asarray(off),
        weights=jnp.asarray(w),
        row_ids=np.zeros((e, r), np.int32),
        feat_idx=np.broadcast_to(np.arange(d, dtype=np.int32), (e, d)))
    B = jnp.asarray(rng.normal(0, 0.5, (k, d)).astype(dtype))
    obj = GLMObjective(loss_for_task(TaskType.LOGISTIC_REGRESSION))

    def cfg(tol):
        return GLMOptimizationConfiguration(
            max_iterations=20, tolerance=tol, regularization_weight=0.3,
            regularization_context=RegularizationContext(
                RegularizationType.L2))

    monkeypatch.setenv("PHOTON_ML_TPU_PALLAS_INTERPRET", "1")
    plain = _solve_factored_block(obj, cfg(1e-8), block, B, None,
                                  jnp.zeros((e, k), dtype), d)
    assert plain.value_history is None

    mesh = make_mesh()
    sblock = shard_block(block, mesh, sentinel_row=1000)
    ep = sblock.num_entities
    sharded = _solve_factored_block(obj, cfg(1.001e-8), sblock, B, None,
                                    jnp.zeros((ep, k), dtype), d,
                                    mesh=mesh)
    assert sharded.value_history is None
    np.testing.assert_allclose(np.asarray(sharded.x[:e]),
                               np.asarray(plain.x),
                               atol=gold(1e-6, f32_floor=5e-3))
    np.testing.assert_array_equal(np.asarray(sharded.x[e:]), 0.0)


def test_vmem_oversize_bucket_keeps_vmapped_path(monkeypatch, rng):
    """Buckets whose kernel working set would exceed the VMEM budget
    route to the vmapped solver even when the kernel is forced on."""
    from photon_ml_tpu.algorithm.coordinates import _use_pallas_entity_solver
    from photon_ml_tpu.ops.glm_objective import GLMObjective as Obj

    obj = Obj(loss_for_task(TaskType.LOGISTIC_REGRESSION))
    cfg = GLMOptimizationConfiguration(
        max_iterations=10, tolerance=1e-6, regularization_weight=0.5,
        regularization_context=RegularizationContext(RegularizationType.L2))
    monkeypatch.setenv("PHOTON_ML_TPU_PALLAS_INTERPRET", "1")
    small = jax.ShapeDtypeStruct((100, 8, 16), jnp.float32)
    big = jax.ShapeDtypeStruct((100, 400, 128), jnp.float32)  # ~26 MB tile
    assert _use_pallas_entity_solver(obj, cfg, small)
    assert not _use_pallas_entity_solver(obj, cfg, big)


@pytest.fixture
def rows_per_step(monkeypatch):
    """Set the kernel's rows per loop step for one test: read when the
    kernel is traced, so the jitted entry point's traces are dropped on
    the way in and out."""
    from photon_ml_tpu.ops import pallas_entity_solver as K

    def set_step(rows):
        if rows is not None:
            monkeypatch.setattr(K, "ROWS_PER_STEP", rows)
        K.pallas_entity_lbfgs.clear_cache()

    yield set_step
    monkeypatch.undo()
    K.pallas_entity_lbfgs.clear_cache()


# Rows against the kernel's row loops (chunks of ROW_CHUNK = 8 rows, steps
# of ROWS_PER_STEP): fewer than a chunk (4), a chunk and a tail (12), a
# rolled loop of 8-row steps and a tail (20), of 16-row steps and a tail
# (36), and at the kernel's own step (None: 136 rows, two steps and a
# chunk).
@pytest.mark.parametrize("mode,r,step", [
    ("lbfgs", 4, 8), ("lbfgs", 12, 8), ("lbfgs", 20, 8), ("lbfgs", 36, 16),
    ("lbfgs", 136, None), ("owlqn", 20, 8), ("tron", 20, 8)])
def test_row_chunks_match_vmapped(rng, rows_per_step, mode, r, step):
    rows_per_step(step)
    dtype = np.float64 if jax.config.jax_enable_x64 else np.float32
    e, d = 11, 5
    x, y, off, w = _bucket(rng, e, r, d, dtype)
    loss = loss_for_task(TaskType.LOGISTIC_REGRESSION)
    obj = GLMObjective(loss)
    reg, lam = RegularizationContext(RegularizationType.L2), 0.7
    if mode == "owlqn":
        reg, lam = RegularizationContext(
            RegularizationType.ELASTIC_NET, 0.5), 1.5
    cfg = GLMOptimizationConfiguration(
        max_iterations=40, tolerance=1e-8, regularization_weight=lam,
        regularization_context=reg,
        optimizer_type=(OptimizerType.TRON if mode == "tron"
                        else OptimizerType.LBFGS))
    coef0 = np.zeros((e, d), dtype)

    res_k = pallas_entity_lbfgs(
        loss, jnp.asarray(x), jnp.asarray(y), jnp.asarray(off),
        jnp.asarray(w), jnp.asarray(coef0), reg.l2_weight(lam),
        reg.l1_weight(lam), max_iter=40, tol=1e-8, mode=mode,
        interpret=True)

    def fit_one(c0, xe, ye, oe, we):
        return solve_glm(obj, GLMBatch(DenseFeatures(xe), ye, oe, we),
                         cfg, c0)

    res_v = jax.vmap(fit_one)(jnp.asarray(coef0), jnp.asarray(x),
                              jnp.asarray(y), jnp.asarray(off),
                              jnp.asarray(w))
    # each mode's tolerances as its own parity test above has them
    value_rtol, x_atol = {
        "lbfgs": (gold(1e-8, f32_floor=1e-4), gold(1e-5, f32_floor=5e-3)),
        "owlqn": (gold(1e-7, f32_floor=1e-4), gold(1e-6, f32_floor=5e-3)),
        "tron": (gold(1e-7, f32_floor=2e-4), gold(1e-4, f32_floor=1e-2)),
    }[mode]
    np.testing.assert_allclose(np.asarray(res_k.value),
                               np.asarray(res_v.value), rtol=value_rtol)
    np.testing.assert_allclose(np.asarray(res_k.x), np.asarray(res_v.x),
                               atol=x_atol)


@pytest.mark.parametrize("variant", ["lbfgs", "owlqn", "tron",
                                     "lbfgs+norm", "lbfgs+bounds"])
def test_kernel_body_does_not_grow_with_rows(variant):
    """The compile ledger's record of each kernel body traced: rows,
    width and the equations of its jaxpr. From two loop steps on
    (ROWS_PER_STEP rows each) the body holds one step's rows whatever r
    is, so 256, 512 and 1,024 rows trace the same body, and 32 rows (a
    static unroll) hold less than half as many equations as it does
    rather than an eighth (unrolled, the body grew about eightfold from
    32 rows to 256)."""
    from photon_ml_tpu.ops.pallas_entity_solver import ROWS_PER_STEP
    from photon_ml_tpu.utils import compile_cache

    mode, _, extra = variant.partition("+")
    e, d = 128, 32
    arg = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.float32)
    kw = {"norm": {"factors": arg(e, d), "shifts": arg(e, d)},
          "bounds": {"lower": arg(e, d), "upper": arg(e, d)}}.get(extra, {})
    rows = (32, 256, 512, 1024)
    assert 2 * ROWS_PER_STEP <= 256
    bodies = []
    for r in rows:
        jax.make_jaxpr(functools.partial(
            pallas_entity_lbfgs, loss_for_task(TaskType.LOGISTIC_REGRESSION),
            max_iter=20, tol=1e-6, mode=mode, interpret=True, **kw))(
            arg(e, r, d), arg(e, r), arg(e, r), arg(e, r), arg(e, d),
            arg(), arg())
        bodies.append(compile_cache.compile_ledger()["kernel_bodies"][-1])
    assert [(b["rows"], b["width"], b["variant"]) for b in bodies] == [
        (r, d, variant) for r in rows]
    assert all(b["kernel"].startswith("pallas_entity_lbfgs")
               for b in bodies)
    eqns = [b["eqns"] for b in bodies]
    assert eqns[1] == eqns[2] == eqns[3]
    assert 0 < eqns[0] < eqns[1] < 2.5 * eqns[0]
