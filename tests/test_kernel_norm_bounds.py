"""Parity: normalization and box constraints folded into the fused
Pallas entity kernel vs the vmapped host path.

STANDARDIZATION (NormalizationContext.scala:38-83) and box constraints
(OptimizationUtils.scala:53) are first-class reference features on
random-effect problems (RandomEffectOptimizationProblem.scala:105-125);
they must keep the kernel path, not silently shed it. All kernel runs
here use interpreter mode (no TPU needed).
"""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from tests.conftest import gold
from photon_ml_tpu.data.normalization import (
    NormalizationContext,
    gather_normalization,
    gathered_to_normalized_space,
    gathered_to_original_space,
)
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
    return np.random.default_rng(23)


def _bucket(rng, e, r, d, dtype, scale=None):
    x = rng.normal(0, 1, (e, r, d)).astype(dtype)
    if scale is not None:  # badly-scaled columns: what normalization fixes
        x *= scale[None, None, :]
    x[:, :, 0] = 1.0  # intercept column
    w_true = rng.normal(0, 0.5, (e, d))
    z = np.einsum("erd,ed->er", x / (scale[None, None, :] if scale is not None
                                     else 1.0), w_true)
    y = (rng.random((e, r)) < 1 / (1 + np.exp(-z))).astype(dtype)
    off = rng.normal(0, 0.1, (e, r)).astype(dtype)
    w = np.ones((e, r), dtype)
    return x, y, off, w


def _standardization_arrays(rng, e, r, d, x, dtype):
    """Per-entity STANDARDIZATION-like factor/shift arrays (intercept
    column 0 untouched: factor 1, shift 0)."""
    fac = 1.0 / np.maximum(x.std(axis=(0, 1)), 0.2)
    shf = x.mean(axis=(0, 1))
    fac[0], shf[0] = 1.0, 0.0
    factors = np.tile(fac, (e, 1)).astype(dtype)
    shifts = np.tile(shf, (e, 1)).astype(dtype)
    return jnp.asarray(factors), jnp.asarray(shifts)


def _vmapped(obj, cfg, x, y, off, w, coef0, factors=None, shifts=None,
             lb=None, ub=None):
    def fit_one(c0, xe, ye, oe, we, fe, se, le, ue):
        if se is not None:
            xe = xe - se[None, :]
        if fe is not None:
            xe = xe * fe[None, :]
        return solve_glm(obj, GLMBatch(DenseFeatures(xe), ye, oe, we),
                         cfg, c0, le, ue)

    return jax.vmap(fit_one)(coef0, x, y, off, w, factors, shifts, lb, ub)


@pytest.mark.parametrize("mode,opt,l1", [
    ("lbfgs", OptimizerType.LBFGS, 0.0),
    ("owlqn", OptimizerType.LBFGS, 0.3),
    ("tron", OptimizerType.TRON, 0.0),
])
@pytest.mark.slow
def test_kernel_normalization_matches_vmapped(rng, mode, opt, l1):
    dtype = np.float64 if jax.config.jax_enable_x64 else np.float32
    e, r, d = 29, 6, 5
    scale = np.array([1.0, 10.0, 0.1, 5.0, 0.5])
    x, y, off, w = _bucket(rng, e, r, d, dtype, scale=scale)
    factors, shifts = _standardization_arrays(rng, e, r, d, x, dtype)
    loss = loss_for_task(TaskType.LOGISTIC_REGRESSION)
    obj = GLMObjective(loss)
    reg = (RegularizationContext(RegularizationType.ELASTIC_NET, 0.5)
           if l1 > 0 else RegularizationContext(RegularizationType.L2))
    lam = 0.8
    cfg = GLMOptimizationConfiguration(
        max_iterations=40, tolerance=1e-8, regularization_weight=lam,
        regularization_context=reg, optimizer_type=opt)
    l1w, l2w = reg.l1_weight(lam), reg.l2_weight(lam)
    coef0 = jnp.zeros((e, d), dtype)

    res_k = pallas_entity_lbfgs(
        loss, jnp.asarray(x), jnp.asarray(y), jnp.asarray(off),
        jnp.asarray(w), coef0, l2w, l1w, factors=factors, shifts=shifts,
        max_iter=40, tol=1e-8, mode=mode, interpret=True)
    res_v = _vmapped(obj, cfg, jnp.asarray(x), jnp.asarray(y),
                     jnp.asarray(off), jnp.asarray(w), coef0,
                     factors=factors, shifts=shifts)

    np.testing.assert_allclose(np.asarray(res_k.value),
                               np.asarray(res_v.value),
                               rtol=gold(1e-8, f32_floor=2e-4))
    np.testing.assert_allclose(np.asarray(res_k.x), np.asarray(res_v.x),
                               atol=gold(1e-5, f32_floor=8e-3))
    # Normalization actually did something: the normalized solve from a
    # zero start differs from an un-normalized one.
    res_raw = pallas_entity_lbfgs(
        loss, jnp.asarray(x), jnp.asarray(y), jnp.asarray(off),
        jnp.asarray(w), coef0, l2w, l1w, max_iter=40, tol=1e-8, mode=mode,
        interpret=True)
    assert not np.allclose(np.asarray(res_k.x), np.asarray(res_raw.x),
                           atol=1e-4)


@pytest.mark.parametrize("mode,opt", [
    ("lbfgs", OptimizerType.LBFGS),
    ("tron", OptimizerType.TRON),
])
def test_kernel_bounds_match_vmapped(rng, mode, opt):
    dtype = np.float64 if jax.config.jax_enable_x64 else np.float32
    e, r, d = 33, 6, 5
    x, y, off, w = _bucket(rng, e, r, d, dtype)
    loss = loss_for_task(TaskType.LOGISTIC_REGRESSION)
    obj = GLMObjective(loss)
    cfg = GLMOptimizationConfiguration(
        max_iterations=40, tolerance=1e-8, regularization_weight=0.5,
        regularization_context=RegularizationContext(RegularizationType.L2),
        optimizer_type=opt)
    coef0 = jnp.zeros((e, d), dtype)
    # Tight asymmetric box: several coordinates must end up clamped.
    lb = jnp.full((e, d), -0.05, dtype)
    ub = jnp.full((e, d), 0.12, dtype)

    res_k = pallas_entity_lbfgs(
        loss, jnp.asarray(x), jnp.asarray(y), jnp.asarray(off),
        jnp.asarray(w), coef0, 0.5, lower=lb, upper=ub,
        max_iter=40, tol=1e-8, mode=mode, interpret=True)
    res_v = _vmapped(obj, cfg, jnp.asarray(x), jnp.asarray(y),
                     jnp.asarray(off), jnp.asarray(w), coef0,
                     lb=lb, ub=ub)

    xk = np.asarray(res_k.x)
    assert (xk >= -0.05 - 1e-6).all() and (xk <= 0.12 + 1e-6).all()
    assert (np.isclose(xk, -0.05, atol=1e-6) |
            np.isclose(xk, 0.12, atol=1e-6)).any(), "box never active"
    np.testing.assert_allclose(np.asarray(res_k.value),
                               np.asarray(res_v.value),
                               rtol=gold(1e-7, f32_floor=2e-4))
    np.testing.assert_allclose(xk, np.asarray(res_v.x),
                               atol=gold(1e-5, f32_floor=8e-3))


@pytest.mark.parametrize("mode,opt", [
    ("lbfgs", OptimizerType.LBFGS),
    ("tron", OptimizerType.TRON),
])
def test_kernel_bounds_with_normalization(rng, mode, opt):
    dtype = np.float64 if jax.config.jax_enable_x64 else np.float32
    e, r, d = 17, 5, 4
    scale = np.array([1.0, 8.0, 0.2, 3.0])
    x, y, off, w = _bucket(rng, e, r, d, dtype, scale=scale)
    factors, shifts = _standardization_arrays(rng, e, r, d, x, dtype)
    loss = loss_for_task(TaskType.LOGISTIC_REGRESSION)
    obj = GLMObjective(loss)
    cfg = GLMOptimizationConfiguration(
        max_iterations=40, tolerance=1e-8, regularization_weight=0.5,
        regularization_context=RegularizationContext(RegularizationType.L2),
        optimizer_type=opt)
    coef0 = jnp.zeros((e, d), dtype)
    lb = jnp.full((e, d), -0.08, dtype)
    ub = jnp.full((e, d), 0.15, dtype)

    res_k = pallas_entity_lbfgs(
        loss, jnp.asarray(x), jnp.asarray(y), jnp.asarray(off),
        jnp.asarray(w), coef0, 0.5, factors=factors, shifts=shifts,
        lower=lb, upper=ub, max_iter=40, tol=1e-8, mode=mode,
        interpret=True)
    res_v = _vmapped(obj, cfg, jnp.asarray(x), jnp.asarray(y),
                     jnp.asarray(off), jnp.asarray(w), coef0,
                     factors=factors, shifts=shifts, lb=lb, ub=ub)

    np.testing.assert_allclose(np.asarray(res_k.value),
                               np.asarray(res_v.value),
                               rtol=gold(1e-7, f32_floor=2e-4))
    np.testing.assert_allclose(np.asarray(res_k.x), np.asarray(res_v.x),
                               atol=gold(1e-5, f32_floor=8e-3))


def test_bounds_reject_owlqn_mode():
    """L1 + box constraints stays rejected (matching solve_glm); TRON +
    bounds is now a supported kernel mode (projected trust region,
    TRON.scala:228)."""
    e, r, d = 4, 3, 3
    z = jnp.zeros((e, r, d))
    zr = jnp.zeros((e, r))
    zc = jnp.zeros((e, d))
    loss = loss_for_task(TaskType.LOGISTIC_REGRESSION)
    with pytest.raises(ValueError, match="L1"):
        pallas_entity_lbfgs(loss, z, zr, zr, zr, zc, 0.1, 0.2,
                            lower=jnp.full((e, d), -1.0), mode="owlqn",
                            interpret=True)


def test_gathered_transforms_round_trip(rng):
    """to_normalized ∘ to_original == id on gathered per-entity arrays."""
    e, d = 11, 6
    feat_idx = np.tile(np.arange(d, dtype=np.int32), (e, 1))
    feat_idx[:, -1] = -1  # padding column
    factors = np.abs(rng.normal(1.0, 0.3, 7)).astype(np.float32) + 0.2
    shifts = rng.normal(0, 1.0, 7).astype(np.float32)
    factors[0], shifts[0] = 1.0, 0.0  # intercept at global col 0
    norm = NormalizationContext(jnp.asarray(factors), jnp.asarray(shifts),
                                intercept_id=0)
    fac, shf, mask = gather_normalization(norm, jnp.asarray(feat_idx))
    assert np.allclose(np.asarray(fac)[:, -1], 1.0)
    assert np.allclose(np.asarray(shf)[:, -1], 0.0)
    assert np.array_equal(np.asarray(mask)[:, 0], np.ones(e))

    coef = rng.normal(0, 1, (e, d)).astype(np.float32)
    coef[:, -1] = 0.0  # padding coefficients are zero by construction
    normed = gathered_to_normalized_space(jnp.asarray(coef), fac, shf, mask)
    back = gathered_to_original_space(normed, fac, shf, mask)
    np.testing.assert_allclose(np.asarray(back), coef, atol=1e-5)


@pytest.mark.slow
def test_re_coordinate_normalized_kernel_matches_fallback(monkeypatch, rng):
    """End-to-end: a normalized + bounded RandomEffectCoordinate update
    routes through the kernel (interpret mode) and matches the NO_PALLAS
    fallback, with models in the original space both ways."""
    from photon_ml_tpu.algorithm.coordinates import RandomEffectCoordinate
    from photon_ml_tpu.data.game_data import GameDataset
    from photon_ml_tpu.data.random_effect import (
        RandomEffectDataConfiguration,
        build_random_effect_dataset,
    )
    import scipy.sparse as sp

    n, d = 120, 7
    x = rng.normal(0, 1.0, (n, d))
    x *= np.array([1.0, 6.0, 0.3, 2.0, 1.0, 4.0, 0.5])[None, :]
    x[:, 0] = 1.0  # intercept
    ids = rng.integers(0, 9, n)
    y = (rng.random(n) < 0.5).astype(np.float64)
    data = GameDataset.build(
        responses=y,
        feature_shards={"shard": sp.csr_matrix(x)},
        ids={"userId": np.asarray([f"u{i}" for i in ids])})

    cfg_data = RandomEffectDataConfiguration(
        random_effect_type="userId", feature_shard_id="shard")
    ds = build_random_effect_dataset(data, cfg_data, intercept_col=0)

    std = np.maximum(x.std(axis=0), 1e-3)
    norm = NormalizationContext(
        jnp.asarray(1.0 / std, jnp.float32).at[0].set(1.0),
        jnp.asarray(x.mean(axis=0), jnp.float32).at[0].set(0.0),
        intercept_id=0)
    lb = np.full(d, -0.5, np.float32)
    ub = np.full(d, 0.5, np.float32)
    cfg = GLMOptimizationConfiguration(
        max_iterations=30, tolerance=1e-7, regularization_weight=1.0,
        regularization_context=RegularizationContext(RegularizationType.L2))

    def run(pallas: bool):
        if pallas:
            monkeypatch.setenv("PHOTON_ML_TPU_PALLAS_INTERPRET", "1")
            monkeypatch.delenv("PHOTON_ML_TPU_NO_PALLAS", raising=False)
        else:
            monkeypatch.setenv("PHOTON_ML_TPU_NO_PALLAS", "1")
            monkeypatch.delenv("PHOTON_ML_TPU_PALLAS_INTERPRET",
                               raising=False)
        coord = RandomEffectCoordinate(
            name="re", dataset=ds, task_type=TaskType.LOGISTIC_REGRESSION,
            config=cfg, normalization=norm,
            lower_bounds=jnp.asarray(lb), upper_bounds=jnp.asarray(ub))
        model = coord.initialize_model()
        new_model, _ = coord.update_model(model, None,
                                          jax.random.PRNGKey(0))
        return [np.asarray(c) for c in new_model.local_coefs]

    coefs_k = run(True)
    coefs_v = run(False)
    assert any(np.abs(c).max() > 1e-4 for c in coefs_k), "nothing learned"
    # The dataset blocks are f32 regardless of the suite's x64 config, and
    # kernel vs host are different Armijo solvers (projected + normalized)
    # agreeing to solver tolerance — f32-grade bound, not a golden one.
    for ck, cv in zip(coefs_k, coefs_v):
        np.testing.assert_allclose(ck, cv, atol=2e-3)


def test_re_coordinate_normalization_rejects_projected(rng):
    from photon_ml_tpu.algorithm.coordinates import RandomEffectCoordinate
    from photon_ml_tpu.data.game_data import GameDataset
    from photon_ml_tpu.data.random_effect import (
        RandomEffectDataConfiguration,
        build_random_effect_dataset,
    )
    import scipy.sparse as sp

    n, d = 60, 12
    x = rng.normal(0, 1.0, (n, d))
    x[:, 0] = 1.0
    data = GameDataset.build(
        responses=(rng.random(n) < 0.5).astype(np.float64),
        feature_shards={"shard": sp.csr_matrix(x)},
        ids={"userId": np.asarray([f"u{i % 5}" for i in range(n)])})
    ds = build_random_effect_dataset(
        data, RandomEffectDataConfiguration(
            random_effect_type="userId", feature_shard_id="shard",
            projector_type="RANDOM=4"),
        intercept_col=0)
    cfg = GLMOptimizationConfiguration(
        max_iterations=5, tolerance=1e-7, regularization_weight=1.0,
        regularization_context=RegularizationContext(RegularizationType.L2))
    with pytest.raises(ValueError, match="projected"):
        RandomEffectCoordinate(
            name="re", dataset=ds, task_type=TaskType.LOGISTIC_REGRESSION,
            config=cfg,
            normalization=NormalizationContext(
                jnp.ones((d,)), None, intercept_id=0))


def test_norm_bounds_compose_with_entity_sharding(monkeypatch, rng):
    """The gathered normalization/bounds arrays ride through shard_map
    with the entity-sharded kernel (one kernel per device) and match the
    unsharded kernel solve."""
    from photon_ml_tpu.algorithm.coordinates import _solve_block
    from photon_ml_tpu.data.random_effect import EntityBlock
    from photon_ml_tpu.parallel import make_mesh, shard_block

    dtype = np.float64 if jax.config.jax_enable_x64 else np.float32
    e, r, d = 21, 5, 4
    scale = np.array([1.0, 7.0, 0.3, 2.0])
    x, y, off, w = _bucket(rng, e, r, d, dtype, scale=scale)
    block = EntityBlock(
        x=jnp.asarray(x), labels=jnp.asarray(y), offsets=jnp.asarray(off),
        weights=jnp.asarray(w),
        row_ids=np.zeros((e, r), np.int32),
        feat_idx=np.broadcast_to(np.arange(d, dtype=np.int32), (e, d)))
    obj = GLMObjective(loss_for_task(TaskType.LOGISTIC_REGRESSION))
    factors, shifts = _standardization_arrays(rng, e, r, d, x, dtype)
    mask = jnp.zeros((e, d), dtype).at[:, 0].set(1.0)
    norm = (factors, shifts, mask)
    bounds = (jnp.full((e, d), -0.3, dtype), jnp.full((e, d), 0.3, dtype))

    def cfg(tol):
        return GLMOptimizationConfiguration(
            max_iterations=25, tolerance=tol, regularization_weight=0.4,
            regularization_context=RegularizationContext(
                RegularizationType.L2))

    monkeypatch.setenv("PHOTON_ML_TPU_PALLAS_INTERPRET", "1")
    plain = _solve_block(obj, cfg(1e-8), block, None,
                         jnp.zeros((e, d), dtype), norm=norm,
                         bounds=bounds)
    assert plain.value_history is None  # kernel path

    mesh = make_mesh()
    sblock = shard_block(block, mesh, sentinel_row=1000)
    ep = sblock.num_entities
    pad_e = ep - e

    def pad(a, fill):
        return jnp.concatenate(
            [a, jnp.full((pad_e, d), fill, a.dtype)])

    snorm = (pad(factors, 1.0), pad(shifts, 0.0), pad(mask, 0.0))
    sbounds = (pad(bounds[0], -0.3), pad(bounds[1], 0.3))
    sharded = _solve_block(obj, cfg(1.001e-8), sblock, None,
                           jnp.zeros((ep, d), dtype),
                           mesh=mesh, norm=snorm,
                           bounds=sbounds)
    assert sharded.value_history is None
    np.testing.assert_allclose(np.asarray(sharded.x[:e]),
                               np.asarray(plain.x),
                               atol=gold(1e-6, f32_floor=5e-3))
    np.testing.assert_array_equal(np.asarray(sharded.iterations[e:]), 0)


def test_bounds_clamp_solve_space_coefficients(rng):
    """Reference semantics: the optimizer ITERATE is the normalized-space
    coefficient vector (effectiveCoefficients = coef :* factors,
    ValueAndGradientAggregator.scala:100-120) and
    projectCoefficientsToHypercube clamps it against the RAW constraint
    values (LBFGS.scala:77) — so with factor normalization, the
    SOLVE-SPACE coefficients respect the box and the original-space
    model clamps at bound*factor."""
    from photon_ml_tpu.algorithm.coordinates import RandomEffectCoordinate
    from photon_ml_tpu.data.game_data import GameDataset
    from photon_ml_tpu.data.random_effect import (
        RandomEffectDataConfiguration,
        build_random_effect_dataset,
    )
    import scipy.sparse as sp

    n, d = 200, 4
    x = rng.normal(0, 1.0, (n, d))
    x[:, 0] = 1.0
    # Strong signal on column 1 so its unconstrained coefficient is large.
    w_true = np.array([0.0, 3.0, 0.5, -0.5])
    z = x @ w_true
    y = (rng.random(n) < 1 / (1 + np.exp(-z))).astype(np.float64)
    data = GameDataset.build(
        responses=y,
        feature_shards={"shard": sp.csr_matrix(x)},
        ids={"userId": np.asarray(["u0"] * n)})
    ds = build_random_effect_dataset(
        data, RandomEffectDataConfiguration(
            random_effect_type="userId", feature_shard_id="shard"),
        intercept_col=0)
    # Factor-only normalization (no shifts): scale column 1 hard.
    factors = jnp.asarray([1.0, 0.1, 1.0, 1.0], jnp.float32)
    norm = NormalizationContext(factors, None, intercept_id=0)
    cap = 0.7
    lb = jnp.full((d,), -cap, jnp.float32)
    ub = jnp.full((d,), cap, jnp.float32)
    cfg = GLMOptimizationConfiguration(
        max_iterations=60, tolerance=1e-8, regularization_weight=0.01,
        regularization_context=RegularizationContext(RegularizationType.L2))
    coord = RandomEffectCoordinate(
        name="re", dataset=ds, task_type=TaskType.LOGISTIC_REGRESSION,
        config=cfg, normalization=norm,
        lower_bounds=lb, upper_bounds=ub)
    model, _ = coord.update_model(coord.initialize_model(), None,
                                  jax.random.PRNGKey(0))
    coefs = np.concatenate([np.asarray(c)
                            for c in model.local_coefs], axis=0)
    coefs = coefs[:, :d]  # strip padding columns (local cols 0..d-1
    # map to global cols 0..d-1: single entity set, all observed)
    # Solve-space coefficients (w' = w / factor; no shifts here) respect
    # the box...
    solve_space = coefs / np.asarray(factors)[None, :]
    assert (np.abs(solve_space) <= cap + 1e-4).all(), solve_space
    # ...the box is actually ACTIVE (the unconstrained solve-space
    # coefficient on the strong column exceeds the cap)...
    assert np.isclose(np.abs(solve_space).max(), cap, atol=1e-3)
    # ...and the ORIGINAL-space coefficient on the hard-scaled column 1
    # (factor 0.1) therefore clamps at cap*factor, NOT at the raw cap.
    assert np.abs(coefs[:, 1]).max() <= cap * 0.1 + 1e-4, coefs


def test_mesh_sharded_coordinate_with_shift_normalization(rng):
    """Sentinel padding entities added by entity sharding (feat_idx == -1
    everywhere) must not trip the intercept-present validation — mesh +
    STANDARDIZATION is a supported composition."""
    from photon_ml_tpu.algorithm.coordinates import RandomEffectCoordinate
    from photon_ml_tpu.data.game_data import GameDataset
    from photon_ml_tpu.data.random_effect import (
        RandomEffectDataConfiguration,
        build_random_effect_dataset,
    )
    from photon_ml_tpu.parallel import make_mesh
    import scipy.sparse as sp

    n, d = 90, 5
    x = rng.normal(0, 1.0, (n, d))
    x[:, 0] = 1.0
    # 9 users — NOT divisible by the 8-device mesh: sharding pads with
    # sentinel entities.
    data = GameDataset.build(
        responses=(rng.random(n) < 0.5).astype(np.float64),
        feature_shards={"shard": sp.csr_matrix(x)},
        ids={"userId": np.asarray([f"u{i % 9}" for i in range(n)])})
    ds = build_random_effect_dataset(
        data, RandomEffectDataConfiguration(
            random_effect_type="userId", feature_shard_id="shard"),
        intercept_col=0)
    norm = NormalizationContext(
        jnp.ones((d,), jnp.float32),
        jnp.asarray(x.mean(axis=0), jnp.float32).at[0].set(0.0),
        intercept_id=0)
    cfg = GLMOptimizationConfiguration(
        max_iterations=10, tolerance=1e-6, regularization_weight=1.0,
        regularization_context=RegularizationContext(RegularizationType.L2))
    coord = RandomEffectCoordinate(
        name="re", dataset=ds, task_type=TaskType.LOGISTIC_REGRESSION,
        config=cfg, normalization=norm, mesh=make_mesh())
    model, _ = coord.update_model(coord.initialize_model(), None,
                                  jax.random.PRNGKey(0))
    assert any(np.abs(np.asarray(c)).max() > 1e-5
               for c in model.local_coefs)


# Rows past the kernel's chunk of 8, its loop stepping 8 rows at a time:
# one static chunk and a tail (12), a rolled loop and a tail (20);
# normalization applied where a row is read.
@pytest.mark.parametrize("mode,opt", [
    ("lbfgs", OptimizerType.LBFGS),
    ("tron", OptimizerType.TRON),
])
@pytest.mark.parametrize("r", [12, 20])
def test_kernel_bounds_with_normalization_across_row_chunks(
        rng, monkeypatch, mode, opt, r):
    from photon_ml_tpu.ops import pallas_entity_solver as K

    monkeypatch.setattr(K, "ROWS_PER_STEP", 8)  # read at trace time
    K.pallas_entity_lbfgs.clear_cache()
    dtype = np.float64 if jax.config.jax_enable_x64 else np.float32
    e, d = 17, 4
    scale = np.array([1.0, 8.0, 0.2, 3.0])
    x, y, off, w = _bucket(rng, e, r, d, dtype, scale=scale)
    factors, shifts = _standardization_arrays(rng, e, r, d, x, dtype)
    loss = loss_for_task(TaskType.LOGISTIC_REGRESSION)
    obj = GLMObjective(loss)
    cfg = GLMOptimizationConfiguration(
        max_iterations=40, tolerance=1e-8, regularization_weight=0.5,
        regularization_context=RegularizationContext(RegularizationType.L2),
        optimizer_type=opt)
    coef0 = jnp.zeros((e, d), dtype)
    lb = jnp.full((e, d), -0.08, dtype)
    ub = jnp.full((e, d), 0.15, dtype)

    res_k = pallas_entity_lbfgs(
        loss, jnp.asarray(x), jnp.asarray(y), jnp.asarray(off),
        jnp.asarray(w), coef0, 0.5, factors=factors, shifts=shifts,
        lower=lb, upper=ub, max_iter=40, tol=1e-8, mode=mode,
        interpret=True)
    res_v = _vmapped(obj, cfg, jnp.asarray(x), jnp.asarray(y),
                     jnp.asarray(off), jnp.asarray(w), coef0,
                     factors=factors, shifts=shifts, lb=lb, ub=ub)
    monkeypatch.undo()
    K.pallas_entity_lbfgs.clear_cache()

    np.testing.assert_allclose(np.asarray(res_k.value),
                               np.asarray(res_v.value),
                               rtol=gold(1e-7, f32_floor=2e-4))
    np.testing.assert_allclose(np.asarray(res_k.x), np.asarray(res_v.x),
                               atol=gold(1e-5, f32_floor=8e-3))
