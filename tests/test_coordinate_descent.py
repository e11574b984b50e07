"""GAME coordinate-descent integration tests on synthetic GLMix data —
the analog of the reference's CoordinateDescentTest + GameEstimatorTest
(using generated fixed+random effect data like GameTestUtils does).
"""

import numpy as np
import pytest
import scipy.sparse as sp

from photon_ml_tpu.algorithm import (
    CoordinateDescent,
    FixedEffectCoordinate,
    RandomEffectCoordinate,
)
from photon_ml_tpu.data.game_data import GameDataset
from photon_ml_tpu.data.random_effect import (
    RandomEffectDataConfiguration,
    build_random_effect_dataset,
)
from photon_ml_tpu.evaluation import build_evaluator
from photon_ml_tpu.optimization.config import (
    GLMOptimizationConfiguration,
    RegularizationContext,
    RegularizationType,
)
from photon_ml_tpu.types import TaskType


def make_glmix_data(rng, n=400, d=6, n_users=12, user_strength=2.0):
    """Logistic data with a global linear effect + per-user intercept shift."""
    x = rng.normal(0, 1, (n, d))
    x[:, -1] = 1.0
    w_global = rng.normal(0, 1, d)
    users = rng.integers(0, n_users, n)
    user_bias = rng.normal(0, user_strength, n_users)
    z = x @ w_global + user_bias[users]
    y = (rng.random(n) < 1 / (1 + np.exp(-z))).astype(float)

    user_feats = sp.csr_matrix(np.ones((n, 1)))  # per-user intercept shard
    data = GameDataset.build(
        responses=y,
        feature_shards={"global": sp.csr_matrix(x), "user": user_feats},
        ids={"userId": np.asarray([f"u{u}" for u in users])},
    )
    return data, w_global, user_bias, users


def build_coordinates(data, fe_cfg=None, re_cfg=None):
    fe_cfg = fe_cfg or GLMOptimizationConfiguration(
        max_iterations=50, tolerance=1e-8, regularization_weight=0.1,
        regularization_context=RegularizationContext(RegularizationType.L2),
    )
    re_cfg = re_cfg or GLMOptimizationConfiguration(
        max_iterations=30, tolerance=1e-8, regularization_weight=0.1,
        regularization_context=RegularizationContext(RegularizationType.L2),
    )
    re_data = build_random_effect_dataset(
        data, RandomEffectDataConfiguration("userId", "user"),
        intercept_col=0)
    fixed = FixedEffectCoordinate(
        name="fixed", data=data, feature_shard_id="global",
        task_type=TaskType.LOGISTIC_REGRESSION, config=fe_cfg)
    per_user = RandomEffectCoordinate(
        name="perUser", dataset=re_data,
        task_type=TaskType.LOGISTIC_REGRESSION, config=re_cfg)
    return {"fixed": fixed, "perUser": per_user}


def test_fixed_effect_only_descent(rng):
    data, w_global, _, _ = make_glmix_data(rng, user_strength=0.0)
    coords = build_coordinates(data)
    cd = CoordinateDescent({"fixed": coords["fixed"]},
                           TaskType.LOGISTIC_REGRESSION)
    res = cd.run(num_iterations=2)
    fe = res.model.get_model("fixed")
    w = np.asarray(fe.glm.coefficients.means)
    corr = np.corrcoef(w, w_global)[0, 1]
    assert corr > 0.9
    h = res.objective_history
    assert h[-1] <= h[0] + 1e-5 * abs(h[0])  # f32 noise margin


def test_glmix_descent_improves_and_recovers_user_bias(rng):
    data, w_global, user_bias, users = make_glmix_data(rng)
    coords = build_coordinates(data)
    cd = CoordinateDescent(coords, TaskType.LOGISTIC_REGRESSION)
    res = cd.run(num_iterations=3)

    # Objective decreases across coordinate updates.
    h = res.objective_history
    assert h[-1] < h[0]
    # Monotone non-increasing up to tiny numerical noise.
    assert all(h[i + 1] <= h[i] + 1e-4 * abs(h[i]) for i in range(len(h) - 1))

    # The per-user random intercepts should correlate with the true biases.
    re_model = res.model.get_model("perUser")
    m = re_model.model_matrix().toarray()[:, 0]
    vocab = re_model.vocabulary
    learned = np.asarray(
        [m[np.flatnonzero(vocab == f"u{u}")[0]]
         for u in range(len(user_bias))])
    corr = np.corrcoef(learned, user_bias)[0, 1]
    assert corr > 0.8, f"user-bias corr {corr}"


def test_random_effect_scoring_device_equals_host(rng):
    """The device scatter path and the host model_matrix path must agree —
    this pins the projected-space round trip
    (RandomEffectModelInProjectedSpace conversion semantics)."""
    data, *_ = make_glmix_data(rng)
    coords = build_coordinates(data)
    cd = CoordinateDescent(coords, TaskType.LOGISTIC_REGRESSION)
    res = cd.run(num_iterations=1)
    re_coord = coords["perUser"]
    re_model = res.model.get_model("perUser")
    device_scores = np.asarray(re_coord.score(re_model))
    host_scores = re_model.score_numpy(data)
    np.testing.assert_allclose(device_scores, host_scores, atol=1e-5)


def test_validation_tracking_selects_best(rng):
    data, *_ = make_glmix_data(rng, n=500)
    train = data.subset(np.arange(400))
    valid = data.subset(np.arange(400, 500))
    coords = build_coordinates(train)
    cd = CoordinateDescent(
        coords, TaskType.LOGISTIC_REGRESSION,
        validation_data=valid,
        validation_evaluators=[build_evaluator("AUC"),
                               build_evaluator("LOGISTIC_LOSS")])
    res = cd.run(num_iterations=2)
    assert len(res.validation_history) == 2
    assert res.best_metric is not None
    assert res.best_metric >= 0.5  # AUC no worse than random
    for metrics in res.validation_history:
        assert set(metrics) == {"AUC", "LOGISTIC_LOSS"}


def test_warm_start_resumes(rng):
    data, *_ = make_glmix_data(rng)
    coords = build_coordinates(data)
    cd = CoordinateDescent(coords, TaskType.LOGISTIC_REGRESSION)
    res1 = cd.run(num_iterations=1)
    res2 = cd.run(num_iterations=1, initial_model=res1.model)
    assert res2.objective_history[-1] <= res1.objective_history[-1] + 1e-6


@pytest.mark.slow
def test_cd_objective_invariant_across_mesh_sizes(rng):
    """Sharding invariance — the BASELINE north-star's chip-scaling
    property testable without a pod: the SAME GLMix descent on 1/2/4/8
    virtual devices produces the same objective trajectory (row padding,
    entity padding, and the psum'd reductions are all exact no-ops on the
    math)."""
    from photon_ml_tpu.parallel import make_mesh
    from tests.conftest import gold

    data, *_ = make_glmix_data(rng, n=300)
    histories = {}
    for n_dev in (1, 2, 4, 8):
        mesh = make_mesh(n_dev)
        fe_cfg = GLMOptimizationConfiguration(
            max_iterations=20, tolerance=1e-8, regularization_weight=0.1,
            regularization_context=RegularizationContext(
                RegularizationType.L2))
        re_data = build_random_effect_dataset(
            data, RandomEffectDataConfiguration("userId", "user"),
            intercept_col=0)
        coords = {
            "fixed": FixedEffectCoordinate(
                name="fixed", data=data, feature_shard_id="global",
                task_type=TaskType.LOGISTIC_REGRESSION, config=fe_cfg,
                mesh=mesh),
            "perUser": RandomEffectCoordinate(
                name="perUser", dataset=re_data,
                task_type=TaskType.LOGISTIC_REGRESSION, config=fe_cfg,
                mesh=mesh),
        }
        cd = CoordinateDescent(coords, TaskType.LOGISTIC_REGRESSION)
        histories[n_dev] = cd.run(num_iterations=2).objective_history
    base = histories[1]
    for n_dev, h in histories.items():
        # Reduction reassociation across shards perturbs low bits, which
        # the iterative solver amplifies to ~solver-tolerance differences;
        # a padding/sharding BUG shows up orders of magnitude larger.
        np.testing.assert_allclose(h, base, rtol=gold(1e-5, f32_floor=1e-3),
                                   err_msg=f"mesh size {n_dev}")


# -- a cold start is built, not computed ---------------------------------------

TASK = TaskType.LOGISTIC_REGRESSION


def _factored_coordinates(data):
    from photon_ml_tpu.algorithm import FactoredRandomEffectCoordinate
    from photon_ml_tpu.optimization.config import MFOptimizationConfiguration

    cfg = GLMOptimizationConfiguration(
        max_iterations=10, tolerance=1e-8, regularization_weight=0.1,
        regularization_context=RegularizationContext(RegularizationType.L2))
    ds = build_random_effect_dataset(
        data, RandomEffectDataConfiguration("userId", "global",
                                            projector_type="IDENTITY"))
    return {"perUserMF": FactoredRandomEffectCoordinate(
        name="perUserMF", dataset=ds, task_type=TASK, config=cfg,
        latent_config=cfg,
        mf_config=MFOptimizationConfiguration(max_iterations=1,
                                              num_factors=2))}


def _mesh_coordinates(data):
    """``build_coordinates``'s fit with both coordinates over four devices,
    as ``GameEstimator(mesh=make_mesh(4))`` builds it."""
    import dataclasses

    from photon_ml_tpu.parallel import make_mesh

    mesh = make_mesh(4)
    return {n: dataclasses.replace(c, mesh=mesh)
            for n, c in build_coordinates(data).items()}


COLD_CASES = {
    "fixed+random": build_coordinates,
    "fixed": lambda data: {"fixed": build_coordinates(data)["fixed"]},
    "random": lambda data: {"perUser": build_coordinates(data)["perUser"]},
    "factored": _factored_coordinates,
    "mesh": _mesh_coordinates,
}


class _ScoreSpy:
    """Stands in for every coordinate class's ``pure_score`` and keeps, by
    coordinate name, how often it ran EAGERLY: with arrays and not tracers,
    which is a program dispatched before the block."""

    def __init__(self, monkeypatch, coords):
        self.eager = {n: 0 for n in coords}
        for cls in {type(c) for c in coords.values()}:
            monkeypatch.setattr(cls, "pure_score", self._wrap(cls.pure_score))

    def _wrap(self, real):
        import jax

        def pure_score(coord, data, params):
            leaves = jax.tree.leaves((data, params))
            if not any(isinstance(x, jax.core.Tracer) for x in leaves):
                self.eager[coord.name] += 1
            return real(coord, data, params)

        return pure_score


def _initial_game_model(coords):
    from photon_ml_tpu.models.game_model import GameModel

    return GameModel({n: c.initialize_model() for n, c in coords.items()},
                     TASK)


def _leaves(cd, result):
    import jax

    return [np.asarray(x) for n, c in cd.coordinates.items()
            for x in jax.tree.leaves(c.params_of(result.model.get_model(n)))]


@pytest.mark.parametrize("case", sorted(COLD_CASES))
def test_cold_start_equals_the_scored_start_bitwise(rng, case):
    """``run()`` from nothing builds its zero scores; ``run(initial_model=
    <the coordinates' own initialize_model()>)`` computes them (the scored
    branch). Objective history, final coefficients and the final model's
    scores must agree bit for bit: zero times finite is zero."""
    data, *_ = make_glmix_data(rng, n=300)
    coords = COLD_CASES[case](data)
    cd = CoordinateDescent(coords, TASK)
    cold = cd.run(2, seed=5)
    scored = cd.run(2, seed=5, initial_model=_initial_game_model(coords))
    assert cold.objective_history == scored.objective_history
    assert len(cold.objective_history) == 2 * len(coords)
    for a, b in zip(_leaves(cd, cold), _leaves(cd, scored)):
        assert a.dtype == b.dtype and np.array_equal(a, b)
    for n, c in coords.items():
        assert type(cold.model.get_model(n)) is type(scored.model.get_model(n))
        assert np.array_equal(np.asarray(c.score(cold.model.get_model(n))),
                              np.asarray(c.score(scored.model.get_model(n))))


@pytest.mark.parametrize("case", sorted(COLD_CASES))
def test_cold_start_scores_nothing_before_the_block(rng, monkeypatch, case):
    """Counted with a patched ``pure_score`` (eager calls: arrays, not
    tracers), a patched ``initialize_model`` and a counting stand-in for
    the one zero-vector program, because JAX has no event per dispatch;
    the compile ledger adds that no scoring program was ever compiled
    (they are traced, inside the block). A cold run calls no coordinate's
    ``pure_score`` outside a trace. From an object's second cold run on
    (its first builds the initial models, once) nothing is dispatched
    before the block but the one program that makes the zero vectors."""
    from photon_ml_tpu.algorithm import coordinate_descent
    from photon_ml_tpu.utils import compile_cache

    data, *_ = make_glmix_data(rng, n=310 + len(case))  # shapes of its own
    coords = COLD_CASES[case](data)
    spy = _ScoreSpy(monkeypatch, coords)
    compile_cache._listen()
    compile_cache.reset_compile_ledger()
    cd = CoordinateDescent(coords, TASK)
    first = cd.run(1, seed=3)
    assert spy.eager == {n: 0 for n in coords}
    rows = compile_cache.compile_ledger()["functions"]
    for fn in ("_fe_score_impl", "_re_score_impl", "_fre_score_impl"):
        assert rows.get(fn, {"compiles": 0})["compiles"] == 0, fn
    assert rows["_zero_vectors"]["compiles"] == 1

    calls = {"zeros": 0, "models": 0}
    real_zeros = coordinate_descent._zero_vectors

    def zero_vectors(specs):
        calls["zeros"] += 1
        return real_zeros(specs)

    def initialize_model(self):
        calls["models"] += 1
        raise AssertionError("initial models are built once an object")

    monkeypatch.setattr(coordinate_descent, "_zero_vectors", zero_vectors)
    for cls in {type(c) for c in coords.values()}:
        monkeypatch.setattr(cls, "initialize_model", initialize_model)
    before = compile_cache.compile_ledger()
    second = cd.run(1, seed=3)
    assert calls == {"zeros": 1, "models": 0}
    assert spy.eager == {n: 0 for n in coords}
    assert compile_cache.compile_ledger() == before  # nothing new was needed
    assert second.objective_history == first.objective_history
    compile_cache.reset_compile_ledger()


@pytest.mark.parametrize("case", sorted(COLD_CASES))
def test_cold_parameters_lie_where_the_block_takes_them(rng, case):
    """Over a mesh the cold start lays its parameters out once, with the
    shardings the compiled block takes them with (``param_shardings``): a
    later cold run's dispatch moves nothing between devices, and the block
    compiles once for the object. On one device nothing is placed: the
    parameters stay the uncommitted arrays ``jnp.asarray`` made."""
    import jax

    from photon_ml_tpu.algorithm.coordinate_descent import _zero_vectors

    data, *_ = make_glmix_data(rng, n=320)
    coords = COLD_CASES[case](data)
    cd = CoordinateDescent(coords, TASK)
    first = cd.run(1, seed=7)
    with jax.transfer_guard_device_to_device("disallow"):
        second = cd.run(1, seed=7)
    assert second.objective_history == first.objective_history
    assert cd.tracing_guard.counts() == {"block:1": 1}
    start = cd._cold_cache
    args = ({n: c.step_data() for n, c in coords.items()},
            {n: c.penalty_data() for n, c in coords.items()},
            start.params, _zero_vectors(start.score_specs),
            jax.random.PRNGKey(7), np.uint32(0), cd._rows_cache)
    taken = cd._fused_block_fn(1).lower(*args).compile().input_shardings
    stated = {n: c.param_shardings() for n, c in coords.items()}
    assert (case == "mesh") == any(s is not None for s in stated.values())
    for n in coords:
        leaves = jax.tree.leaves(start.params[n])
        if stated[n] is None:
            assert not any(x.committed for x in leaves)
            assert all(len(x.sharding.device_set) == 1 for x in leaves)
            continue
        assert [x.sharding for x in leaves] == jax.tree.leaves(stated[n])
        assert jax.tree.leaves(taken[0][2][n]) == jax.tree.leaves(stated[n])


def test_placed_cold_start_is_bitwise_the_inferred_one(rng, monkeypatch,
                                                       telemetry_on):
    """Over four devices, two cold runs on one object (parameters placed
    once) give bit for bit what a run handed the coordinates' own zero
    models gives where no coordinate states its shardings: today's
    uncommitted arrays, which every dispatch lays over the mesh again. The
    objective history, the coefficients and the scores agree; the counters
    tell the two apart."""
    from photon_ml_tpu.telemetry import scopes

    data, *_ = make_glmix_data(rng, n=330)  # no multiple of the mesh
    coords = _mesh_coordinates(data)
    cd = CoordinateDescent(coords, TASK)
    cold = [cd.run(2, seed=5) for _ in range(2)]
    moves = telemetry_on.counter(scopes.COUNTER_CD_DISPATCH_MOVES)
    placed = telemetry_on.gauge(scopes.GAUGE_CD_COLD_PLACED_LEAVES)
    leaves = 1 + len(coords["perUser"].dataset.blocks)
    assert (placed.value, placed.calls, moves.value) == (leaves, 1, 0)
    for cls in {type(c) for c in coords.values()}:
        monkeypatch.setattr(cls, "param_shardings", lambda self: None)
    inferred = CoordinateDescent(coords, TASK).run(
        2, seed=5, initial_model=_initial_game_model(coords))
    assert moves.value == leaves  # one dispatch, every parameter leaf
    for run in cold:
        assert run.objective_history == inferred.objective_history
        for a, b in zip(_leaves(cd, run), _leaves(cd, inferred)):
            assert a.dtype == b.dtype and np.array_equal(a, b)
        for n, c in coords.items():
            assert np.array_equal(
                np.asarray(c.score(run.model.get_model(n))),
                np.asarray(c.score(inferred.model.get_model(n))))


def test_nothing_is_placed_or_moved_on_one_device(rng, telemetry_on):
    from photon_ml_tpu.telemetry import scopes

    data, *_ = make_glmix_data(rng)
    cd = CoordinateDescent(build_coordinates(data), TASK)
    cd.run(1, seed=1)
    cd.run(1, seed=1)
    placed = telemetry_on.gauge(scopes.GAUGE_CD_COLD_PLACED_LEAVES)
    assert (placed.value, placed.calls) == (0, 1)
    assert telemetry_on.counter(scopes.COUNTER_CD_DISPATCH_MOVES).value == 0


def test_zero_vectors_carry_what_pure_score_returns(rng):
    """Shape, dtype and, over a mesh, the sharding of the compiled scoring
    program's output: the scan's carry must not change type and the block
    is compiled for one layout of its arguments."""
    import jax

    from photon_ml_tpu.algorithm.coordinate_descent import (
        _score_spec,
        _zero_vectors,
    )
    from photon_ml_tpu.parallel import make_mesh

    data, *_ = make_glmix_data(rng, n=300)
    for mesh in (None, make_mesh(2)):
        re_data = build_random_effect_dataset(
            data, RandomEffectDataConfiguration("userId", "user"),
            intercept_col=0)
        base = build_coordinates(data)
        coords = {
            "fixed": FixedEffectCoordinate(
                name="fixed", data=data, feature_shard_id="global",
                task_type=TASK, config=base["fixed"].config, mesh=mesh),
            "perUser": RandomEffectCoordinate(
                name="perUser", dataset=re_data, task_type=TASK,
                config=base["perUser"].config, mesh=mesh)}
        specs = tuple(
            (n, _score_spec(c, c.step_data(),
                            c.params_of(c.initialize_model())))
            for n, c in coords.items())
        built = _zero_vectors(specs)
        for n, c in coords.items():
            scored = c.pure_score(c.step_data(),
                                  c.params_of(c.initialize_model()))
            assert built[n].shape == scored.shape
            assert built[n].dtype == scored.dtype
            assert not np.asarray(built[n]).any()
            if mesh is None:
                assert dict(specs)[n].sharding is None
            else:
                assert len(scored.sharding.device_set) == 2
                assert built[n].sharding.is_equivalent_to(
                    scored.sharding, scored.ndim)
        cd = CoordinateDescent(coords, TASK)
        cold = cd.run(1, seed=2)
        scored = cd.run(1, seed=2, initial_model=_initial_game_model(coords))
        assert cold.objective_history == scored.objective_history
        assert cd._fused_block_fn(1)._cache_size() == 1  # one executable


class _StartsElsewhere(FixedEffectCoordinate):
    """Brings its own ``initialize_model`` and says nothing of what it
    scores: the mark of the parent's does not pass to it."""

    def initialize_model(self):
        model = super().initialize_model()
        return self.model_of(0.25 + self.params_of(model), model)


@pytest.fixture
def telemetry_on():
    from photon_ml_tpu import telemetry

    telemetry.reset()
    telemetry.enable()
    yield telemetry
    telemetry.disable()
    telemetry.reset()


def _counts(telemetry):
    from photon_ml_tpu.telemetry import scopes

    return (telemetry.counter(scopes.COUNTER_CD_COLD_STARTS).value,
            telemetry.counter(scopes.COUNTER_CD_RUNS).value)


def test_only_a_declared_zero_start_is_built(rng, monkeypatch, telemetry_on):
    """The guard: the base class declares nothing, and a class with an
    ``initialize_model`` of its own is scored on a cold start (here it
    starts at 0.25 a coefficient, so built zeros would be wrong)."""
    from photon_ml_tpu.algorithm.coordinates import Coordinate

    assert Coordinate.zero_start.fget(Coordinate()) is False
    data, *_ = make_glmix_data(rng)
    coords = build_coordinates(data)
    fixed = coords["fixed"]
    assert fixed.zero_start and coords["perUser"].zero_start
    coords["fixed"] = _StartsElsewhere(
        name="fixed", data=data, feature_shard_id="global", task_type=TASK,
        config=fixed.config)
    assert not coords["fixed"].zero_start
    spy = _ScoreSpy(monkeypatch, coords)
    cd = CoordinateDescent(coords, TASK)
    cold = cd.run(1, seed=4)
    assert spy.eager == {"fixed": 1, "perUser": 0}
    assert _counts(telemetry_on) == (1, 1)
    scored = cd.run(1, seed=4, initial_model=_initial_game_model(coords))
    assert spy.eager == {"fixed": 2, "perUser": 1}
    assert cold.objective_history == scored.objective_history
    assert _counts(telemetry_on) == (1, 2)


def test_warm_and_resumed_runs_are_scored(rng, monkeypatch, tmp_path,
                                          telemetry_on):
    """A warm start and a run that restored a checkpoint take the scoring
    branch (every coordinate's ``pure_score`` runs once, eagerly) and are
    no cold starts by the counter; a run with a checkpoint directory that
    holds nothing yet is cold."""
    data, *_ = make_glmix_data(rng)
    coords = build_coordinates(data)
    spy = _ScoreSpy(monkeypatch, coords)
    cd = CoordinateDescent(coords, TASK)
    first = cd.run(1, seed=6, checkpoint_dir=tmp_path, checkpoint_interval=2)
    assert spy.eager == {"fixed": 0, "perUser": 0}
    assert _counts(telemetry_on) == (1, 1)
    resumed = cd.run(2, seed=6, checkpoint_dir=tmp_path,
                     checkpoint_interval=2)
    assert spy.eager == {"fixed": 1, "perUser": 1}
    assert resumed.objective_history[:2] == first.objective_history
    assert _counts(telemetry_on) == (1, 2)
    cd.run(1, seed=6, initial_model=first.model)
    assert spy.eager == {"fixed": 2, "perUser": 2}
    assert _counts(telemetry_on) == (1, 3)
    whole = CoordinateDescent(build_coordinates(data), TASK).run(2, seed=6)
    assert whole.objective_history == resumed.objective_history
    assert _counts(telemetry_on) == (2, 4)


def test_cold_start_counter_is_silent_while_telemetry_is_off(rng):
    from photon_ml_tpu import telemetry

    telemetry.disable()
    telemetry.reset()
    from photon_ml_tpu.telemetry import scopes

    data, *_ = make_glmix_data(rng)
    CoordinateDescent(build_coordinates(data), TASK).run(1)
    assert _counts(telemetry) == (0, 0)
    # nor do the mesh placement's gauge and counter say anything
    CoordinateDescent(_mesh_coordinates(data), TASK).run(1)
    assert _counts(telemetry) == (0, 0)
    assert telemetry.gauge(scopes.GAUGE_CD_COLD_PLACED_LEAVES).calls == 0
    assert telemetry.counter(scopes.COUNTER_CD_DISPATCH_MOVES).value == 0
