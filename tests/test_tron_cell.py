"""L2 logistic regression by trust-region Newton (TRON) on the normal path:
one ``FixedEffectCoordinate`` under TRON, fitted through
``CoordinateDescent.run`` (one ``cd_block``), held on the CPU at a small size
through the cell ``tron-lr.fit``'s own files (the recipe's power-law
columns, the job kind, the plain reference ``benchmark/reference/
tron_glm.py``); the counts the solver reports (CG steps and outer steps
attempted) against a hand count, and the last CG where it ran; the
Hessian-vector products' scope in the block's instruction table; the
counters under telemetry; and a TRON solve's sparse products."""

import collections
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from photon_ml_tpu import telemetry
from photon_ml_tpu.telemetry import scopes
from photon_ml_tpu.utils import compile_cache

ROOT = Path(__file__).resolve().parents[1]
CELL = "tron-lr.fit"
# 8,000 rows of 1,000 columns: every CG stops at its cap of 20, as at the
# cell's size. With fewer columns (4,000 x 64) the CG stops on its residual
# test, whose outcome a float32 rounding moves (44 steps against 45).
ROWS, D = 8000, 1000
SEED = 2 ** 31 + 43


@pytest.fixture(autouse=True)
def _clean_telemetry():
    telemetry.disable()
    telemetry.reset()
    yield
    telemetry.disable()
    telemetry.reset()


def small_cell(optimizer: str = None):
    """The cell's configuration on ``ROWS`` rows of ``D`` columns (the
    recipe's law, the L2 weight scaled with the rows), and its workload."""
    from benchmark import harness
    from benchmark.recipes import dense_tron

    loaded = harness.load_cell(CELL)
    config = dense_tron.scale_down(loaded["config"], ROWS)
    config["fixed"]["d"] = D
    if optimizer:
        config["fixed"]["optimizer"] = optimizer
    return config, loaded["workload"]


def _fit(config, workload, problem, kind="cd_fit_tron"):
    """One job of the cell's job kind (or of ``cd_fit``, which takes any
    optimizer string): ``CoordinateDescent.run`` from zero, after a warm-up
    job on the same object."""
    import importlib

    jobs = importlib.import_module(f"benchmark.jobs.{kind}")
    with jax.enable_x64(False):  # the cell is a float32 configuration
        job = jobs.build(config, workload, problem)
        job.warm_up(SEED)
        window = job.window(0.0, SEED)  # one job
        job.after_window(window)
    return job, window


@pytest.fixture(scope="module")
def cell():
    return small_cell()


@pytest.fixture(scope="module")
def problem(cell):
    from benchmark.recipes import dense_tron

    with jax.enable_x64(False):
        return dense_tron.make(cell[0], SEED)


@pytest.fixture(scope="module")
def ref(cell, problem):
    from benchmark.reference import tron_glm

    with jax.enable_x64(False):
        return tron_glm.fit(problem, cell[0])


@pytest.fixture(scope="module")
def sound(cell, problem):
    """One job with telemetry on and the compile ledger listening: the
    answer, the job's counters, the telemetry counters and the block's
    instruction table."""
    compile_cache._listen()
    compile_cache.reset_compile_ledger()
    telemetry.reset()
    telemetry.enable()
    try:
        job, window = _fit(*cell, problem)
        counts = telemetry.snapshot()["counters"]
        with jax.enable_x64(False):
            counters = job.counters(window)
    finally:
        telemetry.disable()
        telemetry.reset()
    return {"window": window, "counters": counters, "counts": counts,
            "table": dict(compile_cache.instruction_scopes())}


def _solve(window):
    (tracker,) = window["kept"]["last"]["trackers"]["fixed"]
    return tracker


# -- the program's objective against the reference's, at any point -----------


def test_value_gradient_and_hessian_vector_product_at_a_random_point(
        cell, problem):
    from benchmark.reference import tron_glm
    from photon_ml_tpu.ops.features import DenseFeatures
    from photon_ml_tpu.ops.glm_objective import GLMBatch, GLMObjective
    from photon_ml_tpu.ops.losses import loss_for_task
    from photon_ml_tpu.types import TaskType

    config = cell[0]
    l2 = tron_glm.optimizer_of(config["fixed"]["optimizer"])["l2"]
    rng = np.random.default_rng(3)
    w = jnp.asarray(rng.normal(size=D), jnp.float32)
    v = jnp.asarray(rng.normal(size=D), jnp.float32)
    with jax.enable_x64(False):
        objective = GLMObjective(loss_for_task(TaskType.LOGISTIC_REGRESSION))
        batch = GLMBatch(DenseFeatures(problem.x), problem.labels,
                         problem.offsets, problem.weights)
        f, g = objective.value_and_grad(w, batch, l2)
        hv = objective.make_tron_hvp(w, batch, l2)(v)
        f_ref, g_ref = tron_glm.value_and_grad(problem, config, w)
        hv_ref = tron_glm.hvp(problem, config, w, v)
    assert float(f) == pytest.approx(float(f_ref), rel=1e-6)
    np.testing.assert_allclose(g, g_ref, rtol=1e-4, atol=1e-3)
    np.testing.assert_allclose(hv, hv_ref, rtol=1e-4, atol=1e-3)
    # and both against float64 on the host
    x = np.asarray(problem.x, np.float64)
    y = np.asarray(problem.labels, np.float64)
    w64, v64 = np.asarray(w, np.float64), np.asarray(v, np.float64)
    z = x @ w64
    p = 1 / (1 + np.exp(-z))
    assert float(f_ref) == pytest.approx(
        float(np.sum(np.logaddexp(0, z) - y * z) + 0.5 * l2 * w64 @ w64),
        rel=1e-5)
    np.testing.assert_allclose(g_ref, x.T @ (p - y) + l2 * w64,
                               rtol=1e-4, atol=1e-3)
    np.testing.assert_allclose(
        hv_ref, x.T @ (p * (1 - p) * (x @ v64)) + l2 * v64,
        rtol=1e-4, atol=1e-3)


# -- the fit through cd_block against the reference's TRON -------------------


def test_every_accepted_iterate_final_coefficients_and_counts(sound, ref):
    tracker = _solve(sound["window"])
    its = int(np.asarray(tracker.iterations))
    assert its == ref["accepted"] == 5  # the cap binds
    # an ill-conditioned CG in float32 moves with the order of a sum: the
    # two paths part by 0.24% after the first step and meet again (1e-6)
    path = np.asarray(tracker.value_history)[:its + 1]
    np.testing.assert_allclose(path, ref["values"], rtol=5e-3)
    assert path[-1] == pytest.approx(ref["values"][-1], rel=1e-5)
    w = np.asarray(sound["window"]["kept"]["last"]["coefs"]["fixed"])
    w_ref = np.asarray(ref["coefs"]["fixed"])
    assert np.linalg.norm(w - w_ref) <= 1e-3 * np.linalg.norm(w_ref)
    assert int(np.asarray(tracker.cg_iterations)) == ref["cg_steps"]
    assert int(np.asarray(tracker.attempted_iterations)) == ref["attempted"]
    # the reference's CG stops lie far from their thresholds: a count that
    # a float32 rounding could move is no count to pin
    assert min(ref["cg_residual_margins"] + ref["cg_boundary_margins"]) > 0.05
    assert all(ref["accepted_steps"])


def test_the_jobs_counters_are_the_solvers_counts_and_the_work_model(
        sound, ref):
    from benchmark import work_model_tron

    c = sound["counters"]
    assert c["cg_steps"] == ref["cg_steps"] and c["tron_steps"] == 5
    assert c["fe_iterations_per_update"] == [5] and c["updates"] == 1
    assert c["passes"] == 2 + 3 * 5 + 2 * ref["cg_steps"] + 1
    assert c["passes"] - 1 == ref["passes"]
    assert c["hvp_passes"] == 2 * ref["cg_steps"]
    assert c["flops"] == work_model_tron.job_flops(ROWS, D, 1, 5,
                                                   ref["cg_steps"], 1)


def test_the_cells_check_passes_on_the_cells_own_limits(cell, problem, sound,
                                                        ref):
    from benchmark.checks import cd_fit_tron as check

    with jax.enable_x64(False):
        values = check.numbers(problem, cell[0], sound["window"], ref)
    limits = cell[1]["compare"]
    compared = {k: v for k, v in values.items()
                if not k.startswith(check.READ_ONLY)}
    assert set(compared) == set(limits)
    assert set(values) - set(compared) == {"coef_gap.fixed", "descent_gap"}
    assert all(compared[k] <= limits[k] for k in compared), values
    assert values["cg_steps"] == 0.0


# -- the counts against a hand count ------------------------------------------


def test_the_counts_on_a_two_dimensional_problem_with_one_rejected_step():
    """f(x) = sqrt(1 + x0^2) + sqrt(1 + x1^2) from (2, 1.5): away from 0
    the curvature falls, so Newton's quadratic model promises more than a
    long step gives, and one step is rejected. Counted by hand from the
    solver's own rules."""
    from photon_ml_tpu.optimization import minimize_tron

    def f(x):
        return jnp.sqrt(1 + x[0] ** 2) + jnp.sqrt(1 + x[1] ** 2)

    with jax.enable_x64(True):
        res = minimize_tron(f, jnp.asarray([2.0, 1.5]), max_iter=3, tol=1e-30)
        tallied = _hand_count(f, np.asarray([2.0, 1.5]), 3)
    assert int(res.cg_iterations) == tallied["cg"]
    assert int(res.attempted_iterations) == tallied["attempted"]
    assert int(res.iterations) == tallied["accepted"] == 3
    assert tallied["rejected"] == 1
    assert tallied["attempted"] == 4
    # both of the CG's exits ran: the trust region's boundary and the
    # residual test
    assert tallied["boundary"] >= 1 and tallied["residual"] >= 1
    # every CG step is one Hessian-vector product: a 2-D problem takes 1 or 2
    assert 4 <= tallied["cg"] <= 8


def _hand_count(f, x, cap):
    """The trust-region rules (LIBLINEAR's, as ``optimization/tron.py``
    states them) in float64 numpy, counting every CG step and outer step."""
    grad = jax.grad(f)
    hess = jax.hessian(f)
    g = np.asarray(grad(jnp.asarray(x)))
    fx = float(f(jnp.asarray(x)))
    delta = np.linalg.norm(g)
    cg = attempted = accepted = rejected = boundary = 0
    while accepted < cap:
        h = np.asarray(hess(jnp.asarray(x)))
        s, r, d = np.zeros(2), -g, -g
        rtr = r @ r
        tol = 0.1 * np.linalg.norm(g)
        while np.sqrt(rtr) > tol:
            cg += 1
            hd = h @ d
            alpha = rtr / (d @ hd)
            if np.linalg.norm(s + alpha * d) > delta:
                std, sts, dtd = s @ d, s @ s, d @ d
                rad = np.sqrt(std * std + dtd * (delta * delta - sts))
                alpha = ((delta * delta - sts) / (std + rad) if std >= 0
                         else (rad - std) / dtd)
                s, r = s + alpha * d, r - alpha * hd
                boundary += 1
                break
            s, r = s + alpha * d, r - alpha * hd
            rnew = r @ r
            d = r + (rnew / rtr) * d
            rtr = rnew
        attempted += 1
        f_new = float(f(jnp.asarray(x + s)))
        gs = g @ s
        prered = -0.5 * (gs - s @ r)
        actred = fx - f_new
        snorm = np.linalg.norm(s)
        if attempted == 1:
            delta = min(delta, snorm)
        denom = f_new - fx - gs
        alpha = 4.0 if denom <= 0 else max(0.25, -0.5 * gs / denom)
        if actred < 1e-4 * prered:
            delta = min(max(alpha, 0.25) * snorm, 0.5 * delta)
        elif actred < 0.25 * prered:
            delta = max(0.25 * delta, min(alpha * snorm, 0.5 * delta))
        elif actred < 0.75 * prered:
            delta = max(0.25 * delta, min(alpha * snorm, 4.0 * delta))
        else:
            delta = max(delta, min(alpha * snorm, 4.0 * delta))
        if actred > 1e-4 * prered:
            accepted += 1
            x, fx = x + s, f_new
            g = np.asarray(grad(jnp.asarray(x)))
        else:
            rejected += 1
    return {"cg": cg, "attempted": attempted, "accepted": accepted,
            "rejected": rejected, "boundary": boundary,
            "residual": attempted - boundary}


def test_the_other_solvers_leave_the_counts_empty():
    from photon_ml_tpu.optimization import minimize_lbfgs, minimize_owlqn

    def f(x):
        return jnp.sum((x - 1.0) ** 2)

    for res in (minimize_lbfgs(f, jnp.zeros(3)),
                minimize_owlqn(f, jnp.zeros(3), l1_weight=0.1)):
        assert res.cg_iterations is None and res.attempted_iterations is None
        assert res.cg_point is None and res.cg_step is None
        assert res.cg_residual is None and res.feature_passes is None


def test_the_last_cg_is_reported_where_it_ran():
    """The 2-D problem above: the last outer step's CG ran at the point
    before the last accepted step, returned that step, and carried the
    residual ``-g - H s`` there."""
    from photon_ml_tpu.optimization import minimize_tron

    def f(x):
        return jnp.sqrt(1 + x[0] ** 2) + jnp.sqrt(1 + x[1] ** 2)

    with jax.enable_x64(True):
        res = minimize_tron(f, jnp.asarray([2.0, 1.5]), max_iter=3, tol=1e-30)
        at, s = np.asarray(res.cg_point), np.asarray(res.cg_step)
        g = np.asarray(jax.grad(f)(jnp.asarray(at)))
        h = np.asarray(jax.hessian(f)(jnp.asarray(at)))
    np.testing.assert_allclose(at + s, np.asarray(res.x), rtol=1e-12)
    np.testing.assert_allclose(np.asarray(res.cg_residual), -g - h @ s,
                               atol=1e-12)


# -- names and counters -------------------------------------------------------


def test_the_hessian_vector_scope_is_in_the_tron_block_and_no_lbfgs_block(
        sound, problem):
    places = [scopes.place(p) for p in sound["table"].values()]
    hvp = [w for w in places if w["product"] == f"{scopes.FE_SOLVE}/"
           f"{scopes.FE_HVP}"]
    assert hvp and all(w["leaf"] == scopes.FE_SOLVE for w in hvp)
    # a child, never a leaf: the whole solve stays under photon.fe.solve
    assert scopes.FE_HVP not in scopes.LEAF_SCOPES
    assert not any(w["leaf"] == scopes.FE_HVP for w in places)
    config, workload = small_cell("5,1e-9,0.02,1.0,LBFGS,L2")
    _fit(config, workload, problem, kind="cd_fit")
    lbfgs = compile_cache.instruction_scopes()
    assert lbfgs and not any(scopes.FE_HVP in p for p in lbfgs.values())
    assert any(scopes.FE_SOLVE in p for p in lbfgs.values())


def test_place_keys_the_hessian_vector_product_under_its_solve():
    solve = f"jit(cd_block)/{scopes.cd_coordinate('fixed')}/{scopes.FE_SOLVE}"
    assert scopes.place(f"{solve}/while/body/{scopes.FE_HVP}/dot_general")[
        "product"] == f"{scopes.FE_SOLVE}/{scopes.FE_HVP}"
    # a sparse product inside it keeps its own key
    sparse = scopes.place(f"{solve}/while/{scopes.FE_HVP}/{scopes.FE_MATVEC}/"
                          f"{scopes.FE_MATVEC_CODED}/gather")
    assert sparse["product"] == f"{scopes.FE_SOLVE}/{scopes.FE_MATVEC}"
    assert sparse["part"] == sparse["product"] + "/" + scopes.FE_MATVEC_CODED
    assert sparse["leaf"] == scopes.FE_SOLVE
    # a sparse product outside the CG keeps its key
    assert scopes.place(f"{solve}/{scopes.FE_MATVEC}/gather")["product"] == (
        f"{scopes.FE_SOLVE}/{scopes.FE_MATVEC}")


def test_the_counters_under_telemetry(sound, ref):
    """Two runs: the warm-up job and the window's."""
    counts = sound["counts"]
    assert counts[scopes.COUNTER_FE_CG_STEPS] == 2 * ref["cg_steps"]
    assert counts[scopes.COUNTER_FE_TRON_STEPS] == 2 * ref["attempted"] == 10
    # the margins ride the loop: 2 + T + 2 K reads of X a solve
    assert counts[scopes.COUNTER_FE_PASSES] == 2 * (
        2 + ref["attempted"] + 2 * ref["cg_steps"])
    # a dense matrix: no sparse product is counted
    assert counts.get(scopes.COUNTER_FE_PRODUCTS, 0) == 0


def test_an_lbfgs_fit_counts_no_trust_region_work(problem):
    config, workload = small_cell("5,1e-9,0.02,1.0,LBFGS,L2")
    telemetry.enable()
    _fit(config, workload, problem, kind="cd_fit")
    counts = telemetry.snapshot()["counters"]
    assert counts.get(scopes.COUNTER_FE_CG_STEPS, 0) == 0
    assert counts.get(scopes.COUNTER_FE_TRON_STEPS, 0) == 0
    assert counts.get(scopes.COUNTER_FE_PASSES, 0) == 0


# -- a TRON solve over a sparse matrix: its products counted ------------------


def test_sparse_work_counts_a_tron_solves_products():
    from photon_ml_tpu.algorithm.coordinate_descent import CoordinateDescent
    from photon_ml_tpu.algorithm.coordinates import FixedEffectCoordinate
    from photon_ml_tpu.data.shard_cache import StreamedFixedEffectData
    from photon_ml_tpu.ops.features import sparse_rows_to_device
    from photon_ml_tpu.ops.glm_objective import GLMBatch
    from photon_ml_tpu.optimization.config import GLMOptimizationConfiguration
    from photon_ml_tpu.types import TaskType

    rng = np.random.default_rng(7)
    n, k, d = 600, 5, 40
    with jax.enable_x64(False):
        cols = jnp.asarray(rng.integers(0, d, (n, k)), jnp.int32)
        vals = jnp.asarray(rng.normal(size=(n, k)), jnp.float32)
        y = jnp.asarray(rng.random(n) < 0.4, jnp.float32)
        feats = sparse_rows_to_device(cols, vals, d)
        batch = GLMBatch(feats, y, jnp.zeros(n, jnp.float32),
                         jnp.ones(n, jnp.float32))
        coord = FixedEffectCoordinate(
            name="fixed", data=StreamedFixedEffectData("g", batch, n, d, {}),
            feature_shard_id="g", task_type=TaskType.LOGISTIC_REGRESSION,
            config=GLMOptimizationConfiguration.parse(
                "4,1e-12,1.0,1.0,TRON,L2"))
        telemetry.enable()
        result = CoordinateDescent({"fixed": coord},
                                   TaskType.LOGISTIC_REGRESSION).run(1, seed=1)
        counts = telemetry.snapshot()["counters"]
        (tracker,) = result.trackers["fixed"]
    cg = int(np.asarray(tracker.cg_iterations))
    attempted = int(np.asarray(tracker.attempted_iterations))
    assert attempted >= 4 and cg >= attempted
    layout, products = coord.sparse_work([tracker])
    assert layout is not None
    # the loop carries the margins: an outer step reads X once, for its
    # trial's gradient
    assert products == int(tracker.feature_passes) == 2 + attempted + 2 * cg
    assert counts[scopes.COUNTER_FE_PRODUCTS] == products
    assert counts[scopes.COUNTER_FE_CG_STEPS] == cg
    assert counts[scopes.COUNTER_FE_TRON_STEPS] == attempted
    assert counts[scopes.COUNTER_FE_PASSES] == products


# -- the margins the fused TRON carries ---------------------------------------

TASKS = ("LOGISTIC_REGRESSION", "POISSON_REGRESSION", "LINEAR_REGRESSION")
LAYOUTS = ("dense", "ell", "csr")


def _glm_problem(task, layout, n=300, k=4, d=12, seed=5):
    """A GLM over ``n`` rows of ``k`` non-zeros out of ``d`` columns, its
    matrix in ``layout``, float64: the objective and the batch."""
    import scipy.sparse as sp

    from photon_ml_tpu.ops.features import (
        DenseFeatures,
        csr_from_scipy,
        sparse_rows_to_device,
    )
    from photon_ml_tpu.ops.glm_objective import GLMObjective, make_batch
    from photon_ml_tpu.ops.losses import loss_for_task
    from photon_ml_tpu.types import TaskType

    rng = np.random.default_rng(seed)
    cols = rng.integers(0, d, (n, k))
    vals = rng.normal(0, 0.5, (n, k))
    dense = np.zeros((n, d))
    np.add.at(dense, (np.arange(n)[:, None], cols), vals)
    truth = rng.normal(0, 1, d)
    z = dense @ truth
    y = {"LOGISTIC_REGRESSION": (rng.random(n) < 1 / (1 + np.exp(-z))),
         "POISSON_REGRESSION": rng.poisson(np.exp(0.5 * z)),
         "LINEAR_REGRESSION": z + rng.normal(0, 0.3, n)}[task]
    features = {
        "dense": lambda: DenseFeatures(jnp.asarray(dense)),
        "ell": lambda: sparse_rows_to_device(
            jnp.asarray(cols, jnp.int32), jnp.asarray(vals), d),
        "csr": lambda: csr_from_scipy(sp.csr_matrix(dense),
                                      dtype=jnp.float64),
    }[layout]()
    batch = make_batch(features, jnp.asarray(y, jnp.float64),
                       jnp.asarray(rng.normal(0, 0.1, n)),
                       jnp.asarray(rng.uniform(0.5, 1.5, n)))
    return GLMObjective(loss_for_task(TaskType(task))), batch


def _tron_config(max_iter=6):
    from photon_ml_tpu.optimization.config import GLMOptimizationConfiguration

    return GLMOptimizationConfiguration.parse(
        f"{max_iter},1e-12,0.5,1.0,TRON,L2")


@pytest.mark.parametrize("layout", LAYOUTS)
@pytest.mark.parametrize("task", TASKS)
def test_the_carried_margins_solve_as_the_point_s_own(task, layout):
    """``solve_glm``'s TRON, which carries the margins, against the same
    solve given the product from the point alone (a margin pass and the
    trial's value and gradient an outer step): the same steps, CG steps
    and coefficients, and two passes over X an outer step fewer."""
    from photon_ml_tpu.optimization import minimize_tron
    from photon_ml_tpu.optimization.solver import solve_glm

    with jax.enable_x64(True):
        obj, batch = _glm_problem(task, layout)
        if layout == "ell":
            assert type(batch.features).__name__ == "SlotMajorEllFeatures"
        x0 = jnp.zeros(batch.features.shape[1])
        carried = solve_glm(obj, batch, _tron_config(), x0)
        own = minimize_tron(obj.value, x0, args=(batch, 0.5), max_iter=6,
                            tol=1e-12, make_hvp=obj.make_tron_hvp)
    for field in ("iterations", "attempted_iterations", "cg_iterations",
                  "reason"):
        assert int(getattr(carried, field)) == int(getattr(own, field))
    t, k = int(own.attempted_iterations), int(own.cg_iterations)
    assert t >= 3 and k > t
    np.testing.assert_allclose(carried.x, own.x, rtol=1e-6,
                               atol=1e-6 * float(jnp.linalg.norm(own.x)))
    assert float(carried.value) == pytest.approx(float(own.value), rel=1e-9)
    assert int(carried.feature_passes) == 2 + t + 2 * k
    assert int(own.feature_passes) == 2 + 3 * t + 2 * k


def test_vmapped_carried_solves_are_each_solve_alone():
    """A random effect's bucket: ``solve_glm``'s TRON vmapped over three
    entities, each lane's margins carried, against each entity solved
    alone, with the carried margins and with the point's product."""
    from photon_ml_tpu.optimization import minimize_tron
    from photon_ml_tpu.optimization.solver import solve_glm

    with jax.enable_x64(True):
        problems = [_glm_problem("LOGISTIC_REGRESSION", "dense", n=120,
                                 seed=seed) for seed in (1, 2, 3)]
        obj = problems[0][0]
        x0 = jnp.zeros(12)
        stacked = jax.tree.map(lambda *a: jnp.stack(a),
                               *[b for _, b in problems])
        lanes = jax.vmap(lambda b: solve_glm(obj, b, _tron_config(), x0))(
            stacked)
        for e, (_, batch) in enumerate(problems):
            alone = solve_glm(obj, batch, _tron_config(), x0)
            own = minimize_tron(obj.value, x0, args=(batch, 0.5), max_iter=6,
                                tol=1e-12, make_hvp=obj.make_tron_hvp)
            for field in ("iterations", "attempted_iterations",
                          "cg_iterations", "feature_passes"):
                assert int(getattr(lanes, field)[e]) == int(
                    getattr(alone, field))
            assert int(alone.cg_iterations) == int(own.cg_iterations)
            np.testing.assert_allclose(lanes.x[e], alone.x, rtol=1e-9,
                                       atol=1e-12)
            np.testing.assert_allclose(alone.x, own.x, rtol=1e-6, atol=1e-6)


class _Recording:
    """``GLMObjective.margins_value_and_grad`` that hands every point and
    the margins the solve used there to the host (``seen``)."""

    def __init__(self, objective):
        self.objective = objective
        self.seen = []

    def __call__(self, x, batch, l2, z=None):
        out = self.objective.margins_value_and_grad(x, batch, l2, z=z)
        jax.debug.callback(lambda a, b: self.seen.append(
            (np.asarray(a), np.asarray(b))), x, out[0])
        return out


def test_the_carried_margins_stay_the_points_after_five_outer_steps(
        cell, problem):
    """The cell's problem at this file's size, float32: the margins a
    trial is valued at are the last point's plus ``X s`` from the CG's
    products; after five accepted outer steps of twenty CG steps each they
    stay within float32 rounding of a fresh ``X x`` at their point."""
    from benchmark.reference import tron_glm
    from photon_ml_tpu.ops.features import DenseFeatures
    from photon_ml_tpu.ops.glm_objective import GLMBatch, GLMObjective
    from photon_ml_tpu.ops.losses import loss_for_task
    from photon_ml_tpu.optimization import minimize_tron
    from photon_ml_tpu.types import TaskType

    l2 = tron_glm.optimizer_of(cell[0]["fixed"]["optimizer"])["l2"]
    with jax.enable_x64(False):
        obj = GLMObjective(loss_for_task(TaskType.LOGISTIC_REGRESSION))
        recording = _Recording(obj)
        batch = GLMBatch(DenseFeatures(problem.x), problem.labels,
                         problem.offsets, problem.weights)
        res = minimize_tron(
            obj.value, jnp.zeros(D, jnp.float32), args=(batch, l2),
            max_iter=5, tol=1e-9, make_hvp=obj.make_tron_hvp_at_margins,
            margins_value_and_grad=recording)
        jax.effects_barrier()
        at, z = next((a, b) for a, b in reversed(recording.seen)
                     if np.array_equal(a, np.asarray(res.x)))
        fresh = np.asarray(obj.margins(jnp.asarray(at), batch))
    assert int(res.iterations) == int(res.attempted_iterations) == 5
    assert int(res.cg_iterations) == 100  # every CG at its cap
    assert len(recording.seen) == 6  # the first point and five trials
    drift = np.linalg.norm(z - fresh) / np.linalg.norm(fresh)
    assert drift < 2e-6, drift  # 2.5e-7 read here


_COUNTED = collections.Counter()


def _tick(kind):
    jax.debug.callback(lambda: _COUNTED.update([kind]))


@jax.custom_vjp
def _counted_matvec(inner, v):
    _tick("matvec")
    return inner.matvec(v)


def _counted_matvec_fwd(inner, v):
    return _counted_matvec(inner, v), inner


def _counted_matvec_bwd(inner, u):
    _tick("rmatvec")
    return jax.tree.map(jnp.zeros_like, inner), inner.rmatvec(u)


_counted_matvec.defvjp(_counted_matvec_fwd, _counted_matvec_bwd)


@jax.tree_util.register_pytree_node_class
class CountingFeatures:
    """A feature matrix that counts, as they run, its matvecs and rmatvecs,
    those that differentiation makes of a matvec among them."""

    def __init__(self, inner):
        self.inner = inner

    @property
    def shape(self):
        return self.inner.shape

    def matvec(self, v):
        return _counted_matvec(self.inner, v)

    def rmatvec(self, u):
        _tick("rmatvec")
        return self.inner.rmatvec(u)

    def tree_flatten(self):
        return (self.inner,), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)


@pytest.mark.parametrize("path, per_step", [
    ("carried", 1), ("point", 3), ("bounded", 5)])
def test_feature_passes_are_the_passes_the_solve_ran(path, per_step):
    """``OptimizerResult.feature_passes`` against the matvecs and rmatvecs
    counted as they ran: ``2 + T + 2 K`` where the loop carries the margins
    (``solve_glm`` without bounds), ``2 + 3 T + 2 K`` with the point's
    product alone, ``2 + 5 T + 2 K`` under bounds (``solve_glm`` with
    them: the realized step's product too)."""
    from photon_ml_tpu.ops.glm_objective import GLMBatch
    from photon_ml_tpu.optimization import minimize_tron
    from photon_ml_tpu.optimization.solver import solve_glm

    with jax.enable_x64(True):
        obj, plain = _glm_problem("LOGISTIC_REGRESSION", "dense")
        batch = GLMBatch(CountingFeatures(plain.features), plain.labels,
                         plain.offsets, plain.weights)
        d = plain.features.shape[1]
        x0 = jnp.zeros(d)
        _COUNTED.clear()
        if path == "point":
            res = minimize_tron(obj.value, x0, args=(batch, 0.5), max_iter=6,
                                tol=1e-12, make_hvp=obj.make_tron_hvp)
        else:
            bounds = ((jnp.full(d, -0.8), jnp.full(d, 0.8))
                      if path == "bounded" else (None, None))
            res = solve_glm(obj, batch, _tron_config(), x0, *bounds)
        jax.effects_barrier()
    t, k = int(res.attempted_iterations), int(res.cg_iterations)
    assert t >= 3 and k >= t
    assert _COUNTED["matvec"] + _COUNTED["rmatvec"] == int(
        res.feature_passes) == 2 + per_step * t + 2 * k
    # one rmatvec a gradient and a product: the first, a trial's, a CG
    # step's and under bounds the realized step's
    assert _COUNTED["rmatvec"] == 1 + t + k + (t if path == "bounded" else 0)


def test_the_jvp_of_grad_product_counts_no_passes():
    from photon_ml_tpu.optimization import minimize_tron

    with jax.enable_x64(True):
        obj, batch = _glm_problem("LOGISTIC_REGRESSION", "dense")
        res = minimize_tron(obj.value, jnp.zeros(12), args=(batch, 0.5),
                            max_iter=3)
    assert res.feature_passes is None and int(res.cg_iterations) > 0


def test_carried_margins_refuse_bounds_and_a_missing_product():
    from photon_ml_tpu.optimization import minimize_tron

    with jax.enable_x64(True):
        obj, batch = _glm_problem("LOGISTIC_REGRESSION", "dense")
        for kw in ({"lower_bounds": jnp.full(12, -1.0),
                    "make_hvp": obj.make_tron_hvp_at_margins}, {}):
            with pytest.raises(ValueError, match="carried margins"):
                minimize_tron(
                    obj.value, jnp.zeros(12), args=(batch, 0.5),
                    margins_value_and_grad=obj.margins_value_and_grad, **kw)


# Bitwise what the trust-region body gave before the loop could carry the
# margins (float64 on the CPU), where it does not: under bounds, with the
# point's product alone and for an objective that is no GLM.
UNCARRIED = {
    "bounded": {
        "x": [
            "0x1.999999999999ap-1",
            "-0x1.999999999999ap-1",
            "-0x1.999999999999ap-1",
            "-0x1.65f278f212264p-3",
            "0x1.999999999999ap-1",
            "0x1.999999999999ap-1",
            "-0x1.999999999999ap-1",
            "0x1.999999999999ap-1",
            "0x1.3ca8ea6014923p-1",
            "-0x1.523c217596a08p-2",
            "0x1.999999999999ap-1",
            "0x1.999999999999ap-1"],
        "value": "0x1.5797d7b660c3dp+7",
        "counts": [6, 6, 7]},
    "point": {
        "x": [
            "0x1.54a9c04c0fe94p+0",
            "-0x1.366eee35e8767p+0",
            "-0x1.0592949b1c25bp+1",
            "-0x1.16d47687e1a5ep-2",
            "0x1.2f74ee94ed5e0p+0",
            "0x1.9067fa4eac54bp-1",
            "-0x1.dddabf477f9dep+0",
            "0x1.0013c33317bc3p+1",
            "0x1.5181259abfb74p-1",
            "-0x1.ecfee5f22b5eap-2",
            "0x1.0c9f1861e1187p+0",
            "0x1.d900eb685a079p+0"],
        "value": "0x1.430fd065b8a7cp+7",
        "counts": [6, 6, 12]},
    "not_glm": {
        "x": [
            "-0x1.cdeb083def580p-11",
            "0x1.1c1d522a3b900p-10"],
        "value": "0x1.0000082ebc774p+1",
        "counts": [3, 4, 5]},
}


def _uncarried(path):
    from photon_ml_tpu.optimization import minimize_tron
    from photon_ml_tpu.optimization.solver import solve_glm

    with jax.enable_x64(True):
        if path == "not_glm":
            res = minimize_tron(
                lambda x: jnp.sqrt(1 + x[0] ** 2) + jnp.sqrt(1 + x[1] ** 2),
                jnp.asarray([2.0, 1.5]), max_iter=3, tol=1e-30)
        else:
            obj, batch = _glm_problem("LOGISTIC_REGRESSION", "dense")
            x0 = jnp.zeros(12)
            if path == "bounded":
                res = solve_glm(obj, batch, _tron_config(), x0,
                                jnp.full(12, -0.8), jnp.full(12, 0.8))
            else:
                res = minimize_tron(obj.value, x0, args=(batch, 0.5),
                                    max_iter=6, tol=1e-12,
                                    make_hvp=obj.make_tron_hvp)
    return {"x": [float(v).hex() for v in np.asarray(res.x)],
            "value": float(res.value).hex(),
            "counts": [int(res.iterations), int(res.attempted_iterations),
                       int(res.cg_iterations)]}


@pytest.mark.parametrize("path", sorted(UNCARRIED))
def test_the_uncarried_paths_are_bitwise_as_they_were(path):
    assert _uncarried(path) == UNCARRIED[path]


# -- the configuration --------------------------------------------------------


def test_the_configuration_is_the_sources_shape_with_nothing_cut():
    config = json.loads(
        (ROOT / "benchmark" / "configs" / "tron-lr-epsilon.json").read_text())
    assert config["published"]["n_rows"] == config["n_rows"] == 400000
    assert config["published"]["n_features"] == config["fixed"]["d"] == 2000
    assert config["reduced"] == [] and config["architecture"] is None
    parts = config["fixed"]["optimizer"].split(",")
    assert parts[4:] == ["TRON", "L2"] and float(parts[2]) == 1.0
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    entry = next(c for c in bench["configs"] if c["name"] == config["name"])
    assert entry["source"] == config["source"] and len(entry["source"]) <= 200
    assert entry["reduced"] == []
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert cell["chips"] == 1 and cell["config"] == config["name"]
