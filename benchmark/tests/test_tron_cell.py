"""The cell ``tron-lr.fit`` at a small size on the CPU: its files load
through the harness, a rehearsed run is correct, and the control and every
planted fault fail the number named for each on the cell's own limits; the
new readers read nothing where nothing was measured, and the right thing on
made-up readings."""

import contextlib
import json
from pathlib import Path

import jax
import pytest

from benchmark import faults_tron, harness, work_model_tron
from benchmark.checks import cd_fit_tron as check
from benchmark.jobs import cd_fit_tron
from benchmark.recipes import dense_tron as recipe
from benchmark.reference import tron_glm as reference

HERE = Path(__file__).resolve().parents[1]
CELL, CONFIG = "tron-lr.fit", "tron-lr-epsilon"
# the cell's width on fewer rows, the L2 weight scaled with them: every CG
# still stops at its cap of 20, as at the cell's size
TINY_ROWS = 20000
NEW = {"fe_hvp_job_ms", "fe_hvp_roofline", "tron_fit_mfu", "fe_cg_steps"}


def full_config() -> dict:
    return json.loads((HERE / "configs" / f"{CONFIG}.json").read_text())


def workload() -> dict:
    return json.loads((HERE / "workloads" / f"{CELL}.json").read_text())


@pytest.fixture(scope="module")
def config():
    return recipe.scale_down(full_config(), TINY_ROWS)


@pytest.fixture(scope="module")
def problem(config):
    return recipe.make(config, 2 ** 31 + 47)


@pytest.fixture(scope="module")
def ref(config, problem):
    return reference.fit(problem, config)


def test_the_cells_files_load_through_the_harness():
    loaded = harness.load_cell(CELL)
    assert loaded["cell"]["chips"] == 1
    assert loaded["workload"]["job"] == "cd_fit_tron"
    per_layer = {m["name"] for m in harness.metrics_for(
        loaded["bench"], CELL, "per_layer")}
    assert NEW | {"fe_solve_job_ms", "fe_score_ms", "unscoped_ms",
                  "fe_solve_ms", "device_idle", "hbm_peak_gib"} == per_layer
    for other in ("glmix.fit", "sparse-lr.fit", "game-mf.fit"):
        assert not NEW & {m["name"] for m in harness.metrics_for(
            loaded["bench"], other, "per_layer")}


def test_every_seed_deals_the_same_data_set(config):
    """Two seeds: the same rows, columns and labels, in another order and
    with other signs."""
    import numpy as np

    small = recipe.scale_down(config, 2000)
    dealt = []
    for seed in (3, 2 ** 31 + 5):
        p = recipe.make(small, seed)
        rows, cols, signs = recipe.deal(small, seed)
        x = np.empty_like(np.asarray(p.x))
        x[rows] = np.asarray(p.x)
        base = np.empty_like(x)
        base[:, cols] = x * signs[None, :]
        y = np.empty(len(rows), np.float32)
        y[rows] = np.asarray(p.labels)
        dealt.append((base, y, np.asarray(p.x)))
    np.testing.assert_array_equal(dealt[0][0], dealt[1][0])
    np.testing.assert_array_equal(dealt[0][1], dealt[1][1])
    assert not np.array_equal(dealt[0][2], dealt[1][2])
    np.testing.assert_allclose(np.linalg.norm(dealt[0][0], axis=1), 1.0,
                               rtol=1e-5)
    assert 0.45 < dealt[0][1].mean() < 0.55


def _compared(config, problem, ref, storage="float32", fault=None):
    planted = (faults_tron.FAULTS[fault](problem) if fault
               else contextlib.nullcontext())
    with planted:
        job = cd_fit_tron.build(config, workload(), problem, storage=storage)
        job.warm_up(1)
        window = job.window(0.0, 1)  # one job
        job.after_window(window)
    counters = job.counters(window)
    jax.clear_caches()  # the next variant traces its own programs
    limits = workload()["compare"]
    values = {k: v for k, v in check.numbers(
        problem, config, window, ref).items()
        if not k.startswith(check.READ_ONLY)}
    assert set(values) == set(limits)
    failed = {n for n, v in values.items() if not v <= limits[n]}
    return values, failed, counters


def test_the_program_agrees_with_the_reference(config, problem, ref):
    values, failed, counters = _compared(config, problem, ref)
    assert not failed, values
    assert ref["cg_per_step"] == [20] * 5 and all(ref["accepted_steps"])
    assert counters["cg_steps"] == 100 and counters["tron_steps"] == 5
    assert counters["passes"] == work_model_tron.passes(1, 5, 100, 1) == 218


def test_the_bfloat16_control_fails_score_self_gap(config, problem, ref):
    values, failed, _ = _compared(config, problem, ref, storage="bfloat16")
    assert "score_self_gap" in failed, values


@pytest.mark.parametrize("fault, number", [
    ("hvp_half_batch", "hvp_self_gap"), ("hvp_without_l2", "hvp_self_gap"),
    ("cg_step_short", "cg_steps"), ("score_altered", "score_self_gap")])
def test_a_planted_fault_fails_its_number(config, problem, ref, fault, number):
    values, failed, _ = _compared(config, problem, ref, fault=fault)
    assert number in failed, values


@pytest.mark.parametrize("fault", ["hvp_half_batch", "hvp_without_l2"])
def test_an_hvp_fault_breaks_the_solvers_product_alone(config, problem,
                                                       fault):
    """The product ``make_tron_hvp`` gives any other caller is the sound
    one under the fault: only the solve's own product is wrong, and the
    check reads that one (``hvp_self_gap``)."""
    from photon_ml_tpu.ops.features import DenseFeatures
    from photon_ml_tpu.ops.glm_objective import GLMBatch, GLMObjective
    from photon_ml_tpu.ops.losses import loss_for_task
    from photon_ml_tpu.types import TaskType

    objective = GLMObjective(loss_for_task(TaskType.LOGISTIC_REGRESSION))
    batch = GLMBatch(DenseFeatures(problem.x), problem.labels,
                     problem.offsets, problem.weights)
    w = jax.numpy.full((problem.x.shape[1],), 0.01, jax.numpy.float32)
    sound = objective.make_tron_hvp(w, batch, 1.0)(w)
    with faults_tron.FAULTS[fault](problem):
        again = objective.make_tron_hvp(w, batch, 1.0)(w)
    assert bool((sound == again).all())


def test_a_rehearsed_run_of_the_cell_is_correct():
    result = harness.run_cell(CELL, seed=2 ** 31 + 9, seconds=0.5, trace=True,
                              t0=0.0, require_chip=False,
                              rehearse_rows=TINY_ROWS)
    assert result["correct"] is True and result["failed"] == 0
    assert result["metrics"] == {}  # no device metric from a CPU run
    notes = result["notes"]
    assert notes["rehearsal"] and notes["n_rows"] == TINY_ROWS
    assert notes["counters"]["cg_steps"] == 100
    assert set(notes["probes"]) == {"fe_solve"}
    assert result["compared"]["window_compiles"]["value"] == 0


# -- the readers, on made-up readings -----------------------------------------


def _ctx(**kw):
    ctx = {"config": full_config(), "window": {"seconds": 10.0,
                                               "attempted": 10},
           "device": {"kind": "TPU v5 lite"},
           "peaks": json.loads((HERE / "peaks.json").read_text()),
           "counters": None, "probes": None, "trace": None}
    ctx.update(kw)
    return ctx


def test_the_new_readers_read_nothing_where_nothing_was_measured():
    import importlib

    for name in sorted(NEW):
        reader = importlib.import_module(f"benchmark.metrics.{name}")
        assert reader.read(_ctx()) is None, name
        assert reader.read(_ctx(counters={}, trace={"op_seconds": {},
                                                    "traced_jobs": 3},
                                instruction_scopes={})) is None, name


def test_the_new_readers_on_made_up_readings():
    from benchmark.metrics import (
        fe_cg_steps,
        fe_hvp_job_ms,
        fe_hvp_roofline,
        tron_fit_mfu,
    )
    from photon_ml_tpu.telemetry import scopes

    solve = f"jit(cd_block)/photon.cd.fixed/{scopes.FE_SOLVE}"
    table = {"fusion.1": f"{solve}/while/body/{scopes.FE_HVP}/dot_general",
             "fusion.2": f"{solve}/while/body/{scopes.FE_HVP}/mul",
             "fusion.3": f"{solve}/while/body/dot_general",
             "fusion.4": f"jit(cd_block)/{scopes.FE_SCORE}/dot_general"}
    trace = {"traced_jobs": 2, "op_seconds": {
        "%fusion.1": 1.2, "fusion.2": 0.4, "fusion.3": 0.2, "fusion.4": 0.01}}
    n, d = 400000, 2000
    counters = {"cg_steps": 100.0, "flops": work_model_tron.job_flops(
        n, d, 1, 5, 100, 1)}
    ctx = _ctx(counters=counters, trace=trace, instruction_scopes=table,
               instruction_opcodes={k: "fusion" for k in table})
    assert fe_hvp_job_ms.read(ctx) == pytest.approx(800.0)
    least = 100 * 2 * n * d * 4 / 819e9
    assert fe_hvp_roofline.read(ctx) == pytest.approx(100 * least / 0.8)
    assert tron_fit_mfu.read(ctx) == pytest.approx(
        100 * counters["flops"] / 1.0 / 197e12)
    assert fe_cg_steps.read(ctx) == 100.0
    assert work_model_tron.job_flops(n, d, 1, 5, 100, 1) == pytest.approx(
        4 * n * d * 6 + 2 * n * d * 6 + 4 * n * d * 100)
