"""The cell ``sparse-lr.fit`` at a tiny size on the CPU: its configuration
against the source's counts, what every seed shares, the plain reference
against a dense float64 computation, the program against the reference,
and the control and every planted fault failing the number named for it
on the cell's own limits."""

import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import faults_sparse, harness, work_model_sparse
from benchmark.checks import cd_fit_sparse as check
from benchmark.jobs import cd_fit_sparse
from benchmark.recipes import sparse_glm as recipe
from benchmark.reference import sparse_glm as reference

HERE = Path(__file__).resolve().parents[1]
CELL, CONFIG = "sparse-lr.fit", "sparse-lr-criteo"
TINY_ROWS = 30000


def full_config() -> dict:
    return json.loads((HERE / "configs" / f"{CONFIG}.json").read_text())


def workload() -> dict:
    return json.loads((HERE / "workloads" / f"{CELL}.json").read_text())


@pytest.fixture(scope="module")
def config():
    return recipe.scale_down(full_config(), TINY_ROWS)


@pytest.fixture(scope="module")
def problem(config):
    return recipe.make(config, 2 ** 31 + 41)


# -- the configuration ---------------------------------------------------------


def test_the_configuration_is_the_sources_shape_cut_by_rows_only():
    config = full_config()
    published, fixed = config["published"], config["fixed"]
    assert published == {"n_rows": 45840617, "n_features": 1000000,
                         "nnz_per_row": 39}
    assert config["reduced"] == ["n_rows", "iterations"]
    assert config["n_rows"] == published["n_rows"] // 5  # 20% of the rows
    # no width cut: every field, every hash slot, and the intercept
    assert len(fixed["fields"]) == published["nnz_per_row"]
    assert fixed["n_hash"] == published["n_features"]
    assert fixed["intercept"] == "last"
    assert recipe.shape_of(config) == (
        config["n_rows"], published["nnz_per_row"] + 1,
        published["n_features"] + 1)
    assert fixed["optimizer"] == "2,1e-12,1.0,1.0,LBFGS,L2"
    assert config["updating_sequence"] == ["fixed"]
    assert config["iterations"] == 1 and "random" not in config
    assert config["architecture"] is None
    for key in ("cut", "iterations", "counts", "intercept", "law", "truth",
                "optimizer", "guarantees"):
        assert config["assumed"][key]
    entry = {c["name"]: c for c in json.loads(
        (HERE.parent / "BENCHMARK.json").read_text())["configs"]}[CONFIG]
    assert entry["source"] == config["source"]
    assert len(entry["source"]) <= 200
    assert entry["reduced"] == config["reduced"]
    assert work_model_sparse.shape_of(config) == (
        config["n_rows"] * 40.0, config["n_rows"], 1000001)


def test_the_cells_files_load_through_the_harness():
    loaded = harness.load_cell(CELL)
    assert loaded["cell"]["chips"] == 1
    assert loaded["workload"]["job"] == "cd_fit_sparse"
    per_layer = {m["name"] for m in harness.metrics_for(
        loaded["bench"], CELL, "per_layer")}
    assert per_layer == {
        "fe_solve_ms", "device_idle", "hbm_peak_gib", "sparse_fit_mfu",
        "fe_matvec_ms", "fe_rmatvec_ms", "fe_matvec_roofline",
        "fe_rmatvec_roofline", "fe_slot_ratio"}
    # nothing the new cell adds is read in a cell that was there
    assert "fe_slot_ratio" not in {m["name"] for m in harness.metrics_for(
        loaded["bench"], "glmix.fit", "per_layer")}


# -- what every seed shares ----------------------------------------------------


def test_two_seeds_share_the_shapes_and_the_degree_summary(config, problem):
    other = recipe.make(config, 7)
    for p in (problem, other):
        assert p.cols.shape == p.vals.shape == (TINY_ROWS, 40)
        assert p.n_features == 1000001
        assert p.notes["max_col_degree"] == TINY_ROWS  # the intercept
        assert 0.2 < p.notes["positives_share"] < 0.32
    assert not np.array_equal(np.asarray(problem.cols),
                              np.asarray(other.cols))
    a, b = dict(problem.notes["hottest"]), dict(other.notes["hottest"])
    assert len(set(a) & set(b)) >= 9  # the same columns are the hot ones
    for col in set(a) & set(b):
        assert a[col] == pytest.approx(b[col], rel=0.05)
    assert problem.notes["share_degree_le_1"] == pytest.approx(
        other.notes["share_degree_le_1"], abs=0.005)
    # a row: 39 hashed columns at 1/sqrt(39), the intercept's at 1
    vals = np.asarray(problem.vals)
    np.testing.assert_allclose(vals[:, :39], 1 / np.sqrt(39), rtol=1e-6)
    assert (vals[:, 39] == 1.0).all()
    assert (np.asarray(problem.cols)[:, 39] == 1000000).all()
    assert np.asarray(problem.cols)[:, :39].max() < 1000000
    deg = np.bincount(np.asarray(problem.cols).ravel(), minlength=1000001)
    np.testing.assert_array_equal(deg, np.asarray(problem.col_degree))


def test_scale_down_keeps_the_law_and_deals_the_same_rows(config, problem):
    full = full_config()
    assert config["fixed"] == full["fixed"] and config["truth"] == full[
        "truth"]
    assert config["n_rows"] == TINY_ROWS
    fewer = recipe.make(recipe.scale_down(full, 1000), 2 ** 31 + 41)
    np.testing.assert_array_equal(np.asarray(fewer.cols),
                                  np.asarray(problem.cols)[:1000])
    # small fields are hot columns, large ones a long tail: field 21 has
    # 3 values (ranks 1 and 2 drawn), field 15 ten million
    cols = np.asarray(problem.cols)
    assert len(np.unique(cols[:, 21])) == 2
    assert len(np.unique(cols[:, 15])) > TINY_ROWS // 4


# -- the reference -------------------------------------------------------------


def test_reference_value_and_gradient_against_dense_float64():
    rng = np.random.default_rng(5)
    n, k, d = 300, 6, 50
    cols = rng.integers(0, d, (n, k)).astype(np.int32)
    vals = rng.normal(size=(n, k)).astype(np.float32)
    y = (rng.random(n) < 0.3).astype(np.float32)
    off = (0.2 * rng.normal(size=n)).astype(np.float32)
    wts = rng.uniform(0.5, 2.0, n).astype(np.float32)
    w = rng.normal(size=d).astype(np.float32)
    prob = recipe.SparseProblem(
        n, d, jnp.asarray(cols), jnp.asarray(vals), jnp.asarray(y),
        jnp.asarray(off), jnp.asarray(wts), jnp.zeros((d,), jnp.int32))
    config = {"link": "logistic",
              "fixed": {"name": "fixed",
                        "optimizer": "2,1e-12,0.7,1.0,LBFGS,L2"}}
    x = np.zeros((n, d))
    np.add.at(x, (np.repeat(np.arange(n), k), cols.ravel()),
              vals.ravel().astype(np.float64))
    z = x @ w.astype(np.float64) + off
    want = (wts * (np.logaddexp(0, z) - y * z)).sum() + 0.35 * (
        w.astype(np.float64) ** 2).sum()
    grad = x.T @ (wts * (1 / (1 + np.exp(-z)) - y)) + 0.7 * w
    value, got = reference.value_and_grad(prob, config, w)
    assert float(value) == pytest.approx(want, rel=1e-5)
    np.testing.assert_allclose(np.asarray(got), grad, rtol=2e-4, atol=2e-4)
    assert reference.value(prob, config, w) == pytest.approx(want, rel=1e-5)
    np.testing.assert_allclose(
        np.asarray(reference.scores_of(prob, config, {"fixed": w})),
        x @ w.astype(np.float64), rtol=1e-4, atol=1e-4)
    fit = reference.fit(prob, config)
    assert fit["stopped"] is None and len(fit["values"]) == 3
    assert fit["values"][2] < fit["values"][1] < fit["values"][0]


# -- the program against the reference, sound and broken ----------------------


@pytest.fixture(scope="module")
def ref(config, problem):
    return reference.fit(problem, config)


def _compared(config, problem, ref, storage="float32", fault=None):
    import contextlib

    planted = (faults_sparse.FAULTS[fault](problem) if fault
               else contextlib.nullcontext())
    with planted:
        job = cd_fit_sparse.build(config, workload(), problem,
                                  storage=storage)
        job.warm_up(1)
        window = job.window(0.0, 1)  # one job
        job.after_window(window)
    counters = job.counters(window)
    jax.clear_caches()  # the next variant traces its own programs
    limits = workload()["compare"]
    values = check.numbers(problem, config, window, ref)
    assert set(values) == set(limits)
    failed = {n for n, v in values.items() if not v <= limits[n]}
    return values, failed, counters


def test_the_program_agrees_with_the_reference(config, problem, ref):
    values, failed, counters = _compared(config, problem, ref)
    assert not failed, values
    assert counters["layout"] == "slot_major_ell"
    assert counters["products"] == 7 and counters["fe_iterations"] == 2
    assert counters["nnz"] == counters["slots"] == TINY_ROWS * 40
    assert counters["max_col_degree"] == TINY_ROWS


def test_the_float64_witness_lands_where_the_reference_lands(
        config, problem, ref):
    """``readings_sparse.py --witness``: the reference's method once more
    in float64 on the host, what a sound ``coef_gap`` is measured from."""
    from benchmark import readings_sparse

    w64 = readings_sparse.witness_fit(problem, config)
    w_ref = np.asarray(ref["coefs"]["fixed"], np.float64)
    gaps = readings_sparse.witness_line(problem, w_ref, w_ref, w64)
    assert gaps["reference_vs_witness"] < 1e-4
    assert gaps["program_vs_reference"] == 0.0


def test_the_bfloat16_control_fails_score_self_gap(config, problem, ref):
    values, failed, _ = _compared(config, problem, ref, storage="bfloat16")
    assert "score_self_gap" in failed, values


@pytest.mark.parametrize("fault, number", [
    ("half_batch", "coef_gap.fixed"),
    ("hot_column_dropped", "coef_gap.fixed"),
    ("tail_dropped", "coef_gap.tail"),
    ("score_altered", "score_self_gap")])
def test_a_planted_fault_fails_its_number(config, problem, ref, fault, number):
    values, failed, _ = _compared(config, problem, ref, fault=fault)
    assert number in failed, values


def test_a_solve_that_stops_an_iteration_early_fails_descent_gap(
        config, problem, ref):
    """No planted fault: the program under a cap of 1 held to the
    reference under the cell's cap of 2."""
    early = json.loads(json.dumps(config))
    early["fixed"]["optimizer"] = "1,1e-12,1.0,1.0,LBFGS,L2"
    job = cd_fit_sparse.build(early, workload(), problem)
    job.warm_up(1)
    window = job.window(0.0, 1)
    job.after_window(window)
    values = check.numbers(problem, config, window, ref)
    limits = workload()["compare"]
    assert values["descent_gap"] > limits["descent_gap"], values
    assert values["coef_gap.fixed"] > limits["coef_gap.fixed"], values
    assert values["obj_self_gap"] <= limits["obj_self_gap"], values


def test_a_rehearsed_run_of_the_cell_is_correct():
    result = harness.run_cell(CELL, seed=2 ** 31 + 9, seconds=0.5, trace=True,
                              t0=0.0, require_chip=False,
                              rehearse_rows=TINY_ROWS)
    assert result["correct"] is True and result["failed"] == 0
    assert result["metrics"] == {}  # no device metric from a CPU run
    notes = result["notes"]
    assert notes["rehearsal"] and notes["n_rows"] == TINY_ROWS
    assert notes["routing"]["layout"] == "slot_major_ell"
    assert notes["routing"]["degrees"]["max_col_degree"] == TINY_ROWS
    assert notes["counters"]["products"] == 7
    assert set(notes["probes"]) == {"fe_solve", "fe_matvec", "fe_rmatvec"}
    assert result["compared"]["window_compiles"]["value"] == 0


# -- the readers, on made-up readings ------------------------------------------


def _ctx(**kw):
    ctx = {"config": full_config(), "window": {"seconds": 17.5,
                                               "attempted": 1},
           "device": {"kind": "TPU v5 lite"},
           "peaks": json.loads((HERE / "peaks.json").read_text()),
           "counters": None, "probes": None, "trace": None}
    ctx.update(kw)
    return ctx


def test_the_new_readers_read_nothing_where_nothing_was_measured():
    import importlib

    for name in ("sparse_fit_mfu", "fe_matvec_ms", "fe_rmatvec_ms",
                 "fe_matvec_roofline", "fe_rmatvec_roofline",
                 "fe_slot_ratio"):
        reader = importlib.import_module(f"benchmark.metrics.{name}")
        assert reader.read(_ctx()) is None, name
        assert reader.read(_ctx(counters={}, trace={"probe_busy_s": {}},
                                probes={})) is None, name


def test_the_new_readers_on_made_up_readings():
    from benchmark.metrics import (
        fe_matvec_ms,
        fe_matvec_roofline,
        fe_rmatvec_roofline,
        fe_slot_ratio,
        sparse_fit_mfu,
    )

    nnz, n, d = 366724920.0, 9168123, 1000001
    ctx = _ctx(counters={"products": 7.0, "nnz": nnz, "slots": 1.25 * nnz},
               trace={"probe_busy_s": {"fe_matvec": [2.0, 3.0],
                                       "fe_rmatvec": [2.5]}})
    assert fe_matvec_ms.read(ctx) == pytest.approx(2500.0)
    least = (8 * nnz + 4 * (n + d)) / 819e9
    assert fe_matvec_roofline.read(ctx) == pytest.approx(
        100 * least / 2.5)
    assert fe_rmatvec_roofline.read(ctx) == pytest.approx(
        100 * least / 2.5)
    assert fe_slot_ratio.read(ctx) == pytest.approx(1.25)
    assert sparse_fit_mfu.read(ctx) == pytest.approx(
        100 * 7 * 2 * nnz / 17.5 / 197e12)
