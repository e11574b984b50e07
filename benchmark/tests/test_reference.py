"""The plain reference agrees with the program's fit, the lower-precision
control (the program's own bfloat16 storage of X) does not, and a run
with the timed path broken underneath comes out not correct."""

import pytest

from benchmark import faults, harness
from benchmark.checks import cd_fit as check
from benchmark.jobs import cd_fit
from benchmark.recipes import dense_glm
from benchmark.tests.common import CELL, TINY_ROWS, tiny_config, workload


def _compared(seed: int, storage: str):
    config = tiny_config()
    problem = dense_glm.make(config, seed)
    job = cd_fit.build(config, workload(), problem, storage=storage)
    job.warm_up(seed)
    window = job.window(0.0, seed)  # one job
    job.after_window(window)
    return check.check(problem, config, workload(), window)


def test_reference_agrees_with_the_program():
    for name, v in _compared(11, "float32").items():
        assert v["value"] <= v["limit"], (name, v)


@pytest.mark.parametrize("seed", [21, 22, 2 ** 31 + 23])
def test_bf16_storage_control_is_not_correct(seed):
    compared = _compared(seed, "bfloat16")
    failed = [n for n, v in compared.items() if not v["value"] <= v["limit"]]
    assert "score_self_gap" in failed, compared


# -- the rest of a run, with the timed path broken underneath -----------------


def _run():
    return harness.run_cell(CELL, seed=31, seconds=0.5, trace=False, t0=0.0,
                            require_chip=False, rehearse_rows=TINY_ROWS)


def test_a_sound_run_is_correct_and_prints_no_metric_on_the_cpu():
    result = _run()
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] >= 1
    assert result["device"]["platform"] == "cpu"
    assert result["metrics"] == {}
    assert list(result)[-1] == "compared"


@pytest.mark.parametrize("fault,number", [
    ("half_batch", "coef_gap.fixed"), ("state_unchanged", "obj_gap"),
    ("exchange_left_out", "obj_gap"),
    ("coefficient_altered", "coef_gap.fixed"),
    ("entity_altered", "coef_worst.perUser"),
    ("score_altered", "score_self_gap")])
def test_a_broken_timed_path_is_not_correct(fault, number):
    with faults.FAULTS[fault]():
        result = _run()
    assert result["correct"] is False
    assert result["failed"] == result["attempted"]
    v = result["compared"][number]
    assert v["value"] > v["limit"], (number, v)


def test_no_chip_is_an_error_not_a_cpu_run():
    with pytest.raises(harness.NoChip):
        harness.run_cell(CELL, seed=1, seconds=0.1, trace=False, t0=0.0)


def test_the_reference_blocks_rows_without_counting_any_twice(monkeypatch):
    import jax.numpy as jnp
    import numpy as np

    from benchmark.reference import glm_cd

    monkeypatch.setattr(glm_cd, "BLOCK_ROWS", 1000)  # 2500 rows: 3 blocks
    rng = np.random.default_rng(5)
    x = rng.normal(size=(2500, 7)).astype(np.float32)
    y = (rng.random(2500) < 0.5).astype(np.float32)
    w = rng.random(2500).astype(np.float32)
    off = rng.normal(size=2500).astype(np.float32)
    c = rng.normal(size=7).astype(np.float32)
    np.testing.assert_allclose(glm_cd.matvec(jnp.asarray(x), jnp.asarray(c)),
                               x @ c, rtol=1e-5, atol=1e-5)
    z, g, p = glm_cd._fe_newton_system(
        *(jnp.asarray(a) for a in (x, y, w, off, c)), 1.0, "logistic")
    x64 = x.astype(np.float64)
    z64 = x64 @ c + off
    s = 1 / (1 + np.exp(-z64))
    g64 = x64.T @ (w * (s - y)) + c
    h64 = (x64 * (w * s * (1 - s))[:, None]).T @ x64 + np.eye(7)
    np.testing.assert_allclose(z, z64, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(g, g64, rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(p, np.linalg.solve(h64, g64), rtol=1e-3,
                               atol=1e-5)


def test_a_fixed_only_poisson_tron_configuration_runs_as_data_alone():
    """The next cell of ``PERF.md``'s list (Poisson, TRON, no random
    effect) needs no code: the recipe, the job kind, the reference and the
    check take it from its configuration."""
    from benchmark import work_model

    config = tiny_config()
    config.update(task="POISSON_REGRESSION", link="poisson", random=[],
                  updating_sequence=["fixed"], iterations=1, n_rows=4000)
    config["fixed"].update(intercept="none", x_sd=0.3, w_sd=0.2,
                           optimizer="15,1e-5,1.0,1.0,TRON,L2")
    problem = dense_glm.make(config, 41)
    assert float(problem.labels.max()) > 1 and not problem.buckets
    job = cd_fit.build(config, {}, problem)
    job.warm_up(41)
    window = job.window(0.0, 41)
    job.after_window(window)
    got = check.numbers(problem, config, window)
    assert set(got) == {"obj_gap", "coef_gap.fixed", "score_self_gap"}
    assert got["obj_gap"] < 1e-5 and got["coef_gap.fixed"] < 1e-2
    assert got["score_self_gap"] < 1e-5
    assert work_model.uses_tron(config["fixed"])
