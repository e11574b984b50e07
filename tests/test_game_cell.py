"""GAME with a matrix-factorization term (PR 38): a fixed effect, a per-user
random effect and ONE factored coordinate over a per-movie group through
``CoordinateDescent.run``, held on the CPU at a tiny size through the cell
``game-mf.fit``'s own files: the recipe, the program against the plain
reference on the cell's limits, the control and every planted fault failing
the number named for it, the limits against the readings written beside
them, what the factored coordinate reports from inside."""

import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from photon_ml_tpu import telemetry
from photon_ml_tpu.algorithm import coordinates as co
from photon_ml_tpu.telemetry import scopes
from photon_ml_tpu.utils import compile_cache

ROOT = Path(__file__).resolve().parents[1]
CELL, CONFIG, USER_CONFIG = "game-mf.fit", "game-mf-ml20m-u30", "glmix-ml20m-u30"
TINY_ROWS = 20000
MF = "perMovieMF"


@pytest.fixture(autouse=True)
def _clean_telemetry():
    telemetry.disable()
    telemetry.reset()
    yield
    telemetry.disable()
    telemetry.reset()


def full_config(name=CONFIG) -> dict:
    return json.loads(
        (ROOT / "benchmark" / "configs" / f"{name}.json").read_text())


@pytest.fixture(scope="module")
def cell():
    from benchmark import harness
    from benchmark.recipes import dense_game

    loaded = harness.load_cell(CELL)
    return (dense_game.scale_down(loaded["config"], TINY_ROWS),
            loaded["workload"])


def _fit(config, workload, seed, storage="float32", problem=None):
    from benchmark.jobs import cd_fit_game
    from benchmark.recipes import dense_game

    with jax.enable_x64(False):  # the cell is a float32 configuration
        problem = problem or dense_game.make(config, seed)
        job = cd_fit_game.build(config, workload, problem, storage=storage)
        job.warm_up(seed)
        window = job.window(0.0, seed)  # one job
        job.after_window(window)
    return problem, job, window


def _compared(config, workload, problem, window):
    from benchmark.checks import cd_fit_game as check

    with jax.enable_x64(False):
        return check.check(problem, config, workload, window)


@pytest.fixture(scope="module")
def sound(cell):
    """One sound fit with telemetry on, the block's arguments recorded."""
    compile_cache._listen()
    compile_cache.reset_compile_ledger()
    telemetry.reset()
    telemetry.enable()
    try:
        problem, job, window = _fit(*cell, seed=2 ** 31 + 37)
        snapshot = telemetry.snapshot()
        with jax.enable_x64(False):
            counters = job.counters(window)
    finally:
        telemetry.disable()
        telemetry.reset()
    return {"problem": problem, "job": job, "window": window,
            "counters": counters, "gauges": snapshot["gauges"],
            "counts": snapshot["counters"],
            "ledger": compile_cache.compile_ledger()}


# -- the configuration and the recipe -------------------------------------------


def test_the_configuration_is_glmix_u30_plus_a_group_and_a_factored_coordinate():
    """``game-mf-ml20m-u30`` is ``glmix-ml20m-u30``'s problem letter for
    letter on every key the two files share (the fixed effect, the users,
    the rows, widths, laws and strings), plus a per-movie group (data
    only) and one factored coordinate over it."""
    game, glmix = full_config(), full_config(USER_CONFIG)
    own = {"name", "source", "stands_for", "recipe", "updating_sequence",
           "assumed"}
    shared = (set(game) & set(glmix)) - own
    assert {"fixed", "random", "n_rows", "published", "task", "link",
            "dtype", "iterations", "reduced"} <= shared
    for key in shared - {"published"}:
        assert game[key] == glmix[key], key
    for key, value in glmix["published"].items():
        assert game["published"][key] == value
    assert game["n_rows"] == 6000876
    assert game["random"][0]["n_entities"] == 41548
    assert game["architecture"] is None and game["recipe"] == "dense_game"
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    entry = next(c for c in bench["configs"] if c["name"] == CONFIG)
    assert entry["source"] == game["source"] and len(entry["source"]) <= 200
    assert entry["reduced"] == game["reduced"] == ["n_rows", "n_entities"]
    (movie,), (mf,) = game["groups"], game["factored"]
    user = game["random"][0]
    share = user["n_entities"] / glmix["published"]["n_entities"]
    assert abs(movie["activity"]["share"] - share) < 1e-4
    assert (movie["d"], movie["intercept"]) == (user["d"], "first")
    assert "optimizer" not in movie  # data only: no full-rank coordinate
    assert movie["data_config"] == "movieId,item,1,-1,-1,-1"
    assert mf["group"] == movie["name"] and mf["mf"] == "2,8"
    # the issue's strings
    assert mf["optimizer"] == "20,1e-6,1.0,1.0,LBFGS,L2"
    assert mf["refit_optimizer"].split(",", 1)[1] == "1e-7,1.0,1.0,LBFGS,L2"
    assert int(mf["mf"].split(",")[1]) < movie["d"]  # or nothing is low rank
    assert game["updating_sequence"] == ["fixed", "perUser", MF]
    for key in ("cut", "activity", "items", "rank", "truth", "dealing",
                "optimizer", "guarantees"):
        assert game["assumed"][key]


def test_the_items_law_meets_the_published_numbers():
    from benchmark.recipes import dense_game

    config = full_config()
    law, pub = config["groups"][0]["activity"], config["published"]
    counts = dense_game.published_counts(law)
    assert len(counts) == pub["n_items"] == 26744
    assert counts.sum() == pub["n_rows"] == 20000263
    assert counts.max() == pub["rows_per_item"]["max"] == 67310
    assert (counts == counts.max()).sum() == 1 and counts.min() == 1
    assert np.all(np.diff(counts) <= 0)  # monotone by rank
    assert np.median(counts) == 18
    assert abs(counts.mean() - pub["rows_per_item"]["mean"]) < 0.05


def test_the_thinning_rule_and_what_it_deals_at_the_cells_size():
    from benchmark.recipes import dense_game, dense_glm

    config = full_config()
    movie = config["groups"][0]
    law, n = movie["activity"], config["n_rows"]
    assert dense_glm.n_rows_of(config) == n
    counts = dense_game.thinned_counts(law, n)
    assert counts.sum() == n and len(counts) == movie["n_entities"]
    published = dense_game.published_counts(law)
    rounded = np.floor(law["share"] * published + 0.5).astype(np.int64)
    kept = rounded[rounded > 0]
    assert len(kept) == len(counts) == 23119  # movies of no row dropped
    assert counts.max() == 20192 and kept.sum() - n == 44
    # what the rounding leaves over goes one each to the largest
    over = counts - kept
    assert set(np.unique(over)) <= {0, int(np.sign(n - kept.sum()))}
    assert np.all(np.diff(np.abs(over)) <= 0)
    # the item tail: classes from 4 rows up, most slots in classes >= 512
    pad = dense_glm.next_size(counts, 4)
    assert pad.min() == 4 and (counts < movie["d"]).sum() > 10000
    assert pad[pad >= 512].sum() > 0.8 * pad.sum()
    classes = sorted(set(pad.tolist()))
    assert classes == [2 ** p for p in range(2, 16)]  # fourteen: r 4-32,768
    assert 13000 < (pad <= 16).sum() < 14500  # the tail no other cell has


def test_every_seed_deals_the_same_counts_and_the_groups_independently(cell):
    from benchmark.recipes import dense_game

    config = cell[0]
    n = config["n_rows"]
    dealt = []
    for seed in (5, 2 ** 31 + 6):
        users = dense_game.entity_of_row(config, seed, 0)
        movies = dense_game.entity_of_row(config, seed, 1)
        assert len(users) == len(movies) == n
        dealt.append((np.sort(np.bincount(users)),
                      np.sort(np.bincount(movies)), users, movies))
    np.testing.assert_array_equal(dealt[0][0], dealt[1][0])
    np.testing.assert_array_equal(dealt[0][1], dealt[1][1])
    np.testing.assert_array_equal(
        dealt[0][1][::-1],
        dense_game.group_counts(config["groups"][0], n))
    assert not np.array_equal(dealt[0][3], dealt[1][3])  # the seed's deal
    # independent: the rows of the largest movie are spread over the users
    # as all rows are
    users, movies = dealt[0][2], dealt[0][3]
    top = np.argmax(np.bincount(movies))
    of_top = np.bincount(users[movies == top],
                         minlength=users.max() + 1)
    share = of_top / max(1, of_top.sum())
    overall = np.bincount(users) / n
    assert np.abs(share - overall).max() < 0.25
    assert len(np.unique(users[movies == top])) > 1


def test_the_recipes_blocks_hold_every_row_once_a_group(sound):
    problem = sound["problem"]
    n = problem.n_rows
    assert set(problem.groups) == {"perUser", "perMovie"}
    for group in problem.groups.values():
        seen = np.concatenate([np.asarray(b.row_ids).ravel()
                               for b in group.buckets])
        np.testing.assert_array_equal(np.sort(seen[seen < n]), np.arange(n))
        for b in group.buckets:
            rid = np.asarray(b.row_ids)
            ent = group.entity_of_row[np.where(rid < n, rid, 0)]
            assert np.all((ent == b.codes[:, None]) | (rid == n))


# -- the program against the reference --------------------------------------------


def test_the_fit_is_inside_the_cells_own_limits(cell, sound):
    compared = _compared(*cell, sound["problem"], sound["window"])
    assert set(compared) == set(cell[1]["compare"])
    for name, v in compared.items():
        assert v["value"] <= v["limit"], (name, v)


def test_a_whole_run_is_correct_and_lists_every_class(cell):
    from benchmark import harness

    with jax.enable_x64(False):
        result = harness.run_cell(CELL, seed=41, seconds=0.2, trace=True,
                                  t0=0.0, require_chip=False,
                                  rehearse_rows=TINY_ROWS)
    assert result["correct"] is True and result["failed"] == 0
    assert result["metrics"] == {} and result["device"]["platform"] == "cpu"
    classes = result["notes"]["routing"]["classes"]
    assert set(classes) == {"perUser", MF}
    assert all(c["path"] in ("kernel", "vmapped")
               for rows in classes.values() for c in rows)
    assert classes[MF][0]["rows"] == 4  # the item tail's smallest class
    assert set(result["notes"]["probes"]) == {
        "fe_solve", "re_solve", "mf_solve", "mf_refit", "mf_latent"}
    assert result["notes"]["counters"]["mf"][MF]["alternations"] == 2


def test_a_change_of_basis_moves_no_compared_number(cell, sound):
    """Gamma and B are fixed only up to an invertible k x k matrix: the
    check compares their product."""
    from benchmark.checks import cd_fit_game as check
    from benchmark.reference import game_cd

    assert "never" in check.__doc__  # compared by the products alone
    config, window = cell[0], sound["window"]
    rng = np.random.default_rng(3)
    m = rng.normal(size=(8, 8)) + 3 * np.eye(8)
    turned = dict(window, kept={})
    for key, answer in window["kept"].items():
        mf = answer["coefs"][MF]
        coefs = dict(answer["coefs"])
        coefs[MF] = {
            "gammas": [np.asarray(g, np.float64) @ m for g in mf["gammas"]],
            "B": np.linalg.solve(m, np.asarray(mf["B"], np.float64))}
        turned["kept"][key] = dict(answer, coefs=coefs)
    with jax.enable_x64(False):
        ref = game_cd.fit(sound["problem"], config)
        want = check.numbers(sound["problem"], config, window, ref)
        got = check.numbers(sound["problem"], config, turned, ref)
    assert set(got) == set(want)
    own = f"refit_obj_gap.{MF}"  # holds the PENALTY on B too: see below
    for name in set(want) - {own}:
        assert got[name] == pytest.approx(want[name], rel=2e-2, abs=1e-6)
    assert got[own] > 1e-3 > want[own]
    # ... while the factors themselves moved
    assert not np.allclose(turned["kept"]["last"]["coefs"][MF]["B"],
                           window["kept"]["last"]["coefs"][MF]["B"],
                           atol=1e-2)
    # the refit's own objective carries l2/2 |B|^2, which only a ROTATION of
    # the basis keeps: what the program says its refit's objective was
    # belongs with the B it returned
    q, _ = np.linalg.qr(rng.normal(size=(8, 8)))
    rotated = dict(window, kept={})
    for key, answer in window["kept"].items():
        mf = answer["coefs"][MF]
        rotated["kept"][key] = dict(answer, coefs=dict(answer["coefs"], **{
            MF: {"gammas": [np.asarray(g, np.float64) @ q
                            for g in mf["gammas"]],
                 "B": q.T @ np.asarray(mf["B"], np.float64)}}))
    with jax.enable_x64(False):
        got = check.numbers(sound["problem"], config, rotated, ref)
    assert got[own] == pytest.approx(want[own], abs=2e-6)


@pytest.mark.parametrize("seed", [21, 2 ** 31 + 23])
def test_bf16_storage_control_fails_score_self_gap_alone(cell, seed):
    problem, _, window = _fit(*cell, seed=seed, storage="bfloat16")
    compared = _compared(*cell, problem, window)
    failed = [n for n, v in compared.items() if not v["value"] <= v["limit"]]
    assert failed == ["score_self_gap"], compared


@pytest.mark.parametrize("fault,number", [
    ("refit_left_out", "coef_gap.perMovieMF"),
    ("latent_left_out", "coef_gap.perMovieMF"),
    ("mf_scores_left_out", "obj_gap"),
    ("half_batch", "coef_gap.fixed"),
    ("refit_half_batch", "refit_obj_gap.perMovieMF"),
    ("latent_half_batch", "coef_gap.perMovieMF"),
    ("entity_altered_smallest", "coef_worst.perMovieMF"),
    ("entity_altered_median", "coef_worst.perMovieMF"),
    ("user_altered", "coef_worst.perUser")])
def test_a_planted_fault_fails_the_number_named_for_it(cell, sound, fault,
                                                       number):
    from benchmark import faults_game

    with faults_game.FAULTS[fault]():
        _, _, window = _fit(*cell, seed=2 ** 31 + 37,
                            problem=sound["problem"])
    compared = _compared(*cell, sound["problem"], window)
    v = compared[number]
    assert v["value"] > v["limit"], (number, compared)
    if fault in faults_game.AFTER_FIT:  # one typical entity of a class,
        # altered by faults.py's factor of 1.5, reads 0.5 under the class's
        # own scale, and no other coordinate sees it (at this size one
        # entity of a few hundred also shows in its group's own norm, and
        # its rows in the refit's objective, which the altered model no
        # longer is the argument of; of 23,119 entities and 6.0M rows
        # neither does: the chip's readings)
        assert v["value"] == pytest.approx(0.5, abs=0.03)
        failed = {n for n, c in compared.items()
                  if not c["value"] <= c["limit"]}
        assert failed <= {number, number.replace("worst", "gap"),
                          f"refit_obj_gap.{MF}"}, compared


def test_the_reference_refit_is_at_the_minimiser(cell, sound):
    """At the reference's B the refit's gradient vanishes, by a dense
    float64 computation over every slot."""
    from benchmark.reference import game_cd

    config, problem = cell[0], sound["problem"]
    with jax.enable_x64(False):
        ref = game_cd.fit(problem, config)
    mf = ref["coefs"][MF]
    b = np.asarray(mf["B"], np.float64)
    # the residual the factored coordinate saw: the coordinates before it
    fixed, user = ref["coefs"]["fixed"], ref["coefs"]["perUser"]
    with jax.enable_x64(False):
        before = np.asarray(game_cd._coordinate_scores(
            problem, config, "fixed", fixed) + game_cd._coordinate_scores(
            problem, config, "perUser", user), np.float64)
    grad = b.copy()  # l2 = 1
    ext = np.append(before, 0.0)
    for bucket, gamma in zip(problem.groups["perMovie"].buckets,
                             mf["gammas"]):
        x = np.asarray(bucket.x, np.float64)[..., :25]
        coef = np.asarray(gamma, np.float64) @ b
        z = np.einsum("erd,ed->er", x, coef) + ext[np.asarray(bucket.row_ids)]
        r = np.asarray(bucket.weights) * (
            1 / (1 + np.exp(-z)) - np.asarray(bucket.labels))
        grad += np.einsum("ek,er,erd->kd", np.asarray(gamma, np.float64),
                          r, x)
    assert np.abs(grad).max() < 2e-3 * max(1.0, np.abs(b).max())


def test_the_reference_draws_b0_itself_and_the_check_holds_the_programs(
        cell, sound):
    """B0 is an input the configuration states: the reference draws it
    from the law on its own, and a program whose start differs (another
    seed, another scale, another rank) fails ``b0_gap`` whatever else
    agrees."""
    from benchmark.checks import cd_fit_game as check
    from benchmark.reference import game_cd

    config, window = cell[0], sound["window"]
    spec = config["factored"][0]
    want = game_cd.start_matrix(spec, 25)
    assert want.shape == (8, 25) and np.linalg.matrix_rank(want) == 8
    assert abs(want.std() * 8 - 1.0) < 0.15  # sd 1/k
    np.testing.assert_array_equal(window["b0"][MF], want)
    assert sound["job"].coords[MF].seed == spec["start"]["seed"] == 7
    limit = cell[1]["compare"]["b0_gap"]
    assert check._b0_gap(window["b0"][MF], want) == 0.0 < limit
    other = game_cd.start_matrix(dict(spec, start=dict(spec["start"],
                                                       seed=8)), 25)
    for wrong in (other, want * np.sqrt(8.0), want[:4], None):
        assert check._b0_gap(wrong, want) > limit
    with pytest.raises(ValueError, match="start law"):
        game_cd.start_matrix(dict(spec, start={"law": "x", "seed": 7}), 25)
    # ... and through the check: the compared number reads it
    moved = dict(window, b0={MF: other})
    with jax.enable_x64(False):
        ref = game_cd.fit(sound["problem"], config)
        values = check.numbers(sound["problem"], config, moved, ref)
    assert values["b0_gap"] > 1.0


# -- the cell's own per-layer readers -------------------------------------------


@pytest.fixture(scope="module")
def traced_ctx(cell, sound):
    """What the harness hands a reader after a traced run on a chip, with
    the trace's numbers made up: three jobs of 0.5 s busy, every probe
    0.1 s busy, the kernel's events 0.03 s."""
    config, workload = cell
    layers = ["fe_solve", "re_solve", "mf_solve", "mf_refit", "mf_latent"]
    with jax.enable_x64(False):
        probes = {layer: [call()] for layer, call
                  in sound["job"].probes().items()}
    assert sorted(probes) == sorted(layers)
    return {
        "config": config, "workload": workload,
        "counters": sound["counters"], "probes": probes,
        "device": {"kind": "TPU v5 lite"},
        "peaks": json.loads(
            (ROOT / "benchmark" / "peaks.json").read_text()),
        "window": {"seconds": 1.0, "attempted": 2},
        "trace": {"busy_s": 1.5, "window_s": 1.6, "traced_jobs": 3,
                  "probe_busy_s": {layer: [0.1] for layer in layers},
                  "op_seconds": {"%pallas_entity_lbfgs.3": 0.02,
                                 "%pallas_entity_lbfgs.7": 0.01,
                                 "%fusion.1": 1.0}}}


# the readers that are new with the cell, and the accepted ones whose
# ``workloads`` the cell was appended to (they read the same layer of the
# same program: no second copy)
NEW_READERS = ["mf_solve_ms", "mf_refit_ms", "mf_refit_roofline",
               "mf_latent_ms", "game_unprobed_ms"]
APPENDED_TO = ["fit_mfu", "fe_solve_roofline", "re_solve_ms",
               "re_solve_roofline", "block_trace_lower_s"]
# PR 40's readers of the block's instruction table, appended after the
# cell's own: those that list this cell
TABLE_READERS = ["exchange_ms", "fe_solve_job_ms", "re_solve_job_ms",
                 "mf_solve_job_ms", "mf_kernel_ms", "fe_score_ms",
                 "unscoped_ms"]


@pytest.mark.parametrize("metric,low,high", [
    ("re_solve_ms", 100.0, 100.0),
    ("mf_solve_ms", 100.0, 100.0),
    ("mf_refit_ms", 100.0, 100.0),
    ("mf_latent_ms", 100.0, 100.0),
    # 500 ms a job busy less three solves of 100 ms alone
    ("game_unprobed_ms", 200.0, 200.0),
    ("re_solve_roofline", 1e-6, 100.0),
    ("fe_solve_roofline", 1e-6, 100.0),
    ("mf_refit_roofline", 1e-6, 100.0),
    ("fit_mfu", 1e-9, 100.0)])
def test_a_reader_of_the_cell_reads_its_layer(traced_ctx, metric, low, high):
    """Every per-layer metric that lists ``game-mf.fit`` reads something from
    a traced run's context, and nothing (never 0, never an error) from a
    run without a trace."""
    import importlib

    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    entry = next(m for m in bench["per_layer"] if m["name"] == metric)
    assert CELL in entry["workloads"] and entry["moves"] == "fit_s"
    reader = importlib.import_module(f"benchmark.metrics.{metric}")
    value = reader.read(traced_ctx)
    assert low * (1 - 1e-9) <= value <= high * (1 + 1e-9), value
    untraced = dict(traced_ctx, trace=None, probes=None, counters=None)
    assert reader.read(untraced) is None


def test_the_whole_fits_share_counts_the_factored_coordinates_work(
        traced_ctx):
    """``fit_mfu`` on this cell divides the job's FLOPs as
    ``work_model_game`` counts them (the factored coordinate's projections,
    latent solves at width k and refits included) and
    ``block_trace_lower_s`` reads the row of the game's own block."""
    from benchmark import work_model_game
    from benchmark.metrics import block_trace_lower_s, fit_mfu

    counters = traced_ctx["counters"]
    assert counters["flops"] == work_model_game.job_flops(counters)
    without = dict(counters, mf={})
    assert work_model_game.job_flops(without) < counters["flops"]
    peak = traced_ctx["peaks"]["TPU v5 lite"]["flops_per_s_bf16"]
    assert fit_mfu.read(traced_ctx) == pytest.approx(
        100.0 * counters["flops"] / 0.5 / peak)
    ledger = {"functions": {scopes.CD_BLOCK: {"trace_s": 2.0,
                                              "lower_s": 0.5}}}
    assert block_trace_lower_s.read(
        dict(traced_ctx, compile_ledger=ledger)) == 2.5


def test_the_cells_metrics_are_five_new_and_five_it_was_appended_to():
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    listing = [m["name"] for m in bench["per_layer"]
               if CELL in m.get("workloads", [])]
    assert sorted(listing) == sorted(
        NEW_READERS + APPENDED_TO + TABLE_READERS)
    new = [m for m in bench["per_layer"] if m.get("workloads") == [CELL]
           and m["name"] not in TABLE_READERS]
    assert [m["name"] for m in new] == NEW_READERS
    first = bench["per_layer"].index(new[0])
    assert bench["per_layer"][first:first + len(new)] == new  # in order
    # ... and behind them only what PR 40 appended
    assert {m["name"] for m in bench["per_layer"][first + len(new):]} >= set(
        TABLE_READERS)
    for m in bench["per_layer"]:
        if m["name"] in APPENDED_TO:  # appended to, and nothing else moved
            assert m["workloads"] == ["glmix.fit", CELL]
    unlisted = [m["name"] for m in bench["per_layer"]
                if "workloads" not in m]
    assert unlisted == ["fe_solve_ms", "device_idle", "hbm_peak_gib"]
    metrics = ROOT / "benchmark" / "metrics"
    assert not list(metrics.glob("game_re_*")) + list(
        metrics.glob("game_fit_*"))  # no second copy of an accepted reader
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["chips"]) == (CONFIG, 1)
    assert "glmix.fit" in cell["why"] and len(cell["why"]) <= 200


def test_the_sweeps_roofline_counts_the_users_blocks(traced_ctx):
    from benchmark import work_model
    from benchmark.metrics import re_solve_roofline as reader

    counters = traced_ctx["counters"]
    users = counters["groups"]["perUser"]
    assert counters["buckets"] == users["buckets"]
    assert counters["d_entity"] == users["d"]
    bw = traced_ctx["peaks"]["TPU v5 lite"]["hbm_bytes_per_s"]
    least = work_model.re_sweep_bytes(users["buckets"]) / bw
    assert reader.read(traced_ctx) == pytest.approx(100.0 * least / 0.1,
                                                    rel=1e-6)


def test_the_limits_stand_twice_over_sound_and_twice_under_their_faults():
    """The rule for every limit of the cell: at least twice the largest
    sound reading and at most half the smallest reading of the fault named
    for it, both written beside it (``compare_readings``) from the chip."""
    from benchmark import faults_game

    workload = json.loads(
        (ROOT / "benchmark" / "workloads" / f"{CELL}.json").read_text())
    limits, readings = workload["compare"], workload["compare_readings"]
    assert set(readings) - {"origin"} == set(limits)
    for name, limit in limits.items():
        r = readings[name]
        assert r["sound_n"] >= 8, name
        assert limit >= 2.0 * r["sound_max"], (name, limit, r)
        assert limit <= 0.5 * r["fault_min"], (name, limit, r)
        assert r["fault_n"] >= (3 if r["fault"] == "control" else 2), name
        assert r["fault"] in set(faults_game.FAULTS) | {
            "control", "another_start"}, name
        for also, reading in r.get("also", {}).items():
            assert limit <= 0.5 * reading, (name, also)
    named = {f for k, r in readings.items() if k != "origin"
             for f in [r["fault"], *r.get("also", {})]}
    assert named >= set(faults_game.FAULTS) | {"control", "mxu_default"}


# -- what the factored coordinate says from inside --------------------------------


def test_every_entity_coordinate_indexes_its_own_datasets_rows(sound):
    coords = sound["job"].coords
    mf, user = coords[MF], coords["perUser"]
    assert mf.dataset is not user.dataset
    assert mf.unslotted_rows == user.unslotted_rows == 0
    n = sound["problem"].n_rows
    for coord in (mf, user):
        index = np.asarray(coord._slot_of_row)
        assert index.shape == (n,) and len(np.unique(index)) == n
        assert "_row_index" not in vars(coord.dataset)  # nothing kept there


def test_factored_routing_is_the_guards_word_on_the_latent_width(
        sound, monkeypatch):
    from photon_ml_tpu.optimization.config import (
        GLMOptimizationConfiguration,
    )
    from photon_ml_tpu.types import TaskType

    mf = sound["job"].coords[MF]
    # the full-rank random effect over the same blocks: what the guard says
    # at the blocks' own width
    item = co.RandomEffectCoordinate(
        name="perMovie", dataset=mf.dataset,
        task_type=TaskType.LOGISTIC_REGRESSION,
        config=GLMOptimizationConfiguration.parse(
            "20,1e-6,1.0,1.0,LBFGS,L2"))
    np.testing.assert_array_equal(item._slot_of_row, mf._slot_of_row)
    routing = mf.routing()
    shapes = [b.x.shape for b in mf.dataset.blocks]
    assert [(b["rows"], b["entities"], b["slots"]) for b in routing] == [
        (r, e, e * r) for e, r, _ in shapes]
    assert {b["path"] for b in routing} == {"vmapped"}
    assert all("cpu" in b["reason"] for b in routing)
    assert mf.true_rows() == item.true_rows() == sound["problem"].n_rows
    # on the chip's side of the guard (interpret mode stands in for it) a
    # latent class is judged at r x k, the random effect's at r x d
    monkeypatch.setenv("PHOTON_ML_TPU_PALLAS_INTERPRET", "1")
    for block, wide, narrow in zip(mf.dataset.blocks, item.routing(),
                                   mf.routing()):
        e, r, d = block.x.shape
        want = co._kernel_refusal(mf._objective, mf.config,
                                  jax.ShapeDtypeStruct((e, r, 8), jnp.float32))
        assert (narrow["path"] == "kernel") == (want is None)
        if wide["path"] == "kernel":  # what d admits, k admits
            assert narrow["path"] == "kernel"


def test_the_guard_admits_latent_classes_it_refuses_at_the_blocks_width(
        monkeypatch):
    monkeypatch.setenv("PHOTON_ML_TPU_PALLAS_INTERPRET", "1")
    from photon_ml_tpu.ops.glm_objective import GLMObjective
    from photon_ml_tpu.ops.losses import loss_for_task
    from photon_ml_tpu.optimization.config import (
        GLMOptimizationConfiguration,
    )
    from photon_ml_tpu.types import TaskType

    objective = GLMObjective(loss_for_task(TaskType.LOGISTIC_REGRESSION))
    config = GLMOptimizationConfiguration.parse("20,1e-6,1.0,1.0,LBFGS,L2")
    admitted = {width: [r for r in (4, 64, 256, 512, 1024, 2048, 16384)
                        if co._kernel_refusal(
                            objective, config, jax.ShapeDtypeStruct(
                                (8, r, width), jnp.float32)) is None]
                for width in (32, 8)}
    assert 4 in admitted[32] and 4 in admitted[8]
    assert set(admitted[32]) < set(admitted[8])
    assert 16384 not in admitted[8]


def test_the_mf_gauges_and_counters_are_set(sound):
    gauges, counts, job = sound["gauges"], sound["counts"], sound["job"]
    group = sound["problem"].groups["perMovie"]
    slots = sum(b.x.shape[0] * b.x.shape[1] for b in group.buckets)
    assert gauges[scopes.GAUGE_MF_FACTORS] == 8
    assert gauges[scopes.GAUGE_MF_SLOTS] == slots
    assert gauges[scopes.GAUGE_MF_KERNEL_ENTITIES] == 0
    assert gauges[scopes.GAUGE_MF_FALLBACK_ENTITIES] == group.n_entities
    # the random effects' gauges leave the factored coordinate out
    users = sound["problem"].groups["perUser"]
    assert gauges[scopes.GAUGE_RE_SLOTS] == sum(
        b.x.shape[0] * b.x.shape[1] for b in users.buckets)
    assert gauges[scopes.GAUGE_RE_ROWS] == sound["problem"].n_rows
    assert gauges[scopes.GAUGE_RE_SCORE_ROWS] == 2 * sound["problem"].n_rows
    # two runs (warm-up and the window's one job), two alternations each
    mf = sound["counters"]["mf"][MF]
    assert counts[scopes.COUNTER_MF_ALTERNATIONS] == 4
    assert mf["alternations"] == 2 and len(mf["refit_iterations"]) == 2
    assert 0 < counts[scopes.COUNTER_MF_REFIT_ITERATIONS] <= 40
    assert all(0 < it <= 10 for it in mf["refit_iterations"])
    assert all(it > 0 for it in mf["latent_row_iterations"])
    assert sound["counters"]["flops"] > 0
    assert job is not None


def test_the_ledger_has_the_games_block_and_its_two_inner_programs(sound):
    rows = sound["ledger"]["functions"]
    block = rows[scopes.CD_BLOCK]
    assert block["partitions"] == 1 and block["trace_s"] > 0
    # the factored coordinate's two jitted functions are traced inside it
    assert {"_solve_factored_block", "_solve_latent_matrix"} <= set(rows)


@pytest.fixture(scope="module")
def block_text(cell):
    """The lowered and the compiled text of the game's ``cd_block`` at the
    arguments ``run()`` gives it."""
    _, job, _ = _fit(*cell, seed=7)
    cd = job.cd
    fn = cd._fused_block_fn(1)
    seen = {}

    def recorder(*args):
        seen["args"] = args
        return fn(*args)

    cd._block_fns[1] = recorder
    with jax.enable_x64(False):
        cd.run(1)
        lowered = fn.lower(*seen["args"])
        return {"lowered": lowered.as_text(debug_info=True),
                "compiled": lowered.compile().as_text()}


@pytest.mark.parametrize("scope", scopes.MF_SCOPES + (
    scopes.RE_GATHER, scopes.RE_MARGINS, scopes.RE_SCATTER,
    scopes.cd_coordinate(MF), scopes.cd_coordinate("perUser")))
def test_the_games_block_carries_the_scope(block_text, scope):
    text = block_text["lowered"]
    assert f"{scope}/" in text or f"/{scope}\"" in text, scope


def test_the_mf_scopes_sit_under_their_coordinate(block_text):
    import re

    paths = set(re.findall(r'op_name="([^"]*)"', block_text["compiled"]))
    under = scopes.cd_coordinate(MF)
    for scope in scopes.MF_SCOPES + (scopes.RE_GATHER, scopes.RE_MARGINS,
                                     scopes.RE_SCATTER):
        assert any(under in p.split("/") and scope in p.split("/")
                   for p in paths), scope
    # a latent class is a child of photon.mf.latent, as r<rows> is of
    # photon.re.solve
    assert any(f"{scopes.MF_LATENT}/r4/" in p for p in paths)
    assert any(f"{scopes.RE_SOLVE}/r32/" in p for p in paths)
    # the margins of the factored coordinate's scoring are named
    assert any(under in p and scopes.RE_MARGINS in p for p in paths)


def test_trace_scopes_places_the_mf_scopes():
    import sys

    sys.path.insert(0, str(ROOT / "dev_scripts"))
    import trace_scopes

    base = "jit(cd_block)/while/body/photon.cd.perMovieMF/"
    where = trace_scopes.place(
        base + "jit(_solve_factored_block)/photon.mf.latent/r16/while/dot")
    assert where["leaf"] == scopes.MF_LATENT
    assert where["size_class"] == "r16"
    assert where["coordinate"] == "photon.cd.perMovieMF"
    for scope in (scopes.MF_FLATTEN, scopes.MF_PROJECT, scopes.MF_REFIT):
        assert trace_scopes.place(base + scope + "/add")["leaf"] == scope
    assert trace_scopes.place(
        base + "photon.re.gather/gather")["leaf"] == scopes.RE_GATHER
