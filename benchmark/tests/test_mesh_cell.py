"""The four-chip cell ``glmix-20m.fit4`` at a tiny size on four virtual CPU
devices: its configuration file, the recipe that deals every array out
already sharded, the job kind built with ``mesh=``, the check over the
sharded reference, and the three metrics that read it."""

import json
import os

# Four virtual devices for this process, asked for before any test touches
# a device (pytest imports every test module before it runs the first test).
if "xla_force_host_platform_device_count" not in os.environ.get(
        "XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + " --xla_force_host_platform_device_count=4").strip()

import jax  # noqa: E402
import numpy as np  # noqa: E402
import pytest  # noqa: E402

from benchmark import faults, harness  # noqa: E402
from benchmark.checks import cd_fit_mesh as check  # noqa: E402
from benchmark.jobs import cd_fit_mesh  # noqa: E402
from benchmark.metrics import (  # noqa: E402
    collective_ms,
    mesh_fit_mfu,
    re_slot_imbalance,
)
from benchmark.recipes import dense_glm, dense_glm_mesh  # noqa: E402
from benchmark.tests.common import HERE  # noqa: E402

CELL, CONFIG, ONE_CHIP_CONFIG = "glmix-20m.fit4", "glmix-ml20m", "glmix-ml20m-u30"
TINY_ROWS, K = 6000, 4


def _config(name=CONFIG) -> dict:
    return json.loads((HERE / "configs" / f"{name}.json").read_text())


def _workload() -> dict:
    return json.loads((HERE / "workloads" / f"{CELL}.json").read_text())


def _tiny() -> dict:
    return dense_glm_mesh.scale_down(_config(), TINY_ROWS)


@pytest.fixture(scope="module")
def devices():
    if len(jax.devices()) < K:
        pytest.skip(f"needs {K} devices: another test touched JAX first")
    return jax.devices()[:K]


# -- the configuration ---------------------------------------------------------

def test_the_configuration_is_the_one_chip_cells_with_nothing_cut():
    full, cut = _config(), _config(ONE_CHIP_CONFIG)
    assert full["reduced"] == [] and full["chips"] == 4
    assert full["n_rows"] == full["published"]["n_rows"] == 20000263
    group, cut_group = full["random"][0], cut["random"][0]
    assert group["n_entities"] == full["published"]["n_entities"] == 138493
    assert full["source"] == cut["source"]
    for key in ("task", "link", "dtype", "fixed", "updating_sequence",
                "iterations", "published"):
        assert full[key] == cut[key], key
    assert {**group, "n_entities": 0} == {**cut_group, "n_entities": 0}
    for key in ("activity", "widths", "features", "optimizer", "guarantees"):
        assert full["assumed"][key] == cut["assumed"][key], key
    bench = json.loads((HERE.parent / "BENCHMARK.json").read_text())
    entry = {c["name"]: c for c in bench["configs"]}[CONFIG]
    assert entry["reduced"] == full["reduced"]
    assert entry["source"] == full["source"]
    cell = {w["name"]: w for w in bench["workloads"]}[CELL]
    assert cell["chips"] == 4 and cell["config"] == CONFIG


def test_the_activity_counts_are_the_published_counts_to_the_row():
    full = _config()
    counts = dense_glm_mesh.activity_counts(full)
    law = full["random"][0]["activity"]
    assert counts.sum() == 20000263 and len(counts) == 138493
    assert (counts.min(), counts.max()) == (law["min"], law["max"])
    assert np.median(counts) == law["median"]
    assert np.all(np.diff(counts) >= 0)
    quantiles = dense_glm.activity_counts(full["random"][0])
    assert 0 < quantiles.sum() - counts.sum() < 3000
    assert np.max(np.sort(quantiles) - counts) <= 1
    # a rehearsal's counts are the law's own: nothing to take away
    tiny = _tiny()
    np.testing.assert_array_equal(
        dense_glm_mesh.activity_counts(tiny),
        dense_glm.activity_counts(tiny["random"][0]))
    with pytest.raises(ValueError, match="activity sums to"):
        dense_glm_mesh.activity_counts({**tiny, "n_rows": tiny["n_rows"] // 2})


# -- the recipe ----------------------------------------------------------------

@pytest.fixture(scope="module")
def problems(devices):
    config = _tiny()
    return (dense_glm_mesh.make(config, 2 ** 31 + 7, devices=devices[:1]),
            dense_glm_mesh.make(config, 2 ** 31 + 7, devices=devices))


def test_the_recipe_deals_the_same_problem_on_one_device_and_on_four(problems):
    one, four = problems
    n = one.true_rows
    assert one.n_rows == n == four.true_rows and n % K  # padding is needed
    assert four.n_rows == K * -(-n // K)
    for name in ("x", "labels", "offsets", "weights"):
        a, b = np.asarray(getattr(one, name)), np.asarray(getattr(four, name))
        np.testing.assert_array_equal(b[:n], a, err_msg=name)
        np.testing.assert_array_equal(b[n:], 0, err_msg=name)
    np.testing.assert_array_equal(one.entity_of_row, four.entity_of_row)
    assert len(one.buckets) == len(four.buckets)
    for a, b in zip(one.buckets, four.buckets):
        e = a.x.shape[0]
        assert b.x.shape[0] == K * -(-e // K) and b.x.shape[1:] == a.x.shape[1:]
        np.testing.assert_array_equal(a.codes, b.codes)
        for name in ("x", "labels", "offsets", "weights", "feat_idx"):
            np.testing.assert_array_equal(
                np.asarray(getattr(b, name))[:e], np.asarray(getattr(a, name)),
                err_msg=name)
        rid_a, rid_b = np.asarray(a.row_ids), np.asarray(b.row_ids)
        np.testing.assert_array_equal(
            np.where(rid_b[:e] == four.n_rows, n, rid_b[:e]), rid_a)
        # the entities that fill the class: every slot the sentinel
        assert np.all(rid_b[e:] == four.n_rows)
        assert np.all(np.asarray(b.weights)[e:] == 0)
        assert np.all(np.asarray(b.feat_idx)[e:] == -1)


def test_every_array_is_made_where_it_lives(problems):
    _, four = problems
    per = four.n_rows // K
    for arr in (four.x, four.labels, four.offsets, four.weights):
        assert [s.data.shape[0] for s in arr.addressable_shards] == [per] * K
        assert len({s.device for s in arr.addressable_shards}) == K
    for b in four.buckets:
        for arr in (b.x, b.labels, b.offsets, b.weights, b.row_ids,
                    b.feat_idx):
            assert ({s.data.shape[0] for s in arr.addressable_shards}
                    == {arr.shape[0] // K})
    # a slot's features are its row's
    b = four.buckets[0]
    rid = np.asarray(b.row_ids)
    labels = np.asarray(four.labels)
    live = rid < four.n_rows
    np.testing.assert_array_equal(np.asarray(b.labels)[live], labels[rid[live]])
    assert np.all(np.asarray(b.x)[..., 0][live] == 1.0)  # the intercept
    assert np.all(np.asarray(b.x)[~live] == 0.0)


def test_another_seed_is_another_problem_of_the_same_shapes(devices, problems):
    other = dense_glm_mesh.make(_tiny(), 5, devices=devices)
    _, four = problems
    assert [b.x.shape for b in other.buckets] == [b.x.shape
                                                  for b in four.buckets]
    assert not np.array_equal(np.asarray(other.x), np.asarray(four.x))


# -- the job kind and the check --------------------------------------------------

def _compared(problem, storage="float32", seed=11):
    config = _tiny()
    job = cd_fit_mesh.build(config, _workload(), problem, storage=storage)
    job.warm_up(seed)
    window = job.window(0.0, seed)
    job.after_window(window)
    counters = job.counters(window)
    return check.check(problem, config, _workload(), window), counters


def test_the_reference_agrees_with_the_four_device_fit(problems):
    compared, counters = _compared(problems[1])
    for name, v in compared.items():
        assert v["value"] <= v["limit"], (name, v)
    assert counters["devices"] == K and counters["buffers_kept"] is True
    assert counters["rows_per_device"] == [problems[1].n_rows // K] * K
    assert len(set(counters["slots_per_device"])) == 1


def test_bf16_storage_control_fails_score_self_gap(problems):
    compared, _ = _compared(problems[1], storage="bfloat16")
    failed = [n for n, v in compared.items() if not v["value"] <= v["limit"]]
    assert "score_self_gap" in failed, compared


def test_one_devices_rows_left_out_fails_coef_gap_fixed(problems):
    """The planted fault of a sharded fit: the rows of the mesh's first
    device count for nothing in the fixed-effect solve."""
    import jax.numpy as jnp

    from photon_ml_tpu.algorithm import coordinates as co
    from photon_ml_tpu.ops.glm_objective import GLMBatch

    def make(original):
        def broken(self, data, params, residual, key):
            batch = data[0]
            n = batch.weights.shape[0]
            keep = jnp.arange(n) >= n // K
            batch = GLMBatch(batch.features, batch.labels, batch.offsets,
                             jnp.where(keep, batch.weights, 0.0))
            return original(self, (batch,) + tuple(data[1:]), params,
                            residual, key)
        return broken

    with faults._patched(co.FixedEffectCoordinate, "pure_update", make):
        compared, _ = _compared(problems[1])
    v = compared["coef_gap.fixed"]
    assert v["value"] > v["limit"], compared


def test_the_reference_reads_x_without_gathering_it(problems, monkeypatch):
    from benchmark.reference import glm_cd, glm_cd_mesh

    monkeypatch.setattr(glm_cd, "BLOCK_ROWS", 1000)  # several blocks a pass
    monkeypatch.setattr(glm_cd_mesh, "BLOCK_ROWS", 500)
    one, four = problems
    c = np.linspace(-1, 1, four.x.shape[1]).astype(np.float32)
    want = np.asarray(four.x) @ c
    np.testing.assert_allclose(glm_cd_mesh.matvec(four.x, c, K), want,
                               rtol=1e-5, atol=1e-5)
    text = glm_cd_mesh.matvec.lower(four.x, c, K).compile().as_text()
    assert "all-gather" not in text
    assert "all-gather" in glm_cd.matvec.lower(four.x, c).compile().as_text()
    ref4 = glm_cd_mesh.fit(four, _tiny())
    ref1 = glm_cd.fit(one, _tiny())  # glm_cd itself, on one device
    np.testing.assert_allclose(ref4["history"], ref1["history"], rtol=1e-5)
    np.testing.assert_allclose(ref4["coefs"]["fixed"], ref1["coefs"]["fixed"],
                               atol=1e-4)


# -- a whole run, and the metrics ------------------------------------------------

def test_a_rehearsed_run_of_the_cell_is_correct(devices):
    result = harness.run_cell(CELL, seed=2 ** 31 + 31, seconds=0.5, trace=True,
                              t0=0.0, require_chip=False,
                              rehearse_rows=TINY_ROWS)
    assert result["correct"] is True and result["attempted"] >= 1
    assert result["metrics"] == {} and result["device"]["platform"] == "cpu"
    counters = result["notes"]["counters"]
    assert counters["devices"] == K and counters["buffers_kept"] is True
    assert set(result["notes"]["probes"]) == {"fe_solve", "re_solve"}


def test_fewer_chips_than_the_cell_asks_for_is_an_error():
    with pytest.raises(harness.NoChip):
        harness.run_cell(CELL, seed=1, seconds=0.1, trace=False, t0=0.0)


def _ctx(**kw):
    ctx = {"config": _tiny(), "device": {"kind": "TPU v5 lite"},
           "peaks": json.loads((HERE / "peaks.json").read_text()),
           "window": {"seconds": 10.0, "attempted": 20}, "counters": None,
           "trace": None}
    ctx.update(kw)
    return ctx


def test_mesh_fit_mfu_is_fit_mfu_over_the_meshs_devices():
    from benchmark.metrics import fit_mfu

    counters = {"flops": 1.97e12, "devices": 4}
    ctx = _ctx(counters=counters)
    assert fit_mfu.read(ctx) == pytest.approx(100 * 1.97e12 / 0.5 / 197e12)
    assert mesh_fit_mfu.read(ctx) == pytest.approx(fit_mfu.read(ctx) / 4)
    assert mesh_fit_mfu.read(_ctx()) is None
    assert mesh_fit_mfu.read(_ctx(counters={"flops": 1.0})) is None


def test_collective_ms_sums_the_collectives_of_the_traced_jobs():
    trace = {"traced_jobs": 2, "op_seconds": {
        "%all-reduce.3": 0.004, "%all-gather-start.1": 0.001,
        "%all-gather-done.1": 0.002, "%collective-permute.7": 0.001,
        "%reduce-scatter.2": 0.002, "%all-to-all.9": 0.002,
        "%fusion.12": 0.5, "%gather_all-reduce.fusion": 0.1}}
    assert collective_ms.read(_ctx(trace=trace)) == pytest.approx(
        1e3 * 0.012 / 2)
    quiet = {"traced_jobs": 2, "op_seconds": {"%fusion.12": 0.5}}
    assert collective_ms.read(_ctx(trace=quiet)) is None
    assert collective_ms.read(_ctx()) is None


def test_re_slot_imbalance_is_the_fullest_device_over_the_mean():
    assert re_slot_imbalance.read(_ctx(
        counters={"slots_per_device": [10, 10, 10, 10]})) == 1.0
    assert re_slot_imbalance.read(_ctx(
        counters={"slots_per_device": [16, 8, 8, 8]})) == pytest.approx(1.6)
    assert re_slot_imbalance.read(_ctx(counters={"flops": 1.0})) is None
    assert re_slot_imbalance.read(_ctx()) is None
