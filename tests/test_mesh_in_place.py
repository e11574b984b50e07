"""A fit whose data already lies over a device mesh (PR 31): ``shard_batch``
and ``shard_block`` keep the buffers they are handed and never build a
whole (or whole padded) array on one device; the 4-device fit of the
benchmark's four-chip cell, at a tiny size on 4 of the 8 virtual devices,
agrees with the plain reference inside that cell's own limits and with the
one-device fit of the same problem; the gauges and the compile ledger say
what lies where."""


import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from photon_ml_tpu import telemetry
from photon_ml_tpu.data.random_effect import EntityBlock
from photon_ml_tpu.ops.features import CSRFeatures, DenseFeatures
from photon_ml_tpu.ops.glm_objective import GLMBatch
from photon_ml_tpu.parallel import make_mesh, shard_batch, shard_block
from photon_ml_tpu.telemetry import scopes
from photon_ml_tpu.utils import compile_cache

K = 4


def _pointers(a):
    return [s.data.unsafe_buffer_pointer() for s in a.addressable_shards]


def _dense_batch(n, d=3, put=np.asarray):
    rng = np.random.default_rng(n)
    return GLMBatch(DenseFeatures(put(rng.normal(size=(n, d)).astype(np.float32))),
                    put(rng.random(n).astype(np.float32)),
                    put(np.zeros(n, np.float32)), put(np.ones(n, np.float32)))


def _block(e, r=4, d=8, sentinel=99, put=np.asarray):
    rng = np.random.default_rng(e)
    return EntityBlock(
        put(rng.normal(size=(e, r, d)).astype(np.float32)),
        put(rng.random((e, r)).astype(np.float32)),
        put(np.zeros((e, r), np.float32)), put(np.ones((e, r), np.float32)),
        put(rng.integers(0, sentinel, (e, r)).astype(np.int32)),
        put(np.tile(np.arange(d, dtype=np.int32), (e, 1))))


# -- (c) in place, and shard by shard ------------------------------------------

def test_a_batch_already_over_the_mesh_keeps_its_buffers():
    mesh = make_mesh(K)
    once = shard_batch(_dense_batch(40), mesh)
    again = shard_batch(once, mesh)
    for a, b in zip(jax.tree.leaves(once), jax.tree.leaves(again)):
        assert b is a or _pointers(b) == _pointers(a)
    assert _pointers(again.features.x) == _pointers(once.features.x)


def test_a_block_already_over_the_mesh_keeps_its_buffers():
    mesh = make_mesh(K)
    once = shard_block(_block(8), mesh, sentinel_row=99)
    again = shard_block(once, mesh, sentinel_row=99)
    for a, b in zip(jax.tree.leaves(once), jax.tree.leaves(again)):
        assert _pointers(b) == _pointers(a)


@pytest.mark.parametrize("source, n", [
    ("host", 37), ("host", 40), ("host", 41), ("one_device", 37),
    ("one_device", 40), ("one_device", 41), ("other_mesh", 38),
    ("other_mesh", 40)])
def test_no_step_holds_more_than_a_shard(monkeypatch, source, n):
    """Rows that are no multiple of 4: every shard is cut, padded and
    placed alone; the values are what padding the whole array gave."""
    mesh = make_mesh(K)
    per = -(-n // K)
    batch = _dense_batch(n, put=np.asarray if source == "host"
                         else jnp.asarray)
    if source == "other_mesh":
        two = NamedSharding(make_mesh(2), P("data"))
        batch = jax.tree.map(lambda a: jax.device_put(a, two), batch)
    want = {k: np.asarray(v) for k, v in zip(
        "xlow", jax.tree.leaves(batch))}
    seen = []
    for lib in (np, jnp):
        real = lib.pad
        monkeypatch.setattr(lib, "pad", lambda a, *args, _real=real, **kw: (
            seen.append(np.shape(a)[0]), _real(a, *args, **kw))[1])
    out = shard_batch(batch, mesh)
    assert all(rows <= per for rows in seen), seen
    for leaf in jax.tree.leaves(out):
        assert leaf.shape[0] == K * per
        assert {s.data.shape[0] for s in leaf.addressable_shards} == {per}
        assert len({s.device for s in leaf.addressable_shards}) == K
    got = dict(zip("xlow", jax.tree.leaves(out)))
    for key, full in want.items():
        np.testing.assert_array_equal(np.asarray(got[key])[:n], full)
        np.testing.assert_array_equal(np.asarray(got[key])[n:], 0)


@pytest.mark.parametrize("source", ["host", "one_device"])
def test_a_block_is_padded_with_empty_entities_shard_by_shard(source):
    mesh = make_mesh(K)
    put = np.asarray if source == "host" else jnp.asarray
    block = _block(6, put=put)
    out = shard_block(block, mesh, sentinel_row=99)
    assert out.num_entities == 8
    assert {s.data.shape[0] for s in out.x.addressable_shards} == {2}
    np.testing.assert_array_equal(np.asarray(out.x)[:6], np.asarray(block.x))
    np.testing.assert_array_equal(np.asarray(out.row_ids)[6:], 99)
    np.testing.assert_array_equal(np.asarray(out.feat_idx)[6:], -1)
    np.testing.assert_array_equal(np.asarray(out.weights)[6:], 0)


def test_csr_streams_are_laid_out_the_same_way():
    mesh = make_mesh(K)
    vals = np.arange(1, 8, dtype=np.float32)
    feats = CSRFeatures(values=vals, col_ids=np.arange(7, dtype=np.int32) % 3,
                        row_ids=np.arange(7, dtype=np.int32) % 5, n_rows=5,
                        n_features=3)
    ones = np.ones(5, np.float32)
    out = shard_batch(GLMBatch(feats, ones, ones, ones), mesh)
    assert out.features.values.shape == (8,) and out.labels.shape == (8,)
    assert out.features.n_rows == 8
    np.testing.assert_array_equal(np.asarray(out.features.values)[7:], 0)


def test_the_streamed_batch_reads_its_dtype_without_a_fetch(monkeypatch):
    from photon_ml_tpu.data.shard_cache import StreamedFixedEffectData

    batch = jax.tree.map(jnp.asarray, _dense_batch(12))
    data = StreamedFixedEffectData("global", batch, 12, 3, {})
    monkeypatch.setattr(jax.Array, "__array__", lambda *a, **k: (
        _ for _ in ()).throw(AssertionError("fetched to the host")),
        raising=False)
    assert data.fixed_effect_batch("global", dtype=jnp.float32) is batch
    with pytest.raises(ValueError, match="assembled as float32"):
        data.fixed_effect_batch("global", dtype=jnp.bfloat16)


# -- (a), (b), (d): the four-chip cell at a tiny size ---------------------------

CELL = "glmix-20m.fit4"
TINY_ROWS = 6000


@pytest.fixture(scope="module")
def cell():
    from benchmark import harness
    from benchmark.recipes import dense_glm_mesh

    loaded = harness.load_cell(CELL)
    return (dense_glm_mesh.scale_down(loaded["config"], TINY_ROWS),
            loaded["workload"])


def _fit(config, workload, devices, seed=31):
    from benchmark.jobs import cd_fit_mesh
    from benchmark.recipes import dense_glm_mesh

    with jax.enable_x64(False):  # the cell is a float32 configuration
        problem = dense_glm_mesh.make(config, seed, devices=devices)
        job = cd_fit_mesh.build(config, workload, problem)
        job.warm_up(seed)
        window = job.window(0.0, seed)
        job.after_window(window)
        counters = job.counters(window)
    return problem, job, window, counters


@pytest.fixture(scope="module")
def four(cell):
    compile_cache._listen()
    compile_cache.reset_compile_ledger()
    telemetry.reset()
    telemetry.enable()
    try:
        out = _fit(*cell, jax.devices()[:K])
        gauges = telemetry.snapshot()["gauges"]
    finally:
        telemetry.disable()
        telemetry.reset()
    return out + (gauges, compile_cache.compile_ledger())


def test_four_device_fit_is_inside_the_cells_own_limits(cell, four):
    from benchmark.checks import cd_fit_mesh as check

    problem, _, window, _ = four[:4]
    with jax.enable_x64(False):
        compared = check.check(problem, cell[0], cell[1], window)
    assert set(compared) == set(cell[1]["compare"])
    for name, v in compared.items():
        assert v["value"] <= v["limit"], (name, v)


def test_four_device_fit_agrees_with_the_one_device_fit(cell, four):
    window4 = four[2]
    problem1, _, window1, counters1 = _fit(*cell, jax.devices()[:1])
    assert counters1["devices"] == 1 and problem1.n_rows == problem1.true_rows
    np.testing.assert_allclose(window4["histories"][0],
                               window1["histories"][0], rtol=2e-4)
    a4, a1 = window4["kept"]["last"], window1["kept"]["last"]
    np.testing.assert_allclose(a4["coefs"]["fixed"], a1["coefs"]["fixed"],
                               rtol=0, atol=2e-3)
    n = problem1.true_rows
    np.testing.assert_allclose(np.asarray(a4["scores"])[:n],
                               np.asarray(a1["scores"])[:n], atol=5e-2)
    for got, want in zip(a4["coefs"]["perUser"], a1["coefs"]["perUser"]):
        e = want.shape[0]  # the four-device class is filled to a multiple of 4
        np.testing.assert_allclose(np.asarray(got)[:e], want, atol=2e-2)
        np.testing.assert_array_equal(np.asarray(got)[e:], 0)


def test_the_program_kept_the_buffers_the_recipe_made(four):
    problem, job, _, counters = four[:4]
    assert counters["buffers_kept"] is True
    assert counters["devices"] == K
    assert counters["rows_per_device"] == [problem.n_rows // K] * K
    assert len(set(counters["slots_per_device"])) == 1


def test_gauges_say_what_each_device_holds(four):
    problem, counters, gauges = four[0], four[3], four[4]
    assert gauges[scopes.GAUGE_MESH_DEVICES] == K
    assert gauges[scopes.GAUGE_MESH_ROWS_PER_DEVICE] == problem.n_rows // K
    assert gauges[scopes.GAUGE_RE_SLOTS_PER_DEVICE_MAX] == max(
        counters["slots_per_device"])
    assert gauges[scopes.GAUGE_RE_SLOTS_PER_DEVICE_MEAN] == pytest.approx(
        sum(counters["slots_per_device"]) / K)
    assert gauges[scopes.GAUGE_RE_SLOTS] == sum(counters["slots_per_device"])


def test_the_ledger_says_how_many_partitions(four):
    ledger = four[5]
    assert ledger["functions"][scopes.CD_BLOCK]["partitions"] == K
    assert ledger["functions"]["_solve_fixed"]["partitions"] == 1


def test_without_a_mesh_no_mesh_gauge_is_set_and_the_row_reads_one():
    from tests.test_coordinate_descent import build_coordinates, make_glmix_data
    from photon_ml_tpu.algorithm import CoordinateDescent
    from photon_ml_tpu.types import TaskType

    compile_cache._listen()
    compile_cache.reset_compile_ledger()
    telemetry.reset()
    telemetry.enable()
    try:
        data = make_glmix_data(np.random.default_rng(3))[0]
        cd = CoordinateDescent(build_coordinates(data),
                               TaskType.LOGISTIC_REGRESSION)
        cd.run(1)
        set_calls = [telemetry.gauge(name).calls for name in (
            scopes.GAUGE_MESH_DEVICES, scopes.GAUGE_MESH_ROWS_PER_DEVICE,
            scopes.GAUGE_RE_SLOTS_PER_DEVICE_MAX,
            scopes.GAUGE_RE_SLOTS_PER_DEVICE_MEAN)]
        assert telemetry.gauge(scopes.GAUGE_RE_SLOTS).calls == 1
    finally:
        telemetry.disable()
        telemetry.reset()
    assert set_calls == [0, 0, 0, 0]
    row = compile_cache.compile_ledger()["functions"][scopes.CD_BLOCK]
    assert row["partitions"] == 1
