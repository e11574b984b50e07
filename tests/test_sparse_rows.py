"""A sparse fixed effect that is born on the device (PR 35):
``sparse_rows_to_device`` counts it there, the chooser picks its layout,
and ``CoordinateDescent.run`` fits it like any other fixed effect."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
import scipy.sparse as sp

from photon_ml_tpu import telemetry
from photon_ml_tpu.algorithm.coordinate_descent import CoordinateDescent
from photon_ml_tpu.algorithm.coordinates import FixedEffectCoordinate
from photon_ml_tpu.data.shard_cache import StreamedFixedEffectData
from photon_ml_tpu.ops import features as F
from photon_ml_tpu.ops.glm_objective import GLMBatch
from photon_ml_tpu.optimization.config import GLMOptimizationConfiguration
from photon_ml_tpu.telemetry import scopes
from photon_ml_tpu.types import TaskType
from photon_ml_tpu.utils.compile_cache import (
    compile_ledger,
    enable_compile_cache,
)

TASK = TaskType.LOGISTIC_REGRESSION
OPTIMIZER = "2,1e-12,1.0,1.0,LBFGS,L2"


@pytest.fixture(autouse=True)
def _clean_telemetry():
    telemetry.disable()
    telemetry.reset()
    yield
    telemetry.disable()
    telemetry.reset()


def _prime_below(m: int) -> int:
    return next(p for p in range(m, 1, -1)
                if all(p % q for q in range(2, int(p ** 0.5) + 1)))


def make_rows(rng, n, k, d, fill=1.0):
    """``cols[n, k]`` / ``vals[n, k]`` (numpy): each row stores ``fill`` of
    its slots (the rest padded: value 0 at column 0), its columns distinct
    (so that scipy, which merges a row's repeated column, holds the same
    entries); the last stored slot is the intercept's column d - 1, value
    1; column d - 2 is in no row."""
    p = _prime_below(d - 2)
    start, step = rng.integers(0, p, n), rng.integers(1, p, n)
    cols = (start[:, None] + np.arange(k)[None, :] * step[:, None]) % p
    vals = rng.normal(size=(n, k)).astype(np.float32)
    vals[vals == 0] = 1.0
    stored = np.maximum(1, rng.binomial(k, fill, size=n))
    keep = np.arange(k)[None, :] < stored[:, None]
    cols[np.arange(n), stored - 1] = d - 1
    vals[np.arange(n), stored - 1] = 1.0
    cols = np.where(keep, cols, 0).astype(np.int32)
    vals = np.where(keep, vals, 0.0).astype(np.float32)
    return cols, vals


def as_scipy(cols, vals, d):
    n, k = cols.shape
    rows = np.repeat(np.arange(n), k)
    return sp.coo_matrix((vals.ravel(), (rows, cols.ravel())),
                         shape=(n, d)).tocsr()


# n, k, d, fill, and the layout the chooser must pick: one index operation
# a stored slot against two a non-zero
SHAPES = {
    "uniform": (27000, 40, 5000, 1.0, "slot_major_ell"),
    "three_quarters": (6000, 40, 5000, 0.75, "slot_major_ell"),
    "ragged": (6000, 40, 5000, 0.2, "csr"),
}


@pytest.fixture(scope="module", params=sorted(SHAPES))
def built(request):
    n, k, d, fill, layout = SHAPES[request.param]
    cols, vals = make_rows(np.random.default_rng(7), n, k, d, fill)
    feats = F.sparse_rows_to_device(jnp.asarray(cols), jnp.asarray(vals), d)
    return {"layout": layout, "feats": feats, "cols": cols,
            "vals": vals, "d": d, "mat": as_scipy(cols, vals, d)}


def test_the_chooser_decides_at_three_shapes(built):
    counts = F.layout_counts(built["feats"])
    assert counts.layout == built["layout"]
    kinds = {"csr": F.CSRFeatures,
             "slot_major_ell": F.SlotMajorEllFeatures}
    assert type(built["feats"]) is kinds[built["layout"]]
    # what was counted on the device is what the host counts of the triples
    host = F.layout_counts(F.features_to_device(built["mat"]))
    assert (host.layout, host.nnz) == (counts.layout, counts.nnz)
    n, k = built["cols"].shape
    assert (counts.n_rows, counts.slots_per_row, counts.n_features) == (
        n, k, built["d"])
    assert counts.nnz == int((built["vals"] != 0).sum())
    assert counts.max_col_degree == host.max_col_degree == n  # the intercept
    assert counts.slots == {"csr": counts.nnz,
                            "slot_major_ell": n * k}[built["layout"]]


@pytest.mark.parametrize("product", ["matvec", "rmatvec", "row_sq_matvec",
                                     "sq_rmatvec"])
def test_device_built_products_match_csr_from_scipy(built, product):
    feats, want = built["feats"], F.csr_from_scipy(built["mat"])
    n, d = want.shape
    rng = np.random.default_rng(11)
    by_row = product in ("matvec", "row_sq_matvec")
    vec = jnp.asarray(rng.normal(size=d if by_row else n))
    got = getattr(feats, product)(vec)
    ref = getattr(want, product)(vec)
    # float32's: scipy sums a row's repeated column in the values' dtype
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)
    if not by_row:
        assert float(got[d - 2]) == 0.0  # the column of degree 0
        # the intercept's column: every row's u (sq: times 1 squared)
        assert float(got[d - 1]) == pytest.approx(float(jnp.sum(vec)),
                                                  rel=1e-9)


def test_no_nnz_sized_array_crosses_to_the_host():
    n, k, d = SHAPES["uniform"][:3]
    cols, vals = (jnp.asarray(a) for a in make_rows(
        np.random.default_rng(3), n, k, d))
    w = jnp.ones((d,), jnp.float32)
    both = jax.jit(lambda feats, w: feats.rmatvec(feats.matvec(w)))
    with jax.transfer_guard("disallow"):
        feats = F.sparse_rows_to_device(cols, vals, d)
        jax.block_until_ready(both(feats, w))
    assert F.layout_counts(feats).layout == "slot_major_ell"
    assert feats.cols.shape == (n * k,) and feats.vals.dtype == vals.dtype


def test_a_column_out_of_range_is_refused():
    cols = jnp.asarray([[0, 5], [1, 2]], jnp.int32)
    vals = jnp.ones((2, 2), jnp.float32)
    with pytest.raises(ValueError, match="column ids span"):
        F.sparse_rows_to_device(cols, vals, 5)


def counts(n, k, d, nnz, max_deg):
    return F.LayoutCounts(n_rows=n, slots_per_row=k, n_features=d, nnz=nnz,
                          max_col_degree=max_deg)


@pytest.mark.parametrize("c, want", [
    # the cell's own counts: uniform rows, one column in every row
    (counts(9168123, 40, 1000001, 366724920, 9168123), "slot_major_ell"),
    # ragged rows: a third of the slots stored
    (counts(9168123, 40, 1000001, 122241640, 9168123), "csr"),
    # three quarters of the slots stored: one index operation a slot is
    # still cheaper than two a non-zero
    (counts(4000000, 10, 100000, 30000000, 4000000), "slot_major_ell"),
    # exactly half stored: the two cost the same, the ELL stays
    (counts(1000, 40, 100000, 20000, 1000), "slot_major_ell"),
    # one non-zero fewer than half: the flat triplet
    (counts(1000, 40, 100000, 19999, 1000), "csr"),
])
def test_choose_layout(c, want):
    assert F.choose_layout(c) == want


def test_features_to_device_ends_in_the_same_chooser():
    n, k, d = SHAPES["uniform"][:3]
    cols, vals = make_rows(np.random.default_rng(5), n, k, d)
    mat = as_scipy(cols, vals, d)
    chosen = F.features_to_device(mat)
    assert isinstance(chosen, F.SlotMajorEllFeatures)
    assert F.layout_counts(chosen).layout == "slot_major_ell"
    named = F.features_to_device(mat, sparse_layout="csr")
    assert isinstance(named, F.CSRFeatures)
    v = jnp.asarray(np.random.default_rng(6).normal(size=d))
    np.testing.assert_allclose(np.asarray(chosen.matvec(v)),
                               np.asarray(named.matvec(v)), rtol=1e-5,
                               atol=1e-5)
    ragged = F.features_to_device(as_scipy(*make_rows(
        np.random.default_rng(5), 500, k, d, fill=0.2), d))
    assert isinstance(ragged, F.CSRFeatures)
    assert F.layout_counts(ragged).layout == "csr"
    assert F.layout_counts(named) is None  # the chooser was not asked


# -- the fit through CoordinateDescent.run -------------------------------------


@dataclasses.dataclass
class Problem:
    """The plain arrays the in-repo reference reads."""

    n_rows: int
    n_features: int
    cols: jax.Array
    vals: jax.Array
    labels: jax.Array
    offsets: jax.Array
    weights: jax.Array


CONFIG = {"fixed": {"name": "fixed", "optimizer": OPTIMIZER},
          "link": "logistic"}


@pytest.fixture(scope="module")
def problem():
    n, k, d = SHAPES["uniform"][:3]
    rng = np.random.default_rng(20261002)
    cols, vals = make_rows(rng, n, k, d)
    vals = (vals / np.sqrt(k)).astype(np.float32)
    vals[cols == d - 1] = 1.0
    w_true = rng.normal(size=d)
    margin = (vals * w_true[cols]).sum(axis=1)
    labels = (rng.random(n) < 1 / (1 + np.exp(-margin))).astype(np.float32)
    return Problem(n, d, jnp.asarray(cols), jnp.asarray(vals),
                   jnp.asarray(labels),
                   jnp.asarray(0.1 * rng.normal(size=n), jnp.float32),
                   jnp.asarray(rng.uniform(0.5, 1.5, size=n), jnp.float32))


def descent(p: Problem, feats):
    batch = GLMBatch(feats, p.labels, p.offsets, p.weights)
    coord = FixedEffectCoordinate(
        name="fixed",
        data=StreamedFixedEffectData("global", batch, p.n_rows,
                                     p.n_features, {}),
        feature_shard_id="global", task_type=TASK,
        config=GLMOptimizationConfiguration.parse(OPTIMIZER))
    return CoordinateDescent({"fixed": coord}, TASK), coord


@pytest.fixture(scope="module")
def sparse_fit(problem):
    feats = F.sparse_rows_to_device(problem.cols, problem.vals,
                                    problem.n_features)
    cd, coord = descent(problem, feats)
    result = cd.run(1, seed=1)
    model = result.model.get_model("fixed")
    return {"cd": cd, "coord": coord, "result": result,
            "w": np.asarray(model.glm.coefficients.means),
            "scores": np.asarray(coord.score(model))}


def test_fit_matches_the_in_repo_reference(problem, sparse_fit):
    from benchmark.reference import sparse_glm

    ref = sparse_glm.fit(problem, CONFIG)
    assert ref["stopped"] is None and len(ref["values"]) == 3
    w_ref = np.asarray(ref["coefs"]["fixed"])
    w = sparse_fit["w"]
    assert np.linalg.norm(w - w_ref) / np.linalg.norm(w_ref) < 1e-4
    history = sparse_fit["result"].objective_history
    assert len(history) == 1
    assert history[-1] == pytest.approx(ref["values"][-1], rel=1e-5)
    # the program's objective and scores at ITS coefficients
    assert history[-1] == pytest.approx(
        sparse_glm.value(problem, CONFIG, w), rel=1e-5)
    own = np.asarray(sparse_glm.scores_of(problem, CONFIG, {"fixed": w}))
    assert (np.sqrt(np.mean((sparse_fit["scores"] - own) ** 2)
                    / np.mean(own ** 2)) < 1e-6)
    tracker = sparse_fit["result"].trackers["fixed"][0]
    assert int(np.asarray(tracker.iterations)) == 2  # the cap ends it


def test_fit_matches_the_same_data_densified(problem, sparse_fit):
    rows = jnp.arange(problem.n_rows)[:, None]
    dense = F.DenseFeatures(jnp.zeros(
        (problem.n_rows, problem.n_features), jnp.float32).at[
            rows, problem.cols].add(problem.vals))
    cd, coord = descent(problem, dense)
    result = cd.run(1, seed=1)
    model = result.model.get_model("fixed")
    w = np.asarray(model.glm.coefficients.means)
    assert (np.linalg.norm(sparse_fit["w"] - w) / np.linalg.norm(w)) < 1e-5
    assert result.objective_history[-1] == pytest.approx(
        sparse_fit["result"].objective_history[-1], rel=1e-6)
    np.testing.assert_allclose(sparse_fit["scores"],
                               np.asarray(coord.score(model)), rtol=1e-4,
                               atol=1e-5)


def test_the_product_scopes_are_in_the_lowered_block(sparse_fit):
    cd = sparse_fit["cd"]
    fn = cd._fused_block_fn(1)
    seen = {}

    def recorder(*args):
        seen["args"] = args
        return fn(*args)

    cd._block_fns[1] = recorder
    cd.run(1)
    cd._block_fns[1] = fn
    lowered = fn.lower(*seen["args"])
    text = lowered.as_text(debug_info=True)
    for scope in scopes.FE_PRODUCT_SCOPES:
        assert f"{scope}/" in text or f'/{scope}"' in text, scope
    # in the compiled operations' name paths each product sits under the
    # scope that ran it: both under the solve, the matvec under the score
    import re

    paths = [p.split("/") for p in re.findall(
        r'op_name="([^"]*)"', lowered.compile().as_text())]

    def nested(parent, child):
        return any(parent in p and child in p
                   and p.index(parent) < p.index(child) for p in paths)

    assert nested(scopes.FE_SOLVE, scopes.FE_MATVEC)
    assert nested(scopes.FE_SOLVE, scopes.FE_RMATVEC)
    assert nested(scopes.FE_SCORE, scopes.FE_MATVEC)
    assert not nested(scopes.FE_SCORE, scopes.FE_RMATVEC)


def test_a_dense_fit_opens_no_product_scope(problem):
    dense = F.DenseFeatures(jnp.zeros((64, 8), jnp.float32))
    small = Problem(64, 8, None, None, jnp.zeros((64,), jnp.float32),
                    jnp.zeros((64,), jnp.float32),
                    jnp.ones((64,), jnp.float32))
    cd, _ = descent(small, dense)
    fn = cd._fused_block_fn(1)
    seen = {}
    cd._block_fns[1] = lambda *a: (seen.update(args=a), fn(*a))[1]
    cd.run(1)
    text = fn.lower(*seen["args"]).as_text(debug_info=True)
    assert scopes.FE_SOLVE in text
    assert scopes.FE_MATVEC not in text and scopes.FE_RMATVEC not in text


def test_layout_scope_is_in_the_construction_programs():
    cols = jnp.zeros((4, 2), jnp.int32)
    vals = jnp.ones((4, 2), jnp.float32)
    for text in (
            F._count_rows.lower(cols, vals, n_features=3).as_text(
                debug_info=True),
            F._slot_major.lower(cols).as_text(debug_info=True)):
        assert scopes.FE_LAYOUT in text


def test_gauges_counter_and_ledger_row(problem):
    enable_compile_cache()  # the ledger listens from here
    telemetry.enable()
    feats = F.sparse_rows_to_device(problem.cols, problem.vals,
                                    problem.n_features)
    cd, _ = descent(problem, feats)
    gauges = telemetry.snapshot()["gauges"]
    n, k = problem.cols.shape
    nnz = int((np.asarray(problem.vals) != 0).sum())
    assert gauges[scopes.GAUGE_FE_NNZ] == nnz
    assert gauges[scopes.GAUGE_FE_SLOTS] == n * k
    assert gauges[scopes.GAUGE_FE_MAX_COL_DEGREE] == n
    cd.run(1)
    counters = telemetry.snapshot()["counters"]
    # a solve of 2 iterations: 3 matvec and 3 rmatvec
    assert counters[scopes.COUNTER_FE_PRODUCTS] == 6
    row = compile_ledger()["functions"][scopes.CD_BLOCK]
    assert row["fe_layout"] == "slot_major_ell"


# -- the host path's consumers take the layout the chooser picks ---------------


def uniform_shard(rng, n, k, d):
    """A scipy shard whose rows all store ``k`` of ``d`` columns (density
    under the dense threshold): the chooser lays it slot-major."""
    cols, vals = make_rows(rng, n, k, d)
    return as_scipy(cols, vals, d)


def test_counts_ride_through_jit_tree_map_replace_and_shard_batch(built):
    from photon_ml_tpu.parallel import make_mesh, shard_batch

    feats = built["feats"]
    counts = F.layout_counts(feats)
    assert counts is not None
    assert F.layout_counts(jax.jit(lambda f: f)(feats)) == counts
    assert F.layout_counts(jax.tree_util.tree_map(lambda a: a, feats)
                           ) == counts
    kept = dataclasses.replace(feats, n_features=feats.n_features)
    assert F.layout_counts(kept) == counts
    n = feats.n_rows
    batch = GLMBatch(feats, jnp.zeros((n,)), jnp.zeros((n,)), jnp.ones((n,)))
    sharded = shard_batch(batch, make_mesh(4))
    assert F.layout_counts(sharded.features) == counts
    v = jnp.asarray(np.random.default_rng(2).normal(size=built["d"]))
    np.testing.assert_allclose(
        np.asarray(sharded.features.matvec(v))[:n],
        np.asarray(feats.matvec(v)), rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("feature_sharding", [False, True],
                         ids=["rows", "columns"])
def test_a_mesh_fit_takes_a_shard_the_chooser_lays_slot_major(
        feature_sharding):
    from photon_ml_tpu.data.game_data import GameDataset
    from photon_ml_tpu.parallel import make_mesh

    rng = np.random.default_rng(35)
    n, k, d = 403, 6, 64  # 403: the mesh pads the rows
    mat = uniform_shard(rng, n, k, d)
    w_true = rng.normal(size=d)
    labels = (rng.random(n) < 1 / (1 + np.exp(-(mat @ w_true)))).astype(
        float)
    data = GameDataset.build(responses=labels,
                             feature_shards={"global": mat}, ids={})
    laid = data.fixed_effect_batch("global").features
    assert isinstance(laid, F.SlotMajorEllFeatures)
    assert laid.coded == tuple(range(k))  # d <= the top class: all of them

    def fit(**kw):
        coord = FixedEffectCoordinate(
            name="fixed", data=data, feature_shard_id="global",
            task_type=TASK, dtype=jnp.float64,
            config=GLMOptimizationConfiguration.parse(
                "20,1e-9,1.0,1.0,LBFGS,L2"), **kw)
        result = CoordinateDescent({"fixed": coord}, TASK).run(1, seed=1)
        model = result.model.get_model("fixed")
        return (coord, np.asarray(model.glm.coefficients.means),
                np.asarray(coord.score(model)))

    one, w_one, s_one = fit()
    assert one.sparse_work()[0].layout == "slot_major_ell"
    over, w_mesh, s_mesh = fit(mesh=make_mesh(4),
                               feature_sharding=feature_sharding)
    np.testing.assert_allclose(w_mesh, w_one, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(s_mesh, s_one, rtol=1e-5, atol=1e-6)


def test_the_device_scorer_takes_shards_the_chooser_lays_slot_major():
    from photon_ml_tpu.data.game_data import GameDataset
    from photon_ml_tpu.data.random_effect import (
        RandomEffectDataConfiguration,
        build_random_effect_dataset,
    )
    from photon_ml_tpu.models import (
        Coefficients,
        FixedEffectModel,
        GameModel,
        LogisticRegressionModel,
        RandomEffectModel,
    )
    from photon_ml_tpu.models.device_scoring import DeviceGameScorer

    rng = np.random.default_rng(36)
    n, d, d_user = 120, 64, 32
    data = GameDataset.build(
        responses=(rng.random(n) < 0.5).astype(float),
        feature_shards={"global": uniform_shard(rng, n, 6, d),
                        "user": uniform_shard(rng, n, 3, d_user)},
        ids={"userId": rng.integers(0, 7, n).astype(str)})
    for shard in ("global", "user"):
        laid = F.features_to_device(data.feature_shards[shard])
        assert isinstance(laid, F.SlotMajorEllFeatures)
        assert laid.coded  # d <= the top class: the scorer meets codes
    fe = FixedEffectModel(LogisticRegressionModel(Coefficients(
        jnp.asarray(rng.normal(size=d)))), "global")
    ds = build_random_effect_dataset(
        data, RandomEffectDataConfiguration("userId", "user"),
        intercept_col=d_user - 1)
    re = RandomEffectModel.zeros_like_dataset(ds, dtype=jnp.float64)
    re = re.with_coefs([jnp.asarray(rng.normal(size=np.asarray(c).shape))
                        for c in re.local_coefs])
    gm = GameModel({"fixed": fe, "perUser": re}, TASK)
    got = np.asarray(DeviceGameScorer(gm, data, dtype=jnp.float64).score(gm))
    np.testing.assert_allclose(got, gm.score(data), rtol=1e-10, atol=1e-10)


# -- coded slots: a slot that names few columns is read by code (PR 36), each in
# -- the class its distinct columns fill (PR 39) --------------------------------

TOP = F.CODED_SLOT_TOP_CLASS
W = 1024  # a class in the middle


def fielded_rows(rng, n, d, widths, pad=()):
    """Rows that come field by field, as a click log does: slot f of every
    row names one of ``widths[f]`` columns of its own (a width over
    ``CODED_SLOT_TOP_CLASS`` is a slot the program gathers); the rows of
    ``pad`` leave their slot 0 empty (value 0 at column 0)."""
    k = len(widths)
    cols = np.empty((n, k), np.int32)
    for f, width in enumerate(widths):
        own = rng.choice(d, size=width, replace=False)
        draw = rng.integers(0, width, n)
        draw[:width] = np.arange(width)  # every one of them is named
        cols[:, f] = own[draw]
    vals = rng.normal(size=(n, k)).astype(np.float32)
    vals[vals == 0] = 1.0
    for i in pad:
        cols[i, 0], vals[i, 0] = 0, 0.0
    return cols, vals


# a slot of exactly a class's width, and of one more: at the least class, in
# the middle, and at the top, where one more is a slot the program gathers
WIDTHS = (1, 64, TOP + 1, W, W + 1, 3, 128, 129, 7, TOP, 4096, 4097)
WANT_CODED = (0, 1, 3, 4, 5, 6, 7, 8, 9, 10, 11)
WANT_CLASSES = (128, 128, W, 2 * W, 128, 128, 256, 128, TOP, 4096, 8192)


@pytest.fixture(scope="module", params=["float32", "bfloat16"])
def coded(request):
    n, d = TOP + 2 * W, 2 * TOP
    cols, vals = fielded_rows(np.random.default_rng(36), n, d, WIDTHS,
                              pad=(5, 17, n - 1))
    vals = jnp.asarray(vals).astype(request.param)
    feats = F.sparse_rows_to_device(jnp.asarray(cols), vals, d)
    plain = dataclasses.replace(feats, codes=None, dicts=None, coded=(),
                                classes=())
    return {"feats": feats, "plain": plain, "cols": cols,
            "vals": np.asarray(vals.astype(jnp.float32)), "n": n, "d": d}


def test_a_slot_of_exactly_a_class_gets_it_and_one_more_the_next(coded):
    feats = coded["feats"]
    assert feats.coded == WANT_CODED  # TOP is in, TOP + 1 is gathered
    assert feats.classes == WANT_CLASSES
    counts = F.layout_counts(feats)
    assert counts.coded_slots == len(WANT_CODED)
    assert counts.coded_entries == sum(WANT_CLASSES)
    assert feats.dicts.shape == (sum(WANT_CLASSES),)
    assert feats.codes.dtype == jnp.uint16
    assert feats.codes.shape == (
        len(WANT_CODED), F._code_stride(coded["n"]) // 128, 128)
    # cols and vals stay whole: what every other reader of the layout reads
    k = len(WIDTHS)
    assert feats.cols.shape == feats.vals.shape == (coded["n"] * k,)
    np.testing.assert_array_equal(
        np.asarray(feats.cols).reshape(k, -1).T, coded["cols"])


@pytest.mark.parametrize("distinct,want", [
    (1, 128), (128, 128), (129, 256), (1459, 2048), (2048, 2048),
    (2049, 4096), (14991, 16384), (TOP - 1, TOP), (TOP, TOP)])
def test_a_slots_class_is_the_least_power_of_two_that_holds_it(distinct,
                                                               want):
    assert F._slot_class(distinct) == want


@pytest.mark.parametrize("j", range(len(WANT_CODED)),
                         ids=[f"class{c}" for c in WANT_CLASSES])
def test_what_a_coded_slot_looks_up_is_bitwise_what_the_gather_fetches(
        coded, j):
    feats, n = coded["feats"], coded["n"]
    v = jnp.asarray(np.random.default_rng(1).normal(size=coded["d"]),
                    jnp.float32)
    start = sum(feats.classes[:j])
    table = v[feats.dicts[start:start + feats.classes[j]]]
    found = np.asarray(F._lookup(feats.codes, table, jnp.full((1,), j, jnp.int32))).reshape(-1)[:n]
    fetched = np.asarray(v[coded["cols"][:, feats.coded[j]]])
    assert found.tobytes() == fetched.tobytes()


def test_padded_rows_carry_column_zeros_code_and_a_code_past_the_table_reads_0(
        coded):
    feats, n = coded["feats"], coded["n"]
    # the padded rows of slot 0 (value 0 at column 0) carry column 0's code
    assert int(feats.dicts[0]) == 0
    flat = feats.codes.reshape(-1)
    assert [int(flat[i]) for i in (5, 17, n - 1)] == [0, 0, 0]
    code = jnp.asarray(np.arange(32 * 128).reshape(1, 32, 128), jnp.uint16)
    table = jnp.arange(1.0, 257.0, dtype=jnp.float32)
    got = np.asarray(F._lookup(code, table, jnp.zeros((1,), jnp.int32))).reshape(-1)
    np.testing.assert_array_equal(got[:256], np.asarray(table))
    assert not got[256:].any()


@pytest.mark.parametrize("product", ["matvec", "row_sq_matvec"])
def test_row_products_with_coded_slots_equal_the_gathered_ones(coded,
                                                               product):
    v = jnp.asarray(np.random.default_rng(2).normal(size=coded["d"]),
                    jnp.float32)
    got = np.asarray(getattr(coded["feats"], product)(v))
    want = np.asarray(getattr(coded["plain"], product)(v))
    assert got.dtype == want.dtype == np.float32
    # the same terms in the same order, each rounded as the gathered loop
    # rounds it (``_add_term``): bitwise, off the TPU as on it
    assert got.tobytes() == want.tobytes()
    jitted = jax.jit(lambda f, v: getattr(f, product)(v))
    assert (np.asarray(jitted(coded["feats"], v)).tobytes()
            == np.asarray(jitted(coded["plain"], v)).tobytes())
    # and both are the product: against float64 on the host
    x = coded["vals"] ** 2 if product == "row_sq_matvec" else coded["vals"]
    terms = np.abs(x * np.asarray(v)[coded["cols"]])
    exact = (x.astype(np.float64)
             * np.asarray(v, np.float64)[coded["cols"]]).sum(axis=1)
    np.testing.assert_allclose(got, exact, rtol=0,
                               atol=4e-6 * terms.sum(axis=1).max())


def test_column_products_and_the_triplet_ignore_the_codes(coded):
    feats, plain = coded["feats"], coded["plain"]
    u = jnp.asarray(np.random.default_rng(3).normal(size=coded["n"]),
                    jnp.float32)
    for product in ("rmatvec", "sq_rmatvec"):
        assert (np.asarray(getattr(feats, product)(u)).tobytes()
                == np.asarray(getattr(plain, product)(u)).tobytes())
    csr = feats.to_csr()
    assert isinstance(csr, F.CSRFeatures)
    assert F.layout_counts(csr).coded_slots == len(WANT_CODED)
    v = jnp.asarray(np.random.default_rng(4).normal(size=coded["d"]),
                    jnp.float32)
    np.testing.assert_allclose(np.asarray(csr.matvec(v)),
                               np.asarray(feats.matvec(v)), rtol=1e-5,
                               atol=1e-5)


def test_autodiff_through_coded_slots_is_the_transposed_product(coded):
    """What TRON and OWL-QN do (``jax.value_and_grad`` of the objective,
    ``jax.jvp`` of that gradient): the coded slots differentiate to the
    column-wise product, as the gathered ones do."""
    feats, plain = coded["feats"], coded["plain"]
    rng = np.random.default_rng(6)
    v = jnp.asarray(rng.normal(size=coded["d"]), jnp.float32)
    u = jnp.asarray(rng.normal(size=coded["n"]), jnp.float32)

    def loss(f, v):
        return jnp.sum(jnp.tanh(f.matvec(v)) * u)

    grad = jax.jit(jax.grad(loss, argnums=1))
    got, want = np.asarray(grad(feats, v)), np.asarray(grad(plain, v))
    np.testing.assert_allclose(got, want, rtol=1e-4,
                               atol=1e-5 * np.abs(want).max())
    linear = np.asarray(jax.grad(lambda v: jnp.sum(feats.matvec(v) * u))(v))
    np.testing.assert_allclose(linear, np.asarray(feats.rmatvec(u)),
                               rtol=1e-4, atol=1e-5 * np.abs(linear).max())

    def hvp(f):
        return jax.jvp(lambda w: jax.grad(loss, argnums=1)(f, w), (v,),
                       (v,))[1]

    np.testing.assert_allclose(np.asarray(hvp(feats)),
                               np.asarray(hvp(plain)), rtol=1e-3,
                               atol=1e-4 * float(jnp.abs(hvp(plain)).max()))


@pytest.mark.parametrize("product", ["matvec", "row_sq_matvec"])
def test_vmap_over_the_vector_runs_the_coded_slots_a_vector_at_a_time(
        coded, product):
    """The module's promise (vmap-safe products) with a kernel call inside:
    a batch of vectors is a batch of tables against the same codes."""
    feats, plain = coded["feats"], coded["plain"]
    vs = jnp.asarray(np.random.default_rng(7).normal(size=(3, coded["d"])),
                     jnp.float32)
    got = np.asarray(jax.jit(jax.vmap(getattr(feats, product)))(vs))
    assert got.shape == (3, coded["n"])
    for b in range(3):
        want = np.asarray(getattr(plain, product)(vs[b]))
        np.testing.assert_allclose(got[b], want, rtol=1e-5,
                                   atol=1e-5 * np.abs(want).max())
    # and differentiated under the batch: a gradient a vector
    u = jnp.asarray(np.random.default_rng(8).normal(size=coded["n"]),
                    jnp.float32)

    def loss(f, v):
        return jnp.sum(jnp.tanh(getattr(f, product)(v)) * u)

    grads = np.asarray(jax.vmap(jax.grad(lambda v: loss(feats, v)))(vs))
    for b in range(3):
        want = np.asarray(jax.grad(lambda v: loss(plain, v))(vs[b]))
        np.testing.assert_allclose(grads[b], want, rtol=1e-4,
                                   atol=1e-5 * np.abs(want).max())


def test_a_scan_around_coded_products_differentiates_once_either_way(coded):
    """``SlotMajorEllFeatures``' stated restriction: inside a staged loop
    the coded slots differentiate once, forward or backward; differentiated
    TWICE there the lookup's own rule is lost (JAX keeps none through a
    loop's partial evaluation) and the kernel itself would be
    differentiated, which raises rather than answer wrongly. Derivatives
    taken inside a loop's body are fine at any order."""
    feats, plain = coded["feats"], coded["plain"]
    rng = np.random.default_rng(9)
    v = jnp.asarray(0.1 * rng.normal(size=coded["d"]), jnp.float32)
    t = jnp.asarray(0.1 * rng.normal(size=coded["d"]), jnp.float32)
    u = jnp.asarray(rng.normal(size=coded["n"]), jnp.float32)

    def descended(f, v):
        def step(v, _):
            return v - 1e-5 * f.rmatvec(jnp.tanh(f.matvec(v)) * u), None

        return jnp.sum(jax.lax.scan(step, v, None, length=3)[0] ** 2)

    def close(got, want):
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), rtol=1e-4,
            atol=1e-5 * float(jnp.abs(want).max()))

    close(jax.grad(lambda v: descended(feats, v))(v),
          jax.grad(lambda v: descended(plain, v))(v))
    close(jax.jvp(lambda v: descended(feats, v), (v,), (t,))[1],
          jax.jvp(lambda v: descended(plain, v), (v,), (t,))[1])

    def hvp(f):
        return jax.jvp(jax.grad(lambda v: descended(f, v)), (v,), (t,))[1]

    # derivatives taken INSIDE a loop's body are not through the loop: a
    # Hessian-vector product a step, as TRON's conjugate gradients take
    def inside(f):
        def loss(w):
            return jnp.sum(jnp.tanh(f.matvec(w)) * u)

        return jax.lax.fori_loop(0, 3, lambda _, t: t - 1e-3 * jax.jvp(
            jax.grad(loss), (v,), (t,))[1], t)

    close(inside(feats), inside(plain))
    try:
        twice = hvp(feats)
    except NotImplementedError:
        return
    close(twice, hvp(plain))


def test_codes_ride_through_jit_tree_map_and_replace(coded):
    feats = coded["feats"]
    for other in (jax.jit(lambda f: f)(feats),
                  jax.tree_util.tree_map(lambda a: a, feats),
                  dataclasses.replace(feats, n_features=feats.n_features)):
        assert other.coded == feats.coded
        assert other.counts == feats.counts
        assert (np.asarray(other.codes).tobytes()
                == np.asarray(feats.codes).tobytes())
        np.testing.assert_array_equal(np.asarray(other.dicts),
                                      np.asarray(feats.dicts))
    leaves, tree = jax.tree_util.tree_flatten(feats)
    assert len(leaves) == 4
    assert len(jax.tree_util.tree_leaves(coded["plain"])) == 2
    again = jax.tree_util.tree_unflatten(tree, leaves)
    assert again.coded == feats.coded and again.n_rows == feats.n_rows


def _parent_by_row(cols, vals, v, n, k):
    """``SlotMajorEllFeatures._by_row`` as it stood before any slot was
    coded (PR 35), operation for operation."""
    acc = jnp.promote_types(v.dtype, jnp.float32)

    def body(s, out):
        c = jax.lax.dynamic_slice(cols, (s * n,), (n,))
        x = jax.lax.dynamic_slice(vals, (s * n,), (n,)).astype(acc)
        return out + x * v.at[c].get(mode="promise_in_bounds")

    return jax.lax.fori_loop(0, k, body, jnp.zeros((n,), acc))


def test_a_matrix_with_no_small_slot_runs_the_loop_it_ran_before():
    n, d = TOP + 2 * W, 2 * TOP
    widths = (TOP + 300, TOP + 500, TOP + 1, TOP + 900)
    cols, vals = fielded_rows(np.random.default_rng(8), n, d, widths)
    feats = F.sparse_rows_to_device(jnp.asarray(cols), jnp.asarray(vals), d)
    assert feats.coded == () and feats.codes is None and feats.dicts is None
    counts = F.layout_counts(feats)
    assert counts.coded_slots == counts.coded_entries == 0
    v = jnp.zeros((d,), jnp.float32)
    k = len(widths)
    now = jax.jit(lambda cols, vals, v: F.SlotMajorEllFeatures(
        cols, vals, n, d).matvec(v)).lower(feats.cols, feats.vals, v)
    then = jax.jit(lambda cols, vals, v: _parent_by_row(
        cols, vals, v, n, k)).lower(feats.cols, feats.vals, v)
    assert now.as_text() == then.as_text()
    # with a coded slot the text differs, and names both parts
    cols[:, 0] = 7
    some = F.sparse_rows_to_device(jnp.asarray(cols), jnp.asarray(vals), d)
    assert some.coded == (0,) and some.classes == (128,)
    text = jax.jit(lambda f, v: f.matvec(v)).lower(some, v).as_text(
        debug_info=True)
    for part in scopes.FE_MATVEC_PARTS:
        assert part in text
    assert scopes.FE_MATVEC_CODED not in now.as_text(debug_info=True)


def test_both_constructors_code_the_same_slots():
    """Rows whose columns ascend along the slots, so that scipy's sorted
    rows keep every entry in its slot: the device path and the host path
    (``features_to_device`` -> ``lay_out_triplet``) must agree."""
    n = TOP + 2 * W
    rng = np.random.default_rng(9)
    widths = (1, 30, TOP + 200, W, TOP, TOP + 1, 1)
    d = 2 * TOP * len(widths)
    cols = np.empty((n, len(widths)), np.int32)
    for f, width in enumerate(widths):  # field f owns columns [2 TOP f, ...)
        draw = rng.integers(0, width, n)
        draw[:width] = np.arange(width)
        cols[:, f] = 2 * TOP * f + draw
    vals = rng.normal(size=cols.shape).astype(np.float32)
    vals[vals == 0] = 1.0
    born = F.sparse_rows_to_device(jnp.asarray(cols), jnp.asarray(vals), d)
    host = F.features_to_device(as_scipy(cols, vals, d))
    assert isinstance(host, F.SlotMajorEllFeatures)
    assert born.coded == host.coded == (0, 1, 3, 4, 6)
    assert born.classes == host.classes == (128, 128, W, TOP, 128)
    assert F.layout_counts(born) == F.layout_counts(host)
    np.testing.assert_array_equal(np.asarray(born.dicts),
                                  np.asarray(host.dicts))
    assert (np.asarray(born.codes).tobytes()
            == np.asarray(host.codes).tobytes())


def test_shard_batch_and_the_mesh_fit_hold_with_coded_slots(coded):
    from photon_ml_tpu.parallel import make_mesh, shard_batch

    feats, n = coded["feats"], coded["n"]
    batch = GLMBatch(feats, jnp.zeros((n,)), jnp.zeros((n,)), jnp.ones((n,)))
    sharded = shard_batch(batch, make_mesh(4))
    assert isinstance(sharded.features, F.CSRFeatures)
    v = jnp.asarray(np.random.default_rng(5).normal(size=coded["d"]),
                    jnp.float32)
    np.testing.assert_allclose(
        np.asarray(sharded.features.matvec(v))[:n],
        np.asarray(feats.matvec(v)), rtol=1e-5, atol=1e-5)


def test_the_gauges_and_the_ledger_row_read_the_coded_counts(problem):
    enable_compile_cache()
    telemetry.enable()
    # ``problem``'s rows are full and end in the intercept: one coded slot
    # more than before PR 39, when a slot of over 1,024 columns was gathered
    feats = F.sparse_rows_to_device(problem.cols, problem.vals,
                                    problem.n_features)
    k = problem.cols.shape[1]
    assert feats.coded[-1] == k - 1 and feats.classes[-1] == 128
    slots, entries = len(feats.coded), sum(feats.classes)
    cd, coord = descent(problem, feats)
    counts = coord.sparse_work()[0]
    assert (counts.coded_slots, counts.coded_entries) == (slots, entries)
    gauges = telemetry.snapshot()["gauges"]
    assert gauges[scopes.GAUGE_FE_CODED_SLOTS] == slots
    assert gauges[scopes.GAUGE_FE_CODED_ENTRIES] == entries
    cd.run(1)
    row = compile_ledger()["functions"][scopes.CD_BLOCK]
    assert (row["fe_layout"], row["fe_coded_slots"],
            row["fe_coded_entries"]) == ("slot_major_ell", slots, entries)
    # ... and 0 where no slot is small
    telemetry.reset()
    n, d = TOP + 10, 3 * TOP
    cols, vals = fielded_rows(np.random.default_rng(10), n, d,
                              (TOP + 1, TOP + 2))
    none = F.sparse_rows_to_device(jnp.asarray(cols), jnp.asarray(vals), d)
    p = Problem(n, d, None, None, jnp.zeros((n,), jnp.float32),
                jnp.zeros((n,), jnp.float32), jnp.ones((n,), jnp.float32))
    descent(p, none)
    gauges = telemetry.snapshot()["gauges"]
    assert gauges[scopes.GAUGE_FE_CODED_SLOTS] == 0
    assert gauges[scopes.GAUGE_FE_CODED_ENTRIES] == 0


def test_two_seeds_of_the_recipe_share_every_compiled_program():
    """The cell's own rows, all 40 slots, at 100,000: enough rows that the
    eight widest fields name more columns than the top class holds and are
    gathered, and that each of the 32 others fills the class it fills at
    the cell's 9.2M rows (the seven wide ones name 2,168 to 11,447 columns
    here, all their 1,459 to 14,991 there). Every seed codes the same
    slots in the same classes, and the dictionaries are padded to the
    classes, so nothing compiles for the second seed: not at construction,
    not the products."""
    import json
    import pathlib

    from benchmark.recipes import sparse_glm as recipe

    root = pathlib.Path(__file__).resolve().parent.parent
    config = recipe.scale_down(json.loads(
        (root / "benchmark/configs/sparse-lr-criteo.json").read_text()),
        100000)
    # a field draws ranks 1 .. card - 1; the last slot is the intercept
    named = [card - 1 for card in config["fixed"]["fields"]] + [1]
    want = tuple(f for f, count in enumerate(named) if count <= TOP)
    classes = tuple(F._slot_class(named[f]) for f in want)
    assert len(want) == 32 and sum(classes) == 64768
    assert sum(1 for f in want if named[f] <= 1024) == 25  # coded at PR 36
    matvec = jax.jit(lambda f, w: f.matvec(w))
    programs = (F._slot_dictionaries, F._slot_codes, F._cut_to_classes,
                F._slot_major, F._count_rows, F._lookup_call, F._add_term,
                matvec)
    sizes = []
    for seed in (2147486611, 2147486612):
        p = recipe.make(config, seed)
        feats = F.sparse_rows_to_device(p.cols, p.vals, p.n_features)
        assert feats.coded == want and feats.classes == classes
        counts = F.layout_counts(feats)
        assert (counts.coded_slots, counts.coded_entries) == (32, 64768)
        jax.block_until_ready(matvec(feats, jnp.zeros((p.n_features,),
                                                      jnp.float32)))
        sizes.append([fn._cache_size() for fn in programs])
        # no count lies near a class's edge, here as at the cell's size:
        # another seed's few columns more or fewer move no class
        cols = np.asarray(p.cols)
        for f in want:
            here = len(np.unique(cols[:, f]))
            for count in (here, named[f]):
                if count > 128:
                    assert 1.05 * (classes[want.index(f)] // 2) < count
                    assert count < classes[want.index(f)] / 1.05
        for f in set(range(len(named))) - set(want):
            assert len(np.unique(cols[:, f])) > 1.05 * TOP
    assert sizes[0] == sizes[1]
