"""Data layer tests: GameDataset, random-effect bucketing, sampling,
LibSVM ingest, stats, validators.

Mirrors the reference's data-tier tests (LocalDataSetTest,
RandomEffectDataSetTest + integration builders in GameTestUtils).
"""

import numpy as np
import jax.numpy as jnp
import pytest
import scipy.sparse as sp

from photon_ml_tpu.data.game_data import GameDataset
from photon_ml_tpu.data.libsvm import read_libsvm
from photon_ml_tpu.data.random_effect import (
    RandomEffectDataConfiguration,
    build_random_effect_dataset,
    pearson_correlation_scores,
)
from photon_ml_tpu.data.sampling import (
    binary_classification_down_sampler,
    reservoir_sample,
)
from photon_ml_tpu.data.stats import BasicStatisticalSummary
from photon_ml_tpu.data.validators import validate_data
from photon_ml_tpu.types import DataValidationType, TaskType

import jax


def _toy_game_data(rng, n=60, d=10, n_users=7):
    x = sp.random(n, d, density=0.4, random_state=3, format="csr")
    x[:, d - 1] = 1.0  # intercept
    users = rng.integers(0, n_users, n)
    y = (rng.random(n) < 0.5).astype(float)
    return GameDataset.build(
        responses=y,
        feature_shards={"shard": sp.csr_matrix(x)},
        ids={"userId": np.asarray([f"u{u}" for u in users])},
        offsets=rng.normal(0, 0.1, n),
        weights=rng.random(n) + 0.5,
    )


def test_game_dataset_build_and_codes(rng):
    data = _toy_game_data(rng)
    col = data.id_columns["userId"]
    assert col.num_entities <= 7
    # codes round-trip through the vocabulary
    names = col.vocabulary[col.codes]
    assert names[0].startswith("u")
    batch = data.fixed_effect_batch("shard")
    assert batch.num_rows == data.num_rows


def test_random_effect_blocks_cover_all_rows(rng):
    data = _toy_game_data(rng)
    cfg = RandomEffectDataConfiguration("userId", "shard")
    ds = build_random_effect_dataset(data, cfg, intercept_col=9)
    # Every row appears exactly once across active blocks (no cap set).
    seen = np.concatenate([
        np.asarray(b.row_ids).ravel() for b in ds.blocks])
    seen = seen[seen < ds.n_rows]
    assert sorted(seen) == list(range(data.num_rows))
    assert ds.num_entities == data.id_columns["userId"].num_entities
    # Block features match the original matrix through the gather map.
    b = ds.blocks[0]
    mat = data.feature_shards["shard"].toarray()
    for e in range(b.num_entities):
        fidx = np.asarray(b.feat_idx[e])
        valid_cols = fidx >= 0
        for r in range(b.n_pad):
            gr = int(b.row_ids[e, r])
            if gr == ds.n_rows:
                assert float(b.weights[e, r]) == 0.0
                continue
            np.testing.assert_allclose(
                np.asarray(b.x[e, r])[valid_cols], mat[gr, fidx[valid_cols]])


def test_random_effect_active_cap_and_passive(rng):
    data = _toy_game_data(rng, n=200, n_users=4)
    cfg = RandomEffectDataConfiguration(
        "userId", "shard", num_active_data_points=16)
    ds = build_random_effect_dataset(data, cfg, seed=1, intercept_col=9)
    active_rows = sum(
        int((np.asarray(b.row_ids) < ds.n_rows).sum()) for b in ds.blocks)
    passive_rows = sum(
        int((np.asarray(b.row_ids) < ds.n_rows).sum())
        for b in ds.passive_blocks if b is not None)
    assert active_rows == 16 * 4
    assert active_rows + passive_rows == 200
    # Reweighting preserves total weight per entity approximately:
    # sum of active weights == sum of original weights for that entity.
    col = data.id_columns["userId"]
    for b, codes in zip(ds.blocks, ds.entity_codes):
        for e, code in enumerate(codes):
            total_orig = data.weights[col.codes == code].sum()
            active_w = float(np.asarray(b.weights[e]).sum())
            np.testing.assert_allclose(active_w, total_orig, rtol=0.35)


def test_feature_selection_ratio_caps_dims(rng):
    data = _toy_game_data(rng, n=120, d=30, n_users=3)
    cfg = RandomEffectDataConfiguration(
        "userId", "shard", num_features_to_samples_ratio=0.2)
    ds = build_random_effect_dataset(data, cfg, intercept_col=29)
    for b, codes in zip(ds.blocks, ds.entity_codes):
        n_active = (np.asarray(b.row_ids) < ds.n_rows).sum(axis=1)
        d_local = (np.asarray(b.feat_idx) >= 0).sum(axis=1)
        for e in range(b.num_entities):
            keep = max(1, int(np.ceil(0.2 * n_active[e])))
            assert d_local[e] <= keep + 1  # +1 in case intercept forced in
            # intercept always survives
            assert 29 in np.asarray(b.feat_idx[e])


def test_pearson_scores_match_numpy(rng):
    x = rng.normal(0, 1, (50, 4))
    x[:, 2] = 1.0  # constant/intercept
    y = rng.normal(0, 1, 50)
    scores = pearson_correlation_scores(sp.csr_matrix(x), y, intercept_col=2)
    for j in (0, 1, 3):
        expect = abs(np.corrcoef(x[:, j], y)[0, 1])
        np.testing.assert_allclose(scores[j], expect, rtol=1e-10)
    assert np.isinf(scores[2])


def test_scatter_scores_roundtrip(rng):
    data = _toy_game_data(rng)
    cfg = RandomEffectDataConfiguration("userId", "shard")
    ds = build_random_effect_dataset(data, cfg, intercept_col=9)
    # margins == 1 for every real row -> score vector of ones
    margins = [jnp.where(b.row_ids < ds.n_rows, 1.0, 123.0) for b in ds.blocks]
    scores = ds.scatter_scores(margins, [None] * len(ds.blocks))
    np.testing.assert_allclose(np.asarray(scores), np.ones(data.num_rows))


def _per_user_coordinate(ds):
    from photon_ml_tpu.algorithm import RandomEffectCoordinate
    from photon_ml_tpu.optimization.config import (
        GLMOptimizationConfiguration,
    )

    return RandomEffectCoordinate(
        name="perUser", dataset=ds, task_type=TaskType.LOGISTIC_REGRESSION,
        config=GLMOptimizationConfiguration.parse("5,1e-6,1.0,1.0,LBFGS,L2"))


def _ones_model(coord):
    zero = coord.initialize_model()
    return zero.with_coefs([jnp.ones_like(c) for c in zero.local_coefs])


@pytest.mark.parametrize("where", ["another user's slot", "a passive block"])
def test_a_row_in_two_slots_is_refused_at_construction(rng, where):
    """A coordinate's scores are a gather by row: a dataset in which a row
    sits in two slots has no such reading, and construction names the
    row."""
    import dataclasses

    data = _toy_game_data(rng)
    ds = build_random_effect_dataset(
        data, RandomEffectDataConfiguration("userId", "shard"),
        intercept_col=9)
    _per_user_coordinate(ds)  # as built: every row once
    block = ds.blocks[0]
    row = int(block.row_ids[0, 0])
    if where == "another user's slot":
        last = ds.blocks[-1]
        assert last.row_ids[-1, 0] != row
        twice = dataclasses.replace(
            last, row_ids=last.row_ids.at[-1, 0].set(row))
        ds = dataclasses.replace(ds, blocks=ds.blocks[:-1] + [twice])
    else:
        passive = dataclasses.replace(
            block, row_ids=jnp.full_like(block.row_ids, ds.n_rows).at[
                0, 0].set(row))
        ds = dataclasses.replace(
            ds, passive_blocks=[passive] + ds.passive_blocks[1:])
    with pytest.raises(ValueError, match=rf"row {row} sits in 2 slots"):
        _per_user_coordinate(ds)


def test_a_row_in_no_slot_scores_zero(rng):
    """A user filtered out of the dataset: its rows read the zero behind the
    margins, every other row its own slot, and the coordinate counts
    them."""
    import dataclasses

    data = _toy_game_data(rng)
    ds = build_random_effect_dataset(
        data, RandomEffectDataConfiguration("userId", "shard"),
        intercept_col=9)
    whole = _per_user_coordinate(ds)
    assert whole.unslotted_rows == 0
    want = np.array(whole.score(_ones_model(whole)))
    assert (want >= 1.0).all()  # the intercept
    gone = np.asarray(ds.blocks[0].row_ids)[0]
    gone = gone[gone < ds.n_rows]
    cut = dataclasses.replace(
        ds, blocks=[jax.tree.map(lambda a: a[1:], ds.blocks[0])]
        + ds.blocks[1:],
        entity_codes=[ds.entity_codes[0][1:]] + ds.entity_codes[1:])
    coord = _per_user_coordinate(cut)
    assert coord.unslotted_rows == len(gone) > 0
    got = np.asarray(coord.score(_ones_model(coord)))
    assert not got[gone].any()
    want[gone] = 0.0
    np.testing.assert_array_equal(got, want)


def test_reservoir_sample_properties(rng):
    idx, mult = reservoir_sample(rng, 100, 10)
    assert len(idx) == 10 and mult == 10.0
    assert len(np.unique(idx)) == 10
    idx, mult = reservoir_sample(rng, 5, 10)
    assert len(idx) == 5 and mult == 1.0


def test_binary_down_sampler_keeps_positives():
    key = jax.random.PRNGKey(0)
    labels = jnp.asarray([1.0, 1.0, 0.0, 0.0] * 50)
    weights = jnp.ones(200)
    w = binary_classification_down_sampler(key, labels, weights, 0.3)
    w = np.asarray(w)
    assert np.all(w[::4] == 1.0) and np.all(w[1::4] == 1.0)
    negs = np.concatenate([w[2::4], w[3::4]])
    nz = negs[negs != 0]
    np.testing.assert_allclose(nz, 1 / 0.3, rtol=1e-6)
    # Unbiasedness in expectation: kept negative weight ~ total negatives.
    assert abs(negs.sum() - 100) < 40


def test_libsvm_reader(tmp_path):
    p = tmp_path / "data.libsvm"
    p.write_text("+1 1:0.5 3:2.0\n-1 2:1.5 # comment\n0 1:1.0 4:1.0\n")
    mat, y = read_libsvm(p, add_intercept=True)
    assert mat.shape == (3, 5)  # 4 features + intercept
    np.testing.assert_allclose(y, [1.0, 0.0, 0.0])
    np.testing.assert_allclose(mat.toarray()[:, -1], 1.0)
    assert mat[0, 0] == 0.5 and mat[0, 2] == 2.0 and mat[1, 1] == 1.5

    bad = tmp_path / "bad.libsvm"
    bad.write_text("1 nonsense\n")
    with pytest.raises(ValueError, match="bad.libsvm:1"):
        read_libsvm(bad)


def test_stats_sparse_includes_implicit_zeros(rng):
    x = sp.csr_matrix(np.asarray([[1.0, 0.0], [3.0, -2.0], [0.0, 0.0]]))
    s = BasicStatisticalSummary.compute(x)
    np.testing.assert_allclose(s.mean, [4 / 3, -2 / 3])
    np.testing.assert_allclose(s.max, [3.0, 0.0])
    np.testing.assert_allclose(s.min, [0.0, -2.0])
    np.testing.assert_allclose(s.num_nonzeros, [2, 1])
    dense = BasicStatisticalSummary.compute(x.toarray())
    np.testing.assert_allclose(dense.variance, s.variance)
    np.testing.assert_allclose(dense.mean_abs, s.mean_abs)


def test_validators():
    x = sp.csr_matrix(np.ones((4, 2)))
    validate_data(TaskType.LOGISTIC_REGRESSION, x,
                  np.asarray([0.0, 1.0, 0, 1]))
    with pytest.raises(ValueError, match="binary"):
        validate_data(TaskType.LOGISTIC_REGRESSION, x,
                      np.asarray([0.0, 2.0, 0, 1]))
    with pytest.raises(ValueError, match="non-negative"):
        validate_data(TaskType.POISSON_REGRESSION, x,
                      np.asarray([1.0, -1.0, 0, 1]))
    with pytest.raises(ValueError, match="non-finite"):
        validate_data(TaskType.LINEAR_REGRESSION, x,
                      np.asarray([1.0, np.nan, 0, 1]))
    with pytest.raises(ValueError, match="weights"):
        validate_data(TaskType.LINEAR_REGRESSION, x,
                      np.asarray([1.0, 1.0, 0, 1]),
                      weights=np.asarray([1.0, -2.0, 1, 1]))
    # disabled mode never raises
    validate_data(TaskType.LOGISTIC_REGRESSION, x, np.asarray([5.0] * 4),
                  validation_type=DataValidationType.VALIDATE_DISABLED)


def test_re_config_parse():
    c = RandomEffectDataConfiguration.parse(
        "userId,shard1,10,100,20,0.5,INDEX_MAP")
    assert c.random_effect_type == "userId"
    assert c.num_active_data_points == 100
    assert c.num_passive_data_points_lower_bound == 20
    assert c.num_features_to_samples_ratio == 0.5
    c2 = RandomEffectDataConfiguration.parse("itemId,shard2,4,-1,-1,-1")
    assert c2.num_active_data_points is None
    with pytest.raises(ValueError):
        RandomEffectDataConfiguration.parse("tooFew,fields")


def test_filter_features_by_support():
    import scipy.sparse as sp

    from photon_ml_tpu.data.random_effect import filter_features_by_support

    x = sp.csr_matrix(np.array([
        [1.0, 0.0, 2.0, 1.0],
        [0.0, 0.0, 3.0, 1.0],
        [4.0, 0.0, 0.0, 1.0],
    ]))
    # support per column: [2, 0, 2, 3]
    np.testing.assert_array_equal(
        filter_features_by_support(x, 2), [0, 2, 3])
    np.testing.assert_array_equal(
        filter_features_by_support(x, 3), [3])
    # intercept column always survives
    np.testing.assert_array_equal(
        filter_features_by_support(x, 5, intercept_col=3), [3])
