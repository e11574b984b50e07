"""The device-made data equals what the program's own host path builds
from the same values."""

import jax.numpy as jnp
import numpy as np
import scipy.sparse as sp

from benchmark.jobs import cd_fit
from benchmark.recipes import dense_glm
from benchmark.tests.common import tiny_config


def _host_dataset(problem, config, seed):
    from photon_ml_tpu.data.game_data import EntityIdColumn, GameDataset

    xu, _ = dense_glm.entity_features(config, seed, problem.entity_of_row)
    return GameDataset(
        responses=np.asarray(problem.labels, np.float64),
        offsets=np.asarray(problem.offsets, np.float64),
        weights=np.asarray(problem.weights, np.float64),
        feature_shards={"global": sp.csr_matrix(np.asarray(problem.x)),
                        "user": sp.csr_matrix(np.asarray(xu))},
        id_columns={"userId": EntityIdColumn(
            problem.entity_of_row,
            np.arange(problem.n_entities).astype(str))})


def test_random_effect_dataset_equals_the_host_builders():
    from photon_ml_tpu.data.random_effect import (
        RandomEffectDataConfiguration,
        build_random_effect_dataset,
    )

    seed = 2 ** 31 + 12345  # the driver's seeds pass 32 signed bits
    config = tiny_config()
    problem = dense_glm.make(config, seed)
    job = cd_fit.build(config, {}, problem)
    mine = job.coords["perUser"].dataset
    theirs = build_random_effect_dataset(
        _host_dataset(problem, config, seed),
        RandomEffectDataConfiguration.parse(
            config["random"][0]["data_config"]))
    assert len(mine.blocks) == len(theirs.blocks) >= 2
    assert mine.n_rows == theirs.n_rows
    assert mine.num_global_features == theirs.num_global_features
    for a, b, ca, cb in zip(mine.blocks, theirs.blocks, mine.entity_codes,
                            theirs.entity_codes):
        np.testing.assert_array_equal(ca, cb)
        for field in ("x", "labels", "offsets", "weights", "row_ids",
                      "feat_idx"):
            np.testing.assert_array_equal(
                np.asarray(getattr(a, field)), np.asarray(getattr(b, field)),
                err_msg=field)
        # the padding contracts, said outright
        pad = np.asarray(a.row_ids) == mine.n_rows
        assert np.all(np.asarray(a.weights)[pad] == 0)
        assert np.all(np.asarray(a.x)[pad] == 0)
        cols = np.asarray(a.feat_idx) == -1
        assert np.all(np.asarray(a.x).transpose(0, 2, 1)[cols] == 0)
    assert all(p is None for p in mine.passive_blocks)
    assert all(p is None for p in theirs.passive_blocks)
    # every row sits in exactly one slot
    ids = np.concatenate([np.asarray(b.row_ids).ravel() for b in mine.blocks])
    assert sorted(ids[ids < mine.n_rows]) == list(range(mine.n_rows))


def test_fixed_effect_batch_equals_game_dataset_batch():
    seed = 7
    config = tiny_config()
    problem = dense_glm.make(config, seed)
    job = cd_fit.build(config, {}, problem)
    mine = job.coords["fixed"]._batch
    theirs = _host_dataset(problem, config, seed).fixed_effect_batch(
        "global", dtype=jnp.float32)
    assert type(mine.features) is type(theirs.features)
    np.testing.assert_array_equal(np.asarray(mine.features.x),
                                  np.asarray(theirs.features.x))
    assert np.all(np.asarray(mine.features.x)[:, -1] == 1.0)
    for field in ("labels", "offsets", "weights"):
        np.testing.assert_array_equal(np.asarray(getattr(mine, field)),
                                      np.asarray(getattr(theirs, field)))


def test_same_seed_same_data_other_seed_other_data_same_shapes():
    config = tiny_config()
    a = dense_glm.make(config, 3_000_000_019)
    b = dense_glm.make(config, 3_000_000_019)
    c = dense_glm.make(config, 3_000_000_020)
    for f in ("x", "labels"):
        np.testing.assert_array_equal(np.asarray(getattr(a, f)),
                                      np.asarray(getattr(b, f)))
        assert not np.array_equal(np.asarray(getattr(a, f)),
                                  np.asarray(getattr(c, f)))
    np.testing.assert_array_equal(a.entity_of_row, b.entity_of_row)
    assert not np.array_equal(a.entity_of_row, c.entity_of_row)
    assert np.all(np.asarray(a.x)[:, -1] == 1.0)
    # a seed that differs only above bit 31 gives other data too
    d = dense_glm.make(config, 3_000_000_019 + 2 ** 32)
    assert not np.array_equal(np.asarray(a.x), np.asarray(d.x))
    # the truth has the configuration's norm in every seed's direction
    wa, wc = (np.asarray(dense_glm.true_fixed(config, s))
              for s in (3_000_000_019, 3_000_000_020))
    want = config["fixed"]["w_sd"] * np.sqrt(config["fixed"]["d"])
    np.testing.assert_allclose([np.linalg.norm(wa), np.linalg.norm(wc)],
                               want, rtol=1e-6)
    assert abs(np.dot(wa, wc)) < 0.5 * want ** 2


def test_every_seed_deals_out_the_same_set_of_activity_counts():
    config = tiny_config()
    group = config["random"][0]
    counts = dense_glm.activity_counts(group)
    law = group["activity"]
    assert counts.min() >= law["min"] and counts.max() <= law["max"]
    assert counts.sum() == config["n_rows"]
    assert len(np.unique(dense_glm.next_size(counts, 4))) >= 4  # a tail
    shapes, owners = set(), []
    for seed in (1, 2, 2 ** 31 + 3):
        problem = dense_glm.make(config, seed)
        shapes.add(tuple(b.x.shape for b in problem.buckets))
        per_entity = np.bincount(problem.entity_of_row,
                                 minlength=group["n_entities"])
        np.testing.assert_array_equal(np.sort(per_entity), counts)
        owners.append(per_entity)
    assert len(shapes) == 1
    assert not np.array_equal(owners[0], owners[1])


def test_the_cells_configuration_states_the_rows_its_activity_sums_to():
    import json

    from benchmark.tests.common import CONFIG, HERE

    config = json.loads((HERE / "configs" / f"{CONFIG}.json").read_text())
    assert dense_glm.n_rows_of(config) == config["n_rows"]
    pub = config["published"]
    assert config["random"][0]["n_entities"] == round(
        0.3 * pub["n_entities"])
    mean = config["n_rows"] / config["random"][0]["n_entities"]
    assert abs(mean - pub["rows_per_entity"]["mean"]) < 0.1
