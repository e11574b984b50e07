"""GameDataset: the host-side columnar container for GAME training data.

Replaces the reference's RDD[(uid, GameDatum)] (ml/data/GameDatum.scala:33-59)
with struct-of-arrays: row order is frozen at construction, so every score
vector is a dense f32[n_rows] indexed by row position and the reference's
KeyValueScore join algebra (ml/data/KeyValueScore.scala:62-82) becomes
elementwise +/- on device.

Feature shards: named sparse matrices over disjoint (or overlapping) feature
spaces (the reference's featureShardContainer). Entity id columns: one
integer-coded column per random-effect type (user ids, item ids, ...), with
the string->code vocabulary kept host-side.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, Optional

import jax.numpy as jnp
import numpy as np
import scipy.sparse as sp

from photon_ml_tpu.ops.features import (
    DENSE_DENSITY_THRESHOLD,
    features_to_device,
)
from photon_ml_tpu.ops.glm_objective import GLMBatch


@dataclasses.dataclass
class EntityIdColumn:
    """Integer-coded entity ids for one random-effect type."""

    codes: np.ndarray  # i32[n_rows], code per row
    vocabulary: np.ndarray  # entity name per code (unicode array)

    @property
    def num_entities(self) -> int:
        return len(self.vocabulary)


def group_rows_by_code(codes: np.ndarray) -> list[np.ndarray]:
    """Row indices grouped by code value (stable order within groups).

    The single host-side replacement for every groupByKey shuffle in the
    reference (entity grouping, sharded evaluators).
    """
    order = np.argsort(codes, kind="stable")
    bounds = np.flatnonzero(np.diff(codes[order])) + 1
    return np.split(order, bounds)


@dataclasses.dataclass
class GameDataset:
    """Columnar GAME data, one row per example (host RAM, numpy/scipy)."""

    responses: np.ndarray  # f[n]
    offsets: np.ndarray  # f[n]
    weights: np.ndarray  # f[n]
    feature_shards: Dict[str, sp.csr_matrix]
    id_columns: Dict[str, EntityIdColumn]
    uids: Optional[np.ndarray] = None  # opaque row ids for score output

    def __post_init__(self):
        n = len(self.responses)
        for name, mat in self.feature_shards.items():
            if mat.shape[0] != n:
                raise ValueError(
                    f"feature shard {name!r} has {mat.shape[0]} rows, "
                    f"expected {n}")
        for name, col in self.id_columns.items():
            if len(col.codes) != n:
                raise ValueError(
                    f"id column {name!r} has {len(col.codes)} rows, "
                    f"expected {n}")

    @property
    def num_rows(self) -> int:
        return len(self.responses)

    @classmethod
    def build(
        cls,
        responses,
        feature_shards: Dict[str, sp.spmatrix],
        ids: Optional[Dict[str, np.ndarray]] = None,
        offsets=None,
        weights=None,
        uids=None,
    ) -> "GameDataset":
        """Build from raw columns; string entity ids are integer-coded here
        (the analog of GameConverters.getGameDataSetFromDataFrame,
        ml/data/GameConverters.scala:27-172)."""
        responses = np.asarray(responses, np.float64)
        n = len(responses)
        offsets = (np.zeros(n) if offsets is None
                   else np.asarray(offsets, np.float64))
        weights = (np.ones(n) if weights is None
                   else np.asarray(weights, np.float64))
        id_columns = {}
        for name, raw in (ids or {}).items():
            vocab, codes = np.unique(np.asarray(raw), return_inverse=True)
            id_columns[name] = EntityIdColumn(codes.astype(np.int32), vocab)
        return cls(
            responses=responses, offsets=offsets, weights=weights,
            feature_shards={k: sp.csr_matrix(v) for k, v in
                            feature_shards.items()},
            id_columns=id_columns, uids=uids,
        )

    # -- device views ------------------------------------------------------

    def fixed_effect_batch(
        self, shard_id: str, dtype=jnp.float32,
        extra_offsets: Optional[np.ndarray] = None,
        dense_threshold: float = DENSE_DENSITY_THRESHOLD,
        sparse_layout: Optional[str] = None,
    ) -> GLMBatch:
        """Materialize one feature shard as a device GLMBatch
        (the analog of FixedEffectDataSet, ml/data/FixedEffectDataSet.scala:29-103).
        ``sparse_layout`` names the below-threshold layout ("csr" |
        "bucketed_ell" | "sort_permute_ell"); None leaves it to the
        program's chooser (see features_to_device)."""
        from photon_ml_tpu.data.device_feed import chunked_device_put

        mat = self.feature_shards[shard_id]
        feats = features_to_device(mat, dtype, dense_threshold,
                                   sparse_layout=sparse_layout)
        off = self.offsets if extra_offsets is None else \
            self.offsets + extra_offsets
        # Column vectors ride the same chunked uploader as the features:
        # a single put below the chunk threshold, bounded overlapped
        # transfers above it (billions-of-rows datasets).
        return GLMBatch(
            features=feats,
            labels=chunked_device_put(self.responses, dtype),
            offsets=chunked_device_put(off, dtype),
            weights=chunked_device_put(self.weights, dtype),
        )

    def subset(self, rows: np.ndarray) -> "GameDataset":
        """Row-sliced view (used by validation splits and tests)."""
        return GameDataset(
            responses=self.responses[rows],
            offsets=self.offsets[rows],
            weights=self.weights[rows],
            feature_shards={k: m[rows] for k, m in self.feature_shards.items()},
            id_columns={
                k: EntityIdColumn(c.codes[rows], c.vocabulary)
                for k, c in self.id_columns.items()},
            uids=None if self.uids is None else self.uids[rows],
        )
