"""Device scoring kernels shared by DeviceGameScorer and the streaming
serving engine.

One implementation per sub-model family (reference scoring semantics:
ml/model/FixedEffectModel.scala:94-105, RandomEffectModel.scala score join,
MatrixFactorizationModel.scala:50-52):

- fixed effect: margin matvec over any FeatureMatrix layout;
- random effect: entity-coefficient matrix assembly from the model's
  bucketed local blocks (device scatter, projection-aware) + the
  per-row contraction against a feature shard;
- matrix factorization: factor dots with the unknown-entity zero row.

The two scorers differ only in WHEN assembly happens: DeviceGameScorer
re-assembles inside every scoring dispatch (the model's coefficients
change between calls during training), while the serving engine assembles
ONCE at model upload (the model is frozen; requests vary instead).

Everything here is trace-safe: static ints arrive as python values, all
arrays as jax arguments.
"""

from __future__ import annotations

from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from photon_ml_tpu.models.random_effect import RandomEffectModel
from photon_ml_tpu.ops.features import CSRFeatures, SlotMajorEllFeatures

Array = jax.Array


class UnsupportedSubModelError(TypeError):
    """A GAME coordinate's sub-model family has no device scoring kernel
    (or would be unreasonable to device-score, e.g. a snapshot past the
    densification ceiling).

    This is the ONE constructor-time condition the scoring driver may
    turn into a host-numpy fallback; any other ``TypeError`` out of a
    scorer is a real bug and must surface (the driver used to catch bare
    ``TypeError``, which masked engine bugs as silent degradations —
    tests/test_cli_drivers.py::test_game_scoring_engine_bug_surfaces).
    Subclasses ``TypeError`` so pre-existing callers keep working."""


def is_re_snapshot(m) -> bool:
    """Duck-typed io.model_io.RandomEffectModelSnapshot check, shared by
    both scorers (kept import-free: the IO layer consumes the scorers'
    callers, so neither may import model_io at module scope)."""
    return (not isinstance(m, RandomEffectModel)
            and hasattr(m, "matrix") and hasattr(m, "vocabulary")
            and hasattr(m, "random_effect_type")
            and hasattr(m, "feature_shard_id"))


# Densification ceiling for loaded entity matrices: past this the dense
# [n_entities, d_global] table doesn't belong in host RAM or HBM wholesale
# and callers must keep the sparse host path (or block the entity axis).
SNAPSHOT_DENSIFY_MAX_BYTES = 2 << 30


def check_snapshot_densifiable(m, dtype) -> None:
    """Raise UnsupportedSubModelError (the scorers' constructor-time 'not
    device-scorable' contract, which drivers turn into a host fallback)
    when densifying a snapshot's entity matrix would be unreasonable."""
    nbytes = (len(m.vocabulary) + 1) * m.matrix.shape[1] \
        * np.dtype(dtype).itemsize
    if nbytes > SNAPSHOT_DENSIFY_MAX_BYTES:
        raise UnsupportedSubModelError(
            f"random-effect snapshot {m.random_effect_type!r} would "
            f"densify to {nbytes / 1e9:.1f} GB "
            f"({len(m.vocabulary)} entities x {m.matrix.shape[1]} global "
            "features) — beyond the device-scoring densification ceiling; "
            "use the host scoring path (sparse row multiply)")


def snapshot_dense_matrix(m, dtype) -> np.ndarray:
    """Host dense [n_codes + 1, d_global] entity matrix of a loaded
    RandomEffectModelSnapshot, with the trailing unknown-entity zero row
    score_random_with_matrix expects. Callers gate on
    check_snapshot_densifiable at CONSTRUCTION time so oversize models
    reject before any per-call work."""
    check_snapshot_densifiable(m, dtype)
    dense = np.zeros((len(m.vocabulary) + 1, m.matrix.shape[1]),
                     np.dtype(dtype))
    dense[:len(m.vocabulary)] = m.matrix.toarray()
    return dense


def score_fixed(feats, coefs: Array, dtype) -> Array:
    """Fixed-effect margins: feats @ coefs -> f[n_rows]."""
    return feats.matvec(coefs.astype(dtype))


def assemble_re_matrix(block_static: Sequence[Tuple[Array, Array]],
                       coefs: Sequence[Array],
                       proj: Optional[Array],
                       n_codes: int, d_global: int, dtype) -> Array:
    """Entity -> global-coefficient matrix [n_codes + 1, d_global] from the
    model's bucketed local blocks, on device. Row ``n_codes`` stays zero —
    the unknown-entity row (reference missing-join semantics). ``proj`` is
    the projection matrix of projected/factored models (local coefs then
    live in the latent space and map back via gamma @ P)."""
    M = jnp.zeros((n_codes + 1, d_global + 1), dtype)
    for (codes_b, fidx_b), coefs_b in zip(block_static, coefs):
        c = coefs_b.astype(dtype)
        if proj is not None:
            k = proj.shape[0]
            M = M.at[codes_b, :d_global].add(c[:, :k] @ proj.astype(dtype))
        else:
            cols = jnp.where(fidx_b >= 0, fidx_b, d_global)
            M = M.at[codes_b[:, None], cols].add(c)
    return M[:, :d_global]


def score_random_with_matrix(feats, mapped: Array, M: Array) -> Array:
    """Random-effect margins x_i . M[entity(i)] given an assembled entity
    matrix (see assemble_re_matrix). ``mapped`` holds per-row model codes,
    -1 = unknown -> the zero row M[n_codes]."""
    rows = jnp.where(mapped >= 0, mapped, M.shape[0] - 1)
    if isinstance(feats, SlotMajorEllFeatures):
        feats = feats.to_csr()
    if isinstance(feats, CSRFeatures):
        contrib = feats.values * M[rows[feats.row_ids], feats.col_ids]
        return jax.ops.segment_sum(contrib, feats.row_ids,
                                   num_segments=feats.n_rows)
    return jnp.einsum("nd,nd->n", feats.x, M[rows])


def score_random(feats, mapped: Array,
                 block_static: Sequence[Tuple[Array, Array]],
                 coefs: Sequence[Array], proj: Optional[Array],
                 n_codes: int, d_global: int, dtype) -> Array:
    """Assemble-then-contract form used when coefficients are PARAMS that
    change per call (training-time validation scoring)."""
    M = assemble_re_matrix(block_static, coefs, proj, n_codes, d_global,
                           dtype)
    return score_random_with_matrix(feats, mapped, M)


def score_mf(row_mapped: Array, col_mapped: Array,
             row_factors: Array, col_factors: Array, dtype) -> Array:
    """MF margins rowFactor(row) . colFactor(col); -1 codes hit an
    appended zero row on either side."""
    rf, cf = row_factors.astype(dtype), col_factors.astype(dtype)
    k = rf.shape[-1]
    rf = jnp.vstack([rf, jnp.zeros((1, k), dtype)])
    cf = jnp.vstack([cf, jnp.zeros((1, k), dtype)])
    rr = jnp.where(row_mapped >= 0, row_mapped, rf.shape[0] - 1)
    cc = jnp.where(col_mapped >= 0, col_mapped, cf.shape[0] - 1)
    return jnp.sum(rf[rr] * cf[cc], axis=-1)
