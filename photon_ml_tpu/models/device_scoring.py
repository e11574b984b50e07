"""Device-side GAME model scoring over an arbitrary GameDataset.

The reference scores distributed: broadcast-dot for fixed effects
(ml/model/FixedEffectModel.scala:94-105), entity joins for random effects
(ml/model/RandomEffectModel.scala:~110-165), factor dots for MF
(ml/model/MatrixFactorizationModel.scala:50-52). The TPU equivalent: the
dataset's feature shards and entity-code columns are uploaded to HBM ONCE
(at scorer construction), and every (re-)scoring of an updated model is a
single jitted dispatch over resident buffers — no per-submodel host
transfers. Used by coordinate descent's per-iteration validation and the
GAME scoring CLI; `GameModel.score` (host numpy) remains for final Avro
writes and one-off host scoring.

All static data is passed to the jitted function as ARGUMENTS, never
captured in the closure: closed-over device constants are baked into the
executable and re-staged on every call.
"""

from __future__ import annotations

from typing import List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from photon_ml_tpu.data.game_data import GameDataset
from photon_ml_tpu.models.fixed_effect import FixedEffectModel
from photon_ml_tpu.models.game_model import GameModel
from photon_ml_tpu.models.matrix_factorization import MatrixFactorizationModel
from photon_ml_tpu.models.random_effect import RandomEffectModel
from photon_ml_tpu.ops.features import features_to_device
from photon_ml_tpu.serving import kernels
from photon_ml_tpu.utils.vocab import vocab_code_lookup

Array = jax.Array


def _mapped_codes(data: GameDataset, effect_type: str,
                  model_vocab: np.ndarray) -> np.ndarray:
    """Map the dataset's per-row entity codes into a model's vocabulary
    (-1 = entity unknown to the model, scores 0 — the reference's
    missing-join semantics). Vectorized searchsorted join — no per-entry
    python dict on the scoring path."""
    col = data.id_columns[effect_type]
    lookup = vocab_code_lookup(model_vocab, col.vocabulary).astype(np.int32)
    return lookup[col.codes]


# The actual scoring math lives in serving/kernels.py, shared with the
# streaming engine; these wrappers adapt it to score_all's uniform
# (sdata, params, dtype, static) signature.

def _score_fixed(sdata, params, dtype, static):
    feats, = sdata
    return kernels.score_fixed(feats, params, dtype)


def _score_random(sdata, params, dtype, static):
    """Assemble the entity->global-coefficients matrix from the model's
    bucketed blocks on device, then contract it against the validation
    shard. The projection matrix (projected/factored random effects) is a
    PARAM: factored models learn it, so it changes across scoring calls —
    hence assemble-per-dispatch, unlike the serving engine's
    assemble-once-at-upload."""
    feats, mapped, block_static = sdata
    n_codes, d_global = static
    coefs, proj = params
    return kernels.score_random(feats, mapped, block_static, coefs, proj,
                                n_codes, d_global, dtype)


def _score_mf(sdata, params, dtype, static):
    row_mapped, col_mapped = sdata
    rf, cf = params
    return kernels.score_mf(row_mapped, col_mapped, rf, cf, dtype)


def _score_random_matrix(sdata, params, dtype, static):
    """Random effect whose entity matrix arrives pre-assembled (loaded
    RandomEffectModelSnapshot): params IS M[n_codes + 1, d_global]."""
    feats, mapped = sdata
    return kernels.score_random_with_matrix(feats, mapped,
                                            params.astype(dtype))


class DeviceGameScorer:
    """Scores GameModels sharing one structure on a fixed GameDataset.

    Construction uploads the dataset once and freezes per-submodel static
    structure (shapes, vocab mappings, block layout); ``score(model)``
    then runs ONE jitted dispatch and returns a device f[n_rows] vector.
    """

    def __init__(self, model: GameModel, data: GameDataset,
                 dtype=jnp.float32):
        self.dtype = np.dtype(dtype)
        self.num_rows = data.num_rows
        self._kinds: List[Tuple[str, str]] = []  # (name, kind)
        self._sdata = []
        self._static = []  # python-int shape info per sub-model (not traced)

        for name, m in model.models.items():
            re_model: Optional[RandomEffectModel] = None
            if isinstance(m, RandomEffectModel):
                re_model = m
            elif hasattr(m, "latent") and isinstance(
                    getattr(m, "latent", None), RandomEffectModel):
                re_model = m.latent  # FactoredRandomEffectModel

            if isinstance(m, FixedEffectModel):
                feats = features_to_device(
                    data.feature_shards[m.feature_shard_id], dtype=dtype)
                self._kinds.append((name, "fixed"))
                self._sdata.append((feats,))
                self._static.append(None)
            elif re_model is not None:
                feats = features_to_device(
                    data.feature_shards[re_model.feature_shard_id],
                    dtype=dtype)
                mapped = jnp.asarray(_mapped_codes(
                    data, re_model.random_effect_type, re_model.vocabulary))
                block_static = tuple(
                    (jnp.asarray(np.asarray(codes, np.int32)),
                     jnp.asarray(fidx, jnp.int32))
                    for codes, fidx in zip(re_model.entity_codes,
                                           re_model.feat_idx))
                self._kinds.append((name, "random"))
                self._sdata.append((feats, mapped, block_static))
                self._static.append((len(re_model.vocabulary),
                                     re_model.num_global_features))
            elif isinstance(m, MatrixFactorizationModel):
                row_mapped = jnp.asarray(_mapped_codes(
                    data, m.row_effect_type, m.row_vocabulary))
                col_mapped = jnp.asarray(_mapped_codes(
                    data, m.col_effect_type, m.col_vocabulary))
                self._kinds.append((name, "mf"))
                self._sdata.append((row_mapped, col_mapped))
                self._static.append(None)
            elif kernels.is_re_snapshot(m):
                # Loaded random-effect snapshot: entity matrix already
                # assembled in global space (io/model_io.py). Oversize
                # matrices must reject HERE (constructor contract), not
                # at the later _params_of densification.
                kernels.check_snapshot_densifiable(m, self.dtype)
                feats = features_to_device(
                    data.feature_shards[m.feature_shard_id], dtype=dtype)
                mapped = jnp.asarray(_mapped_codes(
                    data, m.random_effect_type, m.vocabulary))
                self._kinds.append((name, "random_matrix"))
                self._sdata.append((feats, mapped))
                self._static.append(None)
            else:
                raise kernels.UnsupportedSubModelError(
                    f"coordinate {name!r}: cannot device-score "
                    f"{type(m).__name__}")

        dt = jnp.dtype(dtype)
        kinds = [k for _, k in self._kinds]
        statics = list(self._static)
        n = self.num_rows

        def score_all(sdata_all, params_all):
            total = jnp.zeros((n,), dt)
            for kind, sdata, params, static in zip(
                    kinds, sdata_all, params_all, statics):
                fn = {"fixed": _score_fixed, "random": _score_random,
                      "mf": _score_mf,
                      "random_matrix": _score_random_matrix}[kind]
                total = total + fn(sdata, params, dt, static)
            return total

        self._fn = jax.jit(score_all)

    def _params_of(self, model: GameModel):
        out = []
        for name, kind in self._kinds:
            m = model.models[name]
            if kind == "fixed":
                out.append(m.glm.coefficients.means)
            elif kind == "random":
                re_model = m if isinstance(m, RandomEffectModel) else m.latent
                proj = (None if re_model.projection is None
                        else jnp.asarray(re_model.projection.matrix))
                out.append((tuple(jnp.asarray(c)
                                  for c in re_model.local_coefs), proj))
            elif kind == "random_matrix":
                from photon_ml_tpu.data.device_feed import chunked_device_put

                out.append(chunked_device_put(
                    kernels.snapshot_dense_matrix(m, self.dtype)))
            else:
                out.append((m.row_factors, m.col_factors))
        return tuple(out)

    def score(self, model: GameModel) -> Array:
        """Additive score over all sub-models: one jitted dispatch, device
        result (transfer with np.asarray only when host values are needed)."""
        return self.score_with_params(self.params_of(model))

    def params_of(self, model: GameModel):
        """Extract the device params pytree score_with_params consumes —
        public so callers timing repeated scores can hoist the (host-side)
        extraction and vary the params per call."""
        return self._params_of(model)

    def score_with_params(self, params) -> Array:
        """Score from a pre-extracted params pytree (see params_of)."""
        return self._fn(tuple(self._sdata), params)
