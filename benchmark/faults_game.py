"""Faults planted under the timed path of a ``cd_fit_game`` cell, for the
readings and the tests (``faults.py``'s kind: each breaks one thing in the
program as a later PR might by mistake, and puts it back; the benchmark's
own runs never use them)."""

from __future__ import annotations

import contextlib
import dataclasses

from benchmark.faults import _patched, half_batch


def refit_left_out():
    """The refit of B returns the B it was given: B stays B0."""
    from photon_ml_tpu.algorithm import coordinates as co

    def make(original):
        def broken(objective, config, batch, coef0):
            return dataclasses.replace(
                original(objective, config, batch, coef0), x=coef0)
        return broken

    return _patched(co, "_solve_latent_matrix", make)


def latent_left_out():
    """Every entity's latent solve returns the factors it was given."""
    from photon_ml_tpu.algorithm import coordinates as co

    def make(original):
        def broken(objective, config, block, B, extra, gamma0, d, mesh=None):
            return dataclasses.replace(
                original(objective, config, block, B, extra, gamma0, d,
                         mesh=mesh), x=gamma0)
        return broken

    return _patched(co, "_solve_factored_block", make)


def refit_half_batch():
    """``faults.half_batch`` in the refit of B: every second slot left out
    of the batch ``_solve_latent_matrix`` is given, the others counted
    double (a one-row movie keeps its row, at twice the weight)."""
    import jax.numpy as jnp

    from photon_ml_tpu.algorithm import coordinates as co
    from photon_ml_tpu.ops.glm_objective import GLMBatch

    def make(original):
        def broken(objective, config, batch, coef0):
            keep = jnp.arange(batch.weights.shape[0]) % 2 == 0
            batch = GLMBatch(batch.features, batch.labels, batch.offsets,
                             jnp.where(keep, 2.0 * batch.weights, 0.0))
            return original(objective, config, batch, coef0)
        return broken

    return _patched(co, "_solve_latent_matrix", make)


def latent_half_batch():
    """``faults.half_batch`` in the latent solves: every second slot of
    every entity left out of ``_solve_factored_block``, the others counted
    double."""
    import jax.numpy as jnp

    from photon_ml_tpu.algorithm import coordinates as co

    def make(original):
        def broken(objective, config, block, B, extra, gamma0, d, mesh=None):
            keep = jnp.arange(block.weights.shape[1]) % 2 == 0
            block = type(block)(
                block.x, block.labels, block.offsets,
                jnp.where(keep[None, :], 2.0 * block.weights, 0.0),
                block.row_ids, block.feat_idx)
            return original(objective, config, block, B, extra, gamma0, d,
                            mesh=mesh)
        return broken

    return _patched(co, "_solve_factored_block", make)


@contextlib.contextmanager
def mf_scores_left_out():
    """Inside the block the factored coordinate's scores read zero, so the
    objective recorded after its update (and, in a longer sweep, the residual
    of the coordinate after it) lacks them; the model's own ``score`` is
    left whole."""
    import jax.numpy as jnp

    from photon_ml_tpu.algorithm import coordinates as co

    whole = co.FactoredRandomEffectCoordinate.pure_score

    def zeros(original):
        def broken(self, data, params):
            return jnp.zeros_like(original(self, data, params))
        return broken

    def untouched(original):
        def score(self, model):
            return whole(self, self.step_data(), self.params_of(model))
        return score

    with _patched(co.FactoredRandomEffectCoordinate, "pure_score", zeros), \
            _patched(co.FactoredRandomEffectCoordinate, "score", untouched):
        yield


def _typical_entity(coefs) -> int:
    """The entity of one size class whose coefficients ``[E, d]`` have the
    class's median norm (the upper median of an even class). A fault that
    MULTIPLIES an entity's coefficients shows only as far as they are not
    zero, and of a class of one- to four-row movies a quarter have norms
    under a tenth of the class's median, some exactly zero: planted in the
    class's first entity the same fault read anything from 0 to f - 1 by
    seed. In the typical entity it reads f - 1 under the check's scale."""
    import numpy as np

    norms = np.linalg.norm(np.asarray(coefs, np.float64), axis=1)
    return int(np.argsort(norms, kind="stable")[len(norms) // 2])


def _median_class(dataset) -> int:
    """The size class that holds the median entity by true row count."""
    import numpy as np

    rows = [np.asarray((b.row_ids < dataset.n_rows).sum(axis=1))
            for b in dataset.blocks]
    median = np.median(np.concatenate(rows))
    return next(bi for bi, r in enumerate(rows) if r.max() >= median)


def _entity_altered(where: str):
    """One movie's latent factors altered where the factored model is made,
    by ``faults.entity_altered``'s factor of 1.5, so that its products
    ``gamma_m B`` are 1.5 times what the fit found: the typical movie
    (``_typical_entity``, by its product's norm) of the smallest size class
    (``smallest``: movies of one to four rows, the tail this cell exists
    for) or of the class that holds the median movie (``median``)."""
    import numpy as np

    from photon_ml_tpu.algorithm import coordinates as co

    def make(original):
        def broken(self, params, model):
            gammas, b = params
            bi = 0 if where == "smallest" else _median_class(self.dataset)
            ei = _typical_entity(np.asarray(gammas[bi]) @ np.asarray(b))
            gammas = list(gammas)
            gammas[bi] = gammas[bi].at[ei].multiply(1.5)
            return original(self, (tuple(gammas), b), model)
        return broken

    return _patched(co.FactoredRandomEffectCoordinate, "model_of", make)


def entity_altered_smallest():
    """``_entity_altered`` in the smallest size class."""
    return _entity_altered("smallest")


def entity_altered_median():
    """``_entity_altered`` at the median movie's size class."""
    return _entity_altered("median")


def user_altered():
    """``faults.entity_altered`` (one entity's coefficients of a random
    effect altered by the factor 1.5 where the model is made) in the
    typical user of the smallest size class (r 32)."""
    from photon_ml_tpu.algorithm import coordinates as co

    def make(original):
        def broken(self, params, model):
            ei = _typical_entity(params[0])
            first = params[0].at[ei].multiply(1.5)
            return original(self, (first,) + tuple(params[1:]), model)
        return broken

    return _patched(co.RandomEffectCoordinate, "model_of", make)


#: The faults that alter the model after the fit: the block they run is the
#: sound program's.
AFTER_FIT = ("user_altered", "entity_altered_smallest",
             "entity_altered_median")

FAULTS = {f.__name__: f for f in (
    refit_left_out, latent_left_out, mf_scores_left_out, half_batch,
    refit_half_batch, latent_half_batch, user_altered, entity_altered_smallest, entity_altered_median)}
