"""Faults planted under the timed path, for the readings and the tests.

Each is a context manager that breaks one thing in the program as a later
PR might by mistake, and puts it back. The benchmark's own runs never use
them: ``readings.py`` reads what each number says under each fault on the
chip, and ``tests/test_reference.py`` sees ``correct`` come out false.
"""

from __future__ import annotations

import contextlib


@contextlib.contextmanager
def _patched(cls, attr, make):
    original = getattr(cls, attr)
    setattr(cls, attr, make(original))
    try:
        yield
    finally:
        setattr(cls, attr, original)


def half_batch():
    """Half of the rows left out of the fixed-effect solve, the others
    counted double (the mean taken over the rest)."""
    import jax.numpy as jnp

    from photon_ml_tpu.algorithm import coordinates as co
    from photon_ml_tpu.ops.glm_objective import GLMBatch

    def make(original):
        def broken(self, data, params, residual, key):
            batch = data[0]
            keep = jnp.arange(batch.weights.shape[0]) % 2 == 0
            batch = GLMBatch(batch.features, batch.labels, batch.offsets,
                             jnp.where(keep, 2.0 * batch.weights, 0.0))
            return original(self, (batch,) + tuple(data[1:]), params,
                            residual, key)
        return broken

    return _patched(co.FixedEffectCoordinate, "pure_update", make)


@contextlib.contextmanager
def state_unchanged():
    """Every coordinate update returns its state as it got it."""
    from photon_ml_tpu.algorithm import coordinates as co

    def make(original):
        def broken(self, data, params, residual, key):
            _, tracker = original(self, data, params, residual, key)
            return params, tracker
        return broken

    with _patched(co.FixedEffectCoordinate, "pure_update", make), \
            _patched(co.RandomEffectCoordinate, "pure_update", make):
        yield


def exchange_left_out():
    """The random effect solves without the other coordinates' scores."""
    from photon_ml_tpu.algorithm import coordinates as co

    def make(original):
        def broken(self, data, params, residual, key):
            return original(self, data, params, None, key)
        return broken

    return _patched(co.RandomEffectCoordinate, "pure_update", make)


def coefficient_altered():
    """One coefficient of the returned model altered where it is made."""
    from photon_ml_tpu.algorithm import coordinates as co

    def make(original):
        def broken(self, params, model):
            return original(self, params.at[0].add(0.5), model)
        return broken

    return _patched(co.FixedEffectCoordinate, "model_of", make)


def entity_altered():
    """One entity's coefficients altered where the model is made."""
    from photon_ml_tpu.algorithm import coordinates as co

    def make(original):
        def broken(self, params, model):
            first = params[0].at[0].multiply(1.5)
            return original(self, (first,) + tuple(params[1:]), model)
        return broken

    return _patched(co.RandomEffectCoordinate, "model_of", make)


def score_altered():
    """The fixed effect's scores altered where they are produced."""
    from photon_ml_tpu.algorithm import coordinates as co

    def make(original):
        def broken(self, model):
            return original(self, model) * 1.001
        return broken

    return _patched(co.FixedEffectCoordinate, "score", make)


FAULTS = {f.__name__: f for f in (half_batch, state_unchanged,
                                  exchange_left_out, coefficient_altered,
                                  entity_altered, score_altered)}
