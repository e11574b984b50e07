"""Faults planted under the timed path of a ``cd_fit_sparse`` job, for the
readings and the tests: ``faults.py``'s for a sparse fixed effect.

Each is a context manager that breaks one thing in the program as a later
PR might by mistake, and puts it back; each takes the problem, because two
of them name columns by their degree. A product is broken where every
layout's product ends (``GLMObjective._jt_product``, the gradient's
``X^T u``), so no layout is named. The benchmark's own runs never use them.
"""

from __future__ import annotations

from benchmark import faults
from benchmark.faults import _patched


def half_batch(problem):
    """Every second row's weight dropped in the fixed-effect solve."""
    import jax.numpy as jnp

    from photon_ml_tpu.algorithm import coordinates as co
    from photon_ml_tpu.ops.glm_objective import GLMBatch

    def make(original):
        def broken(self, data, params, residual, key):
            batch = data[0]
            keep = jnp.arange(batch.weights.shape[0]) % 2 == 0
            batch = GLMBatch(batch.features, batch.labels, batch.offsets,
                             jnp.where(keep, batch.weights, 0.0))
            return original(self, (batch,) + tuple(data[1:]), params,
                            residual, key)
        return broken

    return _patched(co.FixedEffectCoordinate, "pure_update", make)


def _columns_left_out(mask):
    """``X^T u`` with the columns of ``mask`` left out."""
    import jax.numpy as jnp

    from photon_ml_tpu.ops import glm_objective

    def make(original):
        def broken(self, u, batch):
            return jnp.where(mask, 0.0, original(self, u, batch))
        return broken

    return _patched(glm_objective.GLMObjective, "_jt_product", make)


def hot_column_dropped(problem):
    """The fullest column (the intercept's) left out of ``rmatvec``."""
    import jax.numpy as jnp

    deg = problem.col_degree
    return _columns_left_out(jnp.arange(deg.shape[0]) == jnp.argmax(deg))


def tail_dropped(problem):
    """The columns of degree 1 left out of ``rmatvec``."""
    return _columns_left_out(problem.col_degree == 1)


def score_altered(problem):
    """The fixed effect's scores altered where they are produced."""
    return faults.score_altered()


FAULTS = {f.__name__: f for f in (half_batch, hot_column_dropped,
                                  tail_dropped, score_altered)}
