"""Faults planted under the timed path of a ``cd_fit_tron`` job, for the
readings and the tests.

Each is a context manager that breaks one thing in the program as a later
PR might by mistake, and puts it back; each takes the problem, as
``faults_sparse.py``'s do. Three break the trust-region solve where its
work is done, on the solve's path alone: the Hessian-vector product that
``solve_glm`` hands ``minimize_tron`` (``make_hvp``, the CG's one product:
the value, the gradient and every other caller of the objective keep
theirs) and the CG loop itself (``optimization.tron._truncated_cg``). The
benchmark's own runs never use them.
"""

from __future__ import annotations

from benchmark import faults
from benchmark.faults import _patched


def _solver_hvp(wrong):
    """``solve_glm``'s ``make_hvp`` replaced by ``wrong(make_hvp)``, one
    wrapped product for each the solver is given (it is a static argument
    of the compiled solve)."""
    from photon_ml_tpu.optimization import solver

    wrapped = {}

    def make(original):
        def broken(fun, x0, args=(), **kw):
            hvp = kw.get("make_hvp")
            if hvp is not None:
                kw["make_hvp"] = wrapped.setdefault(hvp, wrong(hvp))
            return original(fun, x0, args, **kw)
        return broken

    return _patched(solver, "minimize_tron", make)


def hvp_half_batch(problem):
    """The Hessian-vector product over every second row, the others
    counted double: the curvature weights of the odd rows dropped."""
    import jax.numpy as jnp

    from photon_ml_tpu.ops.glm_objective import GLMBatch

    def wrong(make_hvp):
        def half(x, batch, l2):
            keep = jnp.arange(batch.weights.shape[0]) % 2 == 0
            return make_hvp(x, GLMBatch(
                batch.features, batch.labels, batch.offsets,
                jnp.where(keep, 2.0 * batch.weights, 0.0)), l2)
        return half

    return _solver_hvp(wrong)


def hvp_without_l2(problem):
    """The Hessian-vector product without its ``l2 v``."""
    return _solver_hvp(lambda make_hvp: (
        lambda x, batch, l2: make_hvp(x, batch, 0.0)))


def cg_step_short(problem):
    """One CG step fewer an outer step: the CG run to its own stop, then
    run again from zero to one step before it (its count is the second
    run's)."""
    from photon_ml_tpu.optimization import tron

    def make(original):
        def broken(hvp, g, delta, max_cg, dtype):
            _, _, k = original(hvp, g, delta, max_cg, dtype)
            return original(hvp, g, delta, k - 1, dtype)
        return broken

    return _patched(tron, "_truncated_cg", make)


def score_altered(problem):
    """The fixed effect's scores altered where they are produced."""
    return faults.score_altered()


FAULTS = {f.__name__: f for f in (hvp_half_batch, hvp_without_l2,
                                  cg_step_short, score_altered)}
