"""What decides ``correct`` for a ``cd_fit_tron`` cell.

A fit to a cap of outer steps is no solve to a minimiser, so it is held to
the plain reference (``reference/tron_glm.py``) from two sides: what the
program says of its own model and of its own work against what that model
and that work are worth, and the work against an independently written
TRON after the same budget.

- ``obj_self_gap``    the last entry of every job's objective history
                      against the reference's value at the kept jobs'
                      coefficients (every job fits the same problem from
                      zero: a job that was not kept is held to the last
                      kept one's), relative.
- ``score_self_gap``  the kept models' training scores by the program's
                      ``Coordinate.score`` against the reference's scores
                      of the same coefficients: the number that sees X
                      stored or multiplied in a lower precision.
- ``hvp_self_gap``    the Hessian-vector product the solve ran, against
                      the reference's: the kept jobs' last outer step's CG
                      reports the point it ran at, its step ``s`` and the
                      residual ``r`` it carried (``OptimizerResult.
                      cg_point``, ``.cg_step``, ``.cg_residual``), so its
                      products made ``H s = -g - r``; that against the
                      reference's ``X^T (D * X s) + l2 s`` at the same
                      point, ``g`` the reference's gradient there,
                      relative. A float32 CG at this conditioning moves its
                      path with the order of a sum by as much as a product
                      over half the rows or without its ``l2 v`` does, so
                      neither the iterates nor their objectives tell those
                      apart; the product the solve ran does.
- ``cg_steps``        the CG steps (Hessian-vector products) a kept job's
                      solve reports (``OptimizerResult.cg_iterations``)
                      against the reference's count, the largest absolute
                      difference: the work itself, which the coefficients
                      hardly see where a CG step is cut short.

Read by ``numbers`` and compared with no limit (``READ_ONLY``: no control
or fault reads them above the sound runs, ``PERF.md`` section 4):

- ``coef_gap.<coord>`` the norm of (program - reference) coefficients over
                      the reference's norm: the reference's own TRON after
                      the same budget from zero.
- ``descent_gap``     one-sided: how much less the program descended than
                      the reference did, ``(f(w_program) - f(w_reference))
                      / (f(0) - f(w_reference))``, every value the
                      reference's; 0 where the program is as low or lower.

Each compared number has its limit in the cell's workload file, set from
readings that ``PERF.md`` gives. A compared number without a limit there
is an error.
"""

from __future__ import annotations

from typing import Dict

import jax.numpy as jnp
import numpy as np

from benchmark.reference import tron_glm

READ_ONLY = ("coef_gap.", "descent_gap")


def _rel(diff, norm) -> float:
    return float(jnp.linalg.norm(diff) / jnp.maximum(
        jnp.linalg.norm(norm), 1e-30))


def _hvp_gap(problem, config: dict, solve) -> float:
    """``H s`` as the solve's last CG made it, ``-g - r``, against the
    reference's at the CG's own point."""
    at = jnp.asarray(solve.cg_point, jnp.float32)
    _, g = tron_glm.value_and_grad(problem, config, at)
    made = -g - jnp.asarray(solve.cg_residual, jnp.float32)
    hs = tron_glm.hvp(problem, config, at,
                      jnp.asarray(solve.cg_step, jnp.float32))
    return _rel(made - hs, hs)


def numbers(problem, config: dict, window: dict, ref: dict = None
            ) -> Dict[str, float]:
    """Every number, the read-only ones too. ``ref``: the reference's fit
    of this problem, where the caller has it already (the readings hold
    several variants against one)."""
    ref = ref or tron_glm.fit(problem, config)
    fixed = config["fixed"]["name"]
    w_ref = ref["coefs"][fixed]
    f0, f_ref = float(ref["values"][0]), float(ref["values"][-1])
    out = {"obj_self_gap": 0.0, "score_self_gap": 0.0, "hvp_self_gap": 0.0,
           "cg_steps": 0.0, f"coef_gap.{fixed}": 0.0, "descent_gap": 0.0}
    value_at = {}
    for key, answer in window["kept"].items():
        w = jnp.asarray(answer["coefs"][fixed], jnp.float32)
        f_w = tron_glm.value(problem, config, w)
        value_at[key] = f_w
        out["obj_self_gap"] = max(
            out["obj_self_gap"],
            abs(float(answer["history"][-1]) - f_w) / abs(f_w))
        own = tron_glm.scores_of(problem, config, {fixed: w})
        scores = jnp.asarray(answer["scores"], jnp.float32)
        out["score_self_gap"] = max(out["score_self_gap"], float(
            jnp.sqrt(jnp.mean(jnp.square(scores - own))
                     / jnp.mean(jnp.square(own)))))
        solves = answer["trackers"][fixed]
        out["hvp_self_gap"] = max(out["hvp_self_gap"],
                                  _hvp_gap(problem, config, solves[-1]))
        cg = sum(int(np.asarray(tr.cg_iterations)) for tr in solves)
        out["cg_steps"] = max(out["cg_steps"],
                              float(abs(cg - ref["cg_steps"])))
        out[f"coef_gap.{fixed}"] = max(out[f"coef_gap.{fixed}"],
                                       _rel(w - w_ref, w_ref))
        out["descent_gap"] = max(out["descent_gap"],
                                 max(0.0, f_w - f_ref) / (f0 - f_ref))
    f_last = value_at["last"]
    for h in window["histories"]:
        gap = abs(float(h[-1]) - f_last) / abs(f_last) if len(h) else 1e30
        out["obj_self_gap"] = max(out["obj_self_gap"], gap)
    # a gap that is no number has failed; kept finite so the line stays JSON
    return {k: (min(v, 1e30) if np.isfinite(v) else 1e30)
            for k, v in out.items()}


def check(problem, config: dict, workload: dict, window: dict) -> dict:
    limits = workload["compare"]
    values = {k: v for k, v in numbers(problem, config, window).items()
              if not k.startswith(READ_ONLY)}
    missing = sorted(set(values) - set(limits))
    if missing:
        raise KeyError(f"workload {workload['name']!r} sets no limit for "
                       f"{missing}")
    return {k: {"value": values[k], "limit": float(limits[k])}
            for k in values}
