"""What decides ``correct`` for a ``cd_fit_sparse`` cell.

A fit to an iteration cap is no solve to a minimiser, so it is held to the
plain reference (``reference/sparse_glm.py``) from two sides: what the
program says of its own model against what that model is worth, and the
model against an independently written L-BFGS after the same cap.

- ``obj_self_gap``    the last entry of every job's objective history
                      against the reference's value at the kept jobs'
                      coefficients (every job fits the same problem from
                      zero: a job that was not kept is held to the last
                      kept one's), relative.
- ``score_self_gap``  the kept models' training scores by the program's
                      ``Coordinate.score``, from the matrix the timed path
                      reads, against the reference's scores of the same
                      coefficients from the plain arrays: the number that
                      sees values stored or gathered in a lower precision.
- ``coef_gap.<coord>`` the norm of (program - reference) coefficients over
                      the reference's norm: the reference's own L-BFGS
                      after the same cap from zero.
- ``coef_gap.tail``   the same over the columns of degree 1 (one stored
                      entry in the whole matrix) alone: a million columns
                      hide a few thousand in any norm over all of them.
- ``descent_gap``     one-sided: how much less the program descended than
                      the reference did, ``(f(w_program) - f(w_reference))
                      / (f(0) - f(w_reference))``, every value the
                      reference's; 0 where the program is as low or lower.

Each number has its limit in the cell's workload file, set from readings
that ``PERF.md`` gives. A number without a limit there is an error.
"""

from __future__ import annotations

from typing import Dict

import jax.numpy as jnp
import numpy as np

from benchmark.reference import sparse_glm


def _rel(diff, norm) -> float:
    return float(jnp.linalg.norm(diff) / jnp.maximum(
        jnp.linalg.norm(norm), 1e-30))


def numbers(problem, config: dict, window: dict, ref: dict = None
            ) -> Dict[str, float]:
    """``ref``: the reference's fit of this problem, where the caller has
    it already (the readings hold several variants against one)."""
    ref = ref or sparse_glm.fit(problem, config)
    fixed = config["fixed"]["name"]
    w_ref = ref["coefs"][fixed]
    f0, f_ref = float(ref["values"][0]), float(ref["values"][-1])
    tail = problem.col_degree == 1
    out = {"obj_self_gap": 0.0, "score_self_gap": 0.0,
           f"coef_gap.{fixed}": 0.0, "coef_gap.tail": 0.0,
           "descent_gap": 0.0}
    value_at = {}
    for key, answer in window["kept"].items():
        w = jnp.asarray(answer["coefs"][fixed], jnp.float32)
        f_w = sparse_glm.value(problem, config, w)
        value_at[key] = f_w
        out["obj_self_gap"] = max(
            out["obj_self_gap"],
            abs(float(answer["history"][-1]) - f_w) / abs(f_w))
        own = sparse_glm.scores_of(problem, config, {fixed: w})
        scores = jnp.asarray(answer["scores"], jnp.float32)
        out["score_self_gap"] = max(out["score_self_gap"], float(
            jnp.sqrt(jnp.mean(jnp.square(scores - own))
                     / jnp.mean(jnp.square(own)))))
        out[f"coef_gap.{fixed}"] = max(out[f"coef_gap.{fixed}"],
                                       _rel(w - w_ref, w_ref))
        out["coef_gap.tail"] = max(
            out["coef_gap.tail"],
            _rel(jnp.where(tail, w - w_ref, 0.0), jnp.where(tail, w_ref, 0.0)))
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
    values = numbers(problem, config, window)
    missing = sorted(set(values) - set(limits))
    if missing:
        raise KeyError(f"workload {workload['name']!r} sets no limit for "
                       f"{missing}")
    return {k: {"value": values[k], "limit": float(limits[k])}
            for k in values}
