"""What decides ``correct`` for a ``cd_fit`` cell.

After the window has closed, the plain reference (``reference/glm_cd.py``)
fits the same problem once, from the same plain arrays, and the answers of
the timed jobs are held against it:

- ``obj_gap``           every job's objective history, entry by entry,
                        against the reference's: the worst relative gap.
- ``coef_gap.<coord>``  the norm of (program - reference) coefficients over
                        the reference's norm, per coordinate, for the kept
                        jobs (the first, the last, one drawn from the seed).
- ``coef_worst.<group>`` the same gap entity by entity of a random-effect
                        group, each against its own reference norm or the
                        median entity's, whichever is larger: the worst
                        entity, so that a fault in one user shows.
- ``score_self_gap``    the kept models' training scores, as the program's
                        ``Coordinate.score`` gives them from the containers
                        the timed path reads, against the scores the
                        reference computes in float32 at the highest
                        precision from the plain arrays for those same
                        coefficients: what the program says its model
                        scores, held to what that model scores. Computed
                        after the window; this is the number that sees a
                        lower-precision storage of the data.

Each number has its limit in the cell's workload file, set from readings
that ``PERF.md`` gives. A number without a limit there is an error.
"""

from __future__ import annotations

from typing import Dict

import jax.numpy as jnp
import numpy as np

from benchmark.reference import glm_cd


def _rel_rms(a, b) -> float:
    return float(jnp.sqrt(jnp.mean(jnp.square(a - b))
                          / jnp.mean(jnp.square(b))))


def _entity_gaps(got, want):
    """Per entity, over all buckets: |got - want| and |want|."""
    diff = np.concatenate([np.asarray(jnp.linalg.norm(
        jnp.asarray(g, jnp.float32) - w, axis=1)) for g, w in zip(got, want)])
    norm = np.concatenate([np.asarray(jnp.linalg.norm(w, axis=1))
                           for w in want])
    return diff, norm


def numbers(problem, config: dict, window: dict, ref: dict = None
            ) -> Dict[str, float]:
    """``ref``: the reference's fit of this problem, where the caller has
    it already (the readings hold several variants against one)."""
    ref = ref or glm_cd.fit(problem, config)
    fixed = config["fixed"]["name"]
    out: Dict[str, float] = {}
    gaps = [np.max(np.abs(h - ref["history"]) / np.abs(ref["history"]))
            if h.shape == ref["history"].shape else 1e30
            for h in window["histories"]]
    out["obj_gap"] = float(np.max(gaps))
    for name in config["updating_sequence"]:
        out[f"coef_gap.{name}"] = 0.0
        if name != fixed:
            out[f"coef_worst.{name}"] = 0.0
    out["score_self_gap"] = 0.0
    for answer in window["kept"].values():
        for name in config["updating_sequence"]:
            got, want = answer["coefs"][name], ref["coefs"][name]
            if name == fixed:
                diff = np.asarray([float(jnp.linalg.norm(
                    jnp.asarray(got, jnp.float32) - want))])
                norm = np.asarray([float(jnp.linalg.norm(want))])
            else:
                diff, norm = _entity_gaps(got, want)
                worst = np.max(diff / np.maximum(norm, np.median(norm)))
                out[f"coef_worst.{name}"] = max(out[f"coef_worst.{name}"],
                                                float(worst))
            key = f"coef_gap.{name}"
            out[key] = max(out[key], float(
                np.sqrt(np.sum(diff ** 2) / np.sum(norm ** 2))))
        scores = jnp.asarray(answer["scores"], jnp.float32)
        own = glm_cd.scores_of(problem, config, answer["coefs"])
        out["score_self_gap"] = max(out["score_self_gap"],
                                    _rel_rms(scores, own))
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
