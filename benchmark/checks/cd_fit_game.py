"""What decides ``correct`` for a ``cd_fit_game`` cell.

``checks/cd_fit.py``'s numbers against the plain reference of the GAME fit
(``reference/game_cd.py``), with a factored coordinate compared by what it
PREDICTS: its coefficients, the program's and the reference's alike, are
the products ``Gamma B`` (``[E, d]``, entity by entity), never ``Gamma`` or
``B``, which are fixed only up to a k x k change of basis.

- ``obj_gap``, ``coef_gap.<coord>``, ``score_self_gap``: as ``cd_fit``
  (``score_self_gap`` over ALL coordinates' scores).
- ``coef_worst.<coord>`` for every coordinate over a group: the gap
  ``|program - reference|`` entity by entity, each over its own reference
  norm or the median norm of ITS OWN SIZE CLASS, whichever is larger: the
  worst entity. Not ``cd_fit``'s ``max(own norm, the median entity's)``
  over the whole group: a movie of one to four rows has a norm a few times
  under the median movie's, and that scale hides it (one such movie
  altered by ``faults.py``'s factor of 1.5 read 0.13 and 0.16 there beside
  sound readings up to 0.055, PR 37's chip runs: no limit stands twice over
  the one and twice under the other), and this cell exists for the small
  ones. Nor the own norm alone: one-row movies whose residual already
  explains their row have norms down to exactly 0, where a relative gap
  says nothing. Under the class's own scale an entity of its class's
  median norm altered by the factor f reads f - 1 in every class.
- ``b0_gap``: the reference draws the ``B0`` of its factored coordinates
  itself, from the law and the seed the configuration states
  (``game_cd.start_matrix``); the ``B0`` the program's coordinates were
  initialised with (``window["b0"]``, handed over by the job kind) is
  COMPARED with it and used for nothing else: the worst coordinate's
  ``|B0_program - B0_law| / |B0_law|``, so a start of another scale, seed
  or rank fails the run instead of leading the reference along.

- ``refit_obj_gap.<coord>`` for every factored coordinate: what the
  program's LAST refit of B says its objective was where it stopped (the
  job kind hands over ``OptimizerResult.value`` of the last alternation)
  against the reference's refit objective over EVERY slot at full weight,
  evaluated at the program's own kept coefficients against the offsets the
  program's other coordinates produced (``game_cd.refit_objective``: the
  kept model's training scores less the reference's scores of the factored
  coordinate's kept coefficients; ``score_self_gap`` holds the program's
  scores to the reference's, and with the reference's in their place the
  bfloat16 control, which may fail that number alone, read 1e-4 here),
  relative. The other numbers of the
  coordinate compare the program with the reference's MINIMISER, and a
  refit stopped at its cap stands further from that than a fault that
  trains B on part of the batch moves it; this one compares two sums at
  ONE point, so the solver's stopping distance does not enter: a sound
  program differs by float32's rounding of a sum, a refit that saw half
  the slots by the sampling noise of the other half. Defined where the
  factored coordinate is the last of the sweep (the offsets its last refit
  saw are then the other coordinates' kept scores); another order is an
  error here, not a silent zero.

Each number has its limit in the cell's workload file; a number without a
limit there is an error.
"""

from __future__ import annotations

from typing import Dict

import jax.numpy as jnp
import numpy as np

from benchmark.checks.cd_fit import _rel_rms
from benchmark.reference import game_cd


def entity_gaps(got, want):
    """Size class by size class of a group coordinate, per entity:
    ``|got - want|`` and ``|want|``, a factored coordinate's by its
    products."""
    return [(np.asarray(jnp.linalg.norm(
        jnp.asarray(g, jnp.float32) - w, axis=1)),
        np.asarray(jnp.linalg.norm(w, axis=1)))
        for g, w in zip(game_cd.entity_coefficients(got),
                        game_cd.entity_coefficients(want))]


def worst_entity(classes) -> float:
    """The worst entity's gap over ``max(own norm, its class's median)``."""
    return max(float(np.max(diff / np.maximum(norm, np.median(norm))))
               for diff, norm in classes)


def numbers(problem, config: dict, window: dict, ref: dict = None
            ) -> Dict[str, float]:
    """``ref``: the reference's fit of this problem, where the caller has
    it already (the readings hold several variants against one)."""
    ref = ref or game_cd.fit(problem, config)
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
                classes = entity_gaps(got, want)
                key = f"coef_worst.{name}"
                out[key] = max(out[key], worst_entity(classes))
                diff, norm = (np.concatenate(v) for v in zip(*classes))
            key = f"coef_gap.{name}"
            out[key] = max(out[key], float(
                np.sqrt(np.sum(diff ** 2) / np.sum(norm ** 2))))
        scores = jnp.asarray(answer["scores"], jnp.float32)
        own = game_cd.scores_of(problem, config, answer["coefs"])
        out["score_self_gap"] = max(out["score_self_gap"],
                                    _rel_rms(scores, own))
    for name in window["b0"]:
        if name != config["updating_sequence"][-1]:
            raise NotImplementedError(
                f"refit_obj_gap.{name}: the factored coordinate is not the "
                "last of the sweep, so the kept coefficients of the "
                "coordinates after it are not what its last refit saw")
        out[f"refit_obj_gap.{name}"] = max(
            _rel(answer["refit_values"][name], game_cd.refit_objective(
                problem, config, name, answer["coefs"],
                scores=answer["scores"])[0])
            for answer in window["kept"].values())
    out["b0_gap"] = max(_b0_gap(window["b0"].get(name), want)
                        for name, want in ref["b0"].items())
    # a gap that is no number has failed; kept finite so the line stays JSON
    return {k: (min(v, 1e30) if np.isfinite(v) else 1e30)
            for k, v in out.items()}


def _rel(got: float, want: float) -> float:
    return abs(got - want) / abs(want)


def _b0_gap(got, want) -> float:
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    if got.shape != want.shape:
        return 1e30
    return float(np.linalg.norm(got - want) / np.linalg.norm(want))


def check(problem, config: dict, workload: dict, window: dict) -> dict:
    limits = workload["compare"]
    values = numbers(problem, config, window)
    missing = sorted(set(values) - set(limits))
    if missing:
        raise KeyError(f"workload {workload['name']!r} sets no limit for "
                       f"{missing}")
    return {k: {"value": values[k], "limit": float(limits[k])}
            for k in values}
