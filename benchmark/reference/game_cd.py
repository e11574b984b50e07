"""The plain reference of a full GAME coordinate-descent fit: a fixed
effect, random effects over several groups, and factored (matrix-
factorization) random effects.

``glm_cd``'s reference (float32, every contraction at
``precision="highest"``, X in blocks of 2^19 rows, entities bucket by
bucket, no program code) with two things more: every random-effect
coordinate reads the blocks of ITS group (``problem.groups``), and a
factored coordinate, whose entity e has the coefficients ``gamma_e B``
(``gamma_e`` in R^k its own, ``B`` in R^{k x d} shared), is fitted by the
published alternation (Zhang et al., KDD 2016;
FactoredRandomEffectCoordinate.scala:99-165) from the start the
CONFIGURATION states (``start_matrix``: ``B0`` drawn here, from the law and
the seed in the coordinate's ``start`` entry, by nobody's code but numpy's;
the check holds the program's own ``B0`` to it, ``b0_gap``) and zero
factors. For each of the configured alternations:

1. every entity's ``gamma_e`` minimises its own L2-regularised GLM over the
   projected features ``x B^T`` against the current ``B``: ``glm_cd``'s
   bucket solve (safeguarded Newton on the k x k system) on the projection;
2. ``B`` minimises the L2-regularised GLM over ALL slots whose features are
   ``gamma_e (x) x``: safeguarded Newton on the (k d) x (k d) system, its
   gradient and Hessian summed entity by entity (``sum_e gamma_e gamma_e^T
   (x) X_e^T C_e X_e``), never a Kronecker row materialised.

Departures from the published alternation, stated:

- each sub-problem is solved to its minimiser, where the reference library
  and the program stop their L-BFGS at the configured cap and tolerance
  (as ``glm_cd`` departs for the other coordinates): what remains between
  the two is the program's stopping distance, compounded over the
  alternations because each starts from what the last one left;
- the factors restart from zero in every alternation, where the library and
  the program warm-start them from the last alternation's: the sub-problem
  is strictly convex, so the minimiser is the same;
- the library down-samples and re-projects its data set between
  alternations through RDD joins; here the projection is one contraction a
  bucket and nothing is sampled (the configuration's rate is 1).

What is compared of a factored coordinate is the PRODUCT ``Gamma B``
(``[E, d]``): ``Gamma`` and ``B`` are fixed only up to an invertible k x k
change of basis (and the L2 penalties fix that only up to a rotation).
"""

from __future__ import annotations

import functools
from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference import glm_cd
from benchmark.reference.glm_cd import HI, STEPS


def mf_of(spec: str):
    """(alternations, k) of 'maxIterations,numFactors'."""
    iters, k = (int(p) for p in spec.split(","))
    return iters, k


def group_of(config: dict, name: str) -> str:
    """The group a coordinate reads: a factored coordinate names it, a
    random effect is its own."""
    for f in config.get("factored", []):
        if f["name"] == name:
            return f["group"]
    return name


def buckets_of(problem, config: dict, name: str):
    """The blocks a coordinate reads: its group's."""
    return problem.groups[group_of(config, name)].buckets


@functools.partial(jax.jit, static_argnames=("d",))
def project(x, b, d: int):
    """``x[..., :d] B^T``: the latent features ``[E, r, k]``."""
    return jnp.einsum("erd,kd->erk", x[..., :d], b, precision=HI)


@jax.jit
def products(gammas, b):
    """``Gamma B`` bucket by bucket: every entity's coefficients
    ``[E, d]``."""
    return [jnp.matmul(g, b, precision=HI) for g in gammas]


@functools.partial(jax.jit, static_argnames=("link", "d"))
def _refit_system(xs, ys, ws, offs, gammas, b, l2, link: str, d: int):
    """Margins of every slot, and the gradient ``[k, d]`` and Hessian
    ``[k d, k d]`` of the refit's objective at ``b``."""
    _, d1, d2 = glm_cd._loss(link)
    k = b.shape[0]
    g = l2 * b
    h = l2 * jnp.eye(k * d, dtype=b.dtype)
    zs = []
    for x, y, w, off, gamma in zip(xs, ys, ws, offs, gammas):
        x = x[..., :d]
        coef = jnp.matmul(gamma, b, precision=HI)  # [E, d]
        z = jnp.einsum("erd,ed->er", x, coef, precision=HI) + off
        zs.append(z)
        xr = jnp.einsum("erd,er->ed", x, w * d1(z, y), precision=HI)
        g = g + jnp.einsum("ek,ed->kd", gamma, xr, precision=HI)
        m = jnp.einsum("erd,erf->edf", x * (w * d2(z, y))[..., None], x,
                       precision=HI)
        h = h + jnp.einsum("ek,el,edf->kdlf", gamma, gamma, m,
                           precision=HI).reshape(k * d, k * d)
    p = jnp.linalg.solve(h, g.reshape(-1)).reshape(k, d)
    return zs, p


@functools.partial(jax.jit, static_argnames=("link", "d"))
def _refit_line_values(xs, ys, ws, zs, gammas, b, p, l2, link: str, d: int):
    """The refit's objective at ``b - t p`` for every tried step t."""
    loss, _, _ = glm_cd._loss(link)
    zps = [jnp.einsum("erd,ed->er", x[..., :d],
                      jnp.matmul(gamma, p, precision=HI), precision=HI)
           for x, gamma in zip(xs, gammas)]

    def at(t):
        c = b - t * p
        val = 0.5 * l2 * jnp.vdot(c, c)
        for y, w, z, zp in zip(ys, ws, zs, zps):
            val = val + jnp.sum(w * loss(z - t * zp, y))
        return val

    return jax.lax.map(at, jnp.asarray(STEPS, b.dtype))


def solve_refit(buckets, offs, gammas, b, l2: float, link: str, d: int,
                max_newton: int = 30) -> jax.Array:
    """argmin_B sum_slots w l(gamma_e^T B x + off, y) + l2/2 ||B||^2 by
    safeguarded Newton from ``b`` (``glm_cd.solve_fixed``'s iteration)."""
    xs = tuple(bk.x for bk in buckets)
    ys = tuple(bk.labels for bk in buckets)
    ws = tuple(bk.weights for bk in buckets)
    offs, gammas = tuple(offs), tuple(gammas)
    for _ in range(max_newton):
        zs, p = _refit_system(xs, ys, ws, offs, gammas, b, l2, link, d)
        vals = np.asarray(_refit_line_values(
            xs, ys, ws, tuple(zs), gammas, b, p, l2, link, d))
        vals = np.where(np.isfinite(vals), vals, np.inf)
        best = int(np.argmin(vals))
        if STEPS[best] == 0.0:  # the floor of float32
            break
        b = b - STEPS[best] * p
    return b


@functools.partial(jax.jit, static_argnames=("link", "d"))
def _refit_value_and_gradient(xs, ys, ws, offs, gammas, b, l2, link: str,
                              d: int):
    """The refit's objective at ``b``, a data term a bucket (summed by the
    caller in float64) and the penalty, and its gradient ``[k, d]``."""
    loss, d1, _ = glm_cd._loss(link)
    terms = [0.5 * l2 * jnp.vdot(b, b)]
    g = l2 * b
    for x, y, w, off, gamma in zip(xs, ys, ws, offs, gammas):
        x = x[..., :d]
        z = jnp.einsum("erd,ed->er", x, jnp.matmul(gamma, b, precision=HI),
                       precision=HI) + off
        terms.append(jnp.sum(w * loss(z, y)))
        xr = jnp.einsum("erd,er->ed", x, w * d1(z, y), precision=HI)
        g = g + jnp.einsum("ek,ed->kd", gamma, xr, precision=HI)
    return jnp.stack(terms), g


def refit_objective(problem, config: dict, name: str, coefs: dict, b=None,
                    scores=None):
    """What the refit of factored coordinate ``name`` minimises, evaluated
    at ANY coefficients in the layout the fit returns: the value and the
    gradient ``[k, d]`` of ``sum_slots w l(gamma_e^T B x + off, y) + l2/2
    ||B||^2`` over EVERY slot of the group at full weight, at ``coefs``'
    factors and ``b`` (``coefs``' own B where none is given). The offsets
    are the problem's plus the scores of every OTHER coordinate: computed
    here from ``coefs``, or, where ``scores`` gives somebody's total
    per-row scores of ALL coordinates at ``coefs``, those less this
    coordinate's own as computed here. The last refit of a sweep in which
    the factored coordinate comes last saw exactly these offsets."""
    spec = next(f for f in config["factored"] if f["name"] == name)
    buckets = buckets_of(problem, config, name)
    if scores is None:
        off = problem.offsets
        for other in config["updating_sequence"]:
            if other != name:
                off = off + _coordinate_scores(problem, config, other,
                                               coefs[other])
    else:
        off = problem.offsets + jnp.asarray(scores, jnp.float32) \
            - _coordinate_scores(problem, config, name, coefs[name])
    mine = coefs[name]
    b = jnp.asarray(mine["B"] if b is None else b, jnp.float32)
    terms, g = _refit_value_and_gradient(
        tuple(bk.x for bk in buckets), tuple(bk.labels for bk in buckets),
        tuple(bk.weights for bk in buckets),
        tuple(glm_cd.gather_rows(off, bk.row_ids) for bk in buckets),
        tuple(jnp.asarray(g, jnp.float32) for g in mine["gammas"]), b,
        glm_cd.l2_of(spec["refit_optimizer"]), config["link"],
        problem.groups[spec["group"]].d_entity)
    return float(np.sum(np.asarray(terms, np.float64))), g


def start_matrix(spec: dict, d: int) -> np.ndarray:
    """The ``B0`` a factored coordinate's configuration states (``start``):
    ``[k, d]`` standard normals from numpy's ``default_rng(seed)`` over k
    (the reference library's scale: sd 1/k, ProjectionMatrix.scala:96-110),
    clipped to [-1, 1]. An input of the problem, drawn here and not taken
    from the program."""
    start, k = spec["start"], mf_of(spec["mf"])[1]
    if start["law"] != "normal_over_k_clipped":
        raise ValueError(f"unknown start law {start['law']!r}")
    rng = np.random.default_rng(int(start["seed"]))
    return np.clip(rng.normal(0.0, 1.0, (k, d)) / k, -1.0, 1.0)


def solve_factored(buckets, off, b0, spec: dict, link: str, d: int,
                   re_newton: int):
    """The alternation from ``b0`` and zero factors against the per-row
    offsets ``off``; returns the factors bucket by bucket and B."""
    alternations, k = mf_of(spec["mf"])
    l2_latent = glm_cd.l2_of(spec["optimizer"])
    l2_refit = glm_cd.l2_of(spec["refit_optimizer"])
    b = jnp.asarray(b0, jnp.float32)
    if b.shape != (k, d):
        raise ValueError(f"B0 is {b.shape}, the configuration's ({k}, {d})")
    offs = [glm_cd.gather_rows(off, bk.row_ids) for bk in buckets]
    gammas = [jnp.zeros((bk.x.shape[0], k), jnp.float32) for bk in buckets]
    for _ in range(alternations):
        gammas = [glm_cd._solve_bucket(project(bk.x, b, d), bk.labels,
                                       bk.weights, o, l2_latent, link,
                                       re_newton)
                  for bk, o in zip(buckets, offs)]
        b = solve_refit(buckets, offs, gammas, b, l2_refit, link, d)
    return gammas, b


def _coordinate_scores(problem, config: dict, name: str, coefs) -> jax.Array:
    """The per-row scores of one coordinate's coefficients, in the layout
    the fit returns."""
    fixed = config["fixed"]["name"]
    if name == fixed:
        return glm_cd.matvec(problem.x, jnp.asarray(coefs, jnp.float32))
    buckets = buckets_of(problem, config, name)
    per_entity = tuple(jnp.asarray(c, jnp.float32)
                       for c in entity_coefficients(coefs))
    # a factored coordinate's products are d wide, the blocks d_pad
    xs = tuple(b.x[..., :c.shape[1]] for b, c in zip(buckets, per_entity))
    return glm_cd.random_scores(xs, tuple(b.row_ids for b in buckets),
                                per_entity, problem.n_rows)


def entity_coefficients(coefs):
    """A group coordinate's coefficients entity by entity, bucket by
    bucket: a random effect's as they are, of a factored one (``{"gammas":
    [...], "B": ...}``) the products ``Gamma B``."""
    if isinstance(coefs, dict):
        return products(
            tuple(jnp.asarray(g, jnp.float32) for g in coefs["gammas"]),
            jnp.asarray(coefs["B"], jnp.float32))
    return coefs


def scores_of(problem, config: dict, coefs: Dict[str, object]) -> jax.Array:
    """Total per-row score of any coefficients in the layout the fit
    returns (a factored coordinate's as ``{"gammas": [...], "B": ...}`` or
    as its products ``[E, d]`` a bucket)."""
    total = jnp.zeros((problem.n_rows,), jnp.float32)
    for name in config["updating_sequence"]:
        total = total + _coordinate_scores(problem, config, name,
                                           coefs[name])
    return total


def _penalty(config: dict, name: str, coefs) -> float:
    fixed = config["fixed"]
    if name == fixed["name"]:
        return 0.5 * glm_cd.l2_of(fixed["optimizer"]) * float(
            jnp.vdot(coefs, coefs))
    for g in config.get("random", []):
        if g["name"] == name:
            return 0.5 * glm_cd.l2_of(g["optimizer"]) * sum(
                float(jnp.vdot(c, c)) for c in coefs)
    f = next(f for f in config["factored"] if f["name"] == name)
    return (0.5 * glm_cd.l2_of(f["optimizer"]) * sum(
        float(jnp.vdot(g, g)) for g in coefs["gammas"])
        + 0.5 * glm_cd.l2_of(f["refit_optimizer"]) * float(
            jnp.vdot(coefs["B"], coefs["B"])))


def fit(problem, config: dict, re_newton: int = 10) -> dict:
    """One coordinate-descent fit from zero in the configured order:
    objective after every coordinate update, final coefficients per
    coordinate, final scores, and the ``b0`` every factored coordinate
    started from (``start_matrix``)."""
    link = config["link"]
    n = problem.n_rows
    fixed = config["fixed"]
    randoms = {g["name"]: g for g in config.get("random", [])}
    factored = {f["name"]: f for f in config.get("factored", [])}
    zeros = jnp.zeros((n,), jnp.float32)
    score = {name: zeros for name in config["updating_sequence"]}
    coefs: Dict[str, object] = {}
    b0 = {name: start_matrix(f, problem.groups[f["group"]].d_entity)
          for name, f in factored.items()}
    for name in config["updating_sequence"]:
        if name == fixed["name"]:
            coefs[name] = jnp.zeros((problem.x.shape[1],), jnp.float32)
        elif name in randoms:
            coefs[name] = [jnp.zeros(b.x.shape[::2], jnp.float32)
                           for b in buckets_of(problem, config, name)]
        else:
            k = mf_of(factored[name]["mf"])[1]
            coefs[name] = {
                "gammas": [jnp.zeros((b.x.shape[0], k), jnp.float32)
                           for b in buckets_of(problem, config, name)],
                "B": jnp.asarray(b0[name], jnp.float32)}
    history: List[float] = []
    for _ in range(int(config["iterations"])):
        for name in config["updating_sequence"]:
            residual = zeros
            for other, s in score.items():
                if other != name:
                    residual = residual + s
            off = problem.offsets + residual
            if name == fixed["name"]:
                coefs[name] = glm_cd.solve_fixed(
                    problem.x, problem.labels, problem.weights, off,
                    glm_cd.l2_of(fixed["optimizer"]), link)
            elif name in randoms:
                coefs[name] = [
                    glm_cd._solve_bucket(
                        b.x, b.labels, b.weights,
                        glm_cd.gather_rows(off, b.row_ids),
                        glm_cd.l2_of(randoms[name]["optimizer"]), link,
                        re_newton)
                    for b in buckets_of(problem, config, name)]
            else:
                spec = factored[name]
                gammas, b = solve_factored(
                    buckets_of(problem, config, name), off,
                    coefs[name]["B"], spec, link,
                    problem.groups[spec["group"]].d_entity, re_newton)
                coefs[name] = {"gammas": gammas, "B": b}
            score[name] = _coordinate_scores(problem, config, name,
                                             coefs[name])
            total = zeros
            for s in score.values():
                total = total + s
            obj = float(glm_cd._data_loss(total, problem.offsets,
                                          problem.labels, problem.weights,
                                          link))
            obj += sum(_penalty(config, c, v) for c, v in coefs.items())
            history.append(obj)
    total = zeros
    for s in score.values():
        total = total + s
    return {"history": np.asarray(history, np.float64), "coefs": coefs,
            "scores": total, "b0": b0}
