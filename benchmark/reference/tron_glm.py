"""The plain reference of an L2 GLM fit by trust-region Newton (TRON).

Straightforward ``jax.numpy`` in float32 with every contraction at
``precision="highest"``: no kernels, no program code, no program data. It
reads the problem's plain arrays (``recipes/dense_tron.py``: ``x f32[n, d]``,
labels, offsets, weights) and the configuration file, X in blocks of
``BLOCK_ROWS`` rows:

    margins   = X w + offsets
    value     = sum_i weight_i l(margin_i, y_i) + 0.5 l2 |w|^2
    gradient  = X^T (weight * l'(margins, y)) + l2 w
    H v       = X^T (D * (X v)) + l2 v,   D = weight * l''(margins at w, y)

and the trust-region Newton method of Lin, Weng & Keerthi ("Trust Region
Newton Method for Large-Scale Logistic Regression", JMLR 9, 2008:
Algorithm 1 with Algorithm 2's conjugate gradient, as LIBLINEAR's ``tron``
runs it), from zero, with the constants the reference library's port uses
(TRON.scala; ``eta`` 1e-4 / 0.25 / 0.75, ``sigma`` 0.25 / 0.5 / 4, CG to a
residual of 0.1 |g|):

- the first radius is |g(0)|; after the first trial step it is at most that
  step's length;
- CG (Steihaug): from s = 0, r = -g, d = r; a step of rTr / dTHd; a step
  that would leave the region ends on its boundary; it stops at
  |r| <= 0.1 |g| or on the boundary, and after ``MAX_CG`` steps;
- a trial step is taken where the actual reduction exceeds 1e-4 of the
  predicted ``-0.5 (g.s - s.r)``; the radius follows LIBLINEAR's
  interpolation rule either way.

Departures from LIBLINEAR, stated: CG stops after ``MAX_CG`` = 20 steps
(the reference library's cap), and the solve stops by the reference
library's rules (Optimizer.scala), not LIBLINEAR's: on an accepted step
where |g| <= tol |g(0)| or the value moved by at most tol |f(0)|, at the
configuration's cap of accepted steps, or after more than 5 rejected steps
in a row. Everything is counted: the CG steps of every outer step and the
outer steps attempted, accepted or rejected, and the passes over X they
cost. The d-space arithmetic runs in float32 as the reference's vectors
are.

Besides the fit, ``value_and_grad``, ``hvp``, ``value`` and ``scores_of``
evaluate at ANY ``w``: the check holds the program's objective and scores
at the program's own coefficients to them.
"""

from __future__ import annotations

import functools
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

HI = "highest"
BLOCK_ROWS = 1 << 17  # rows of X the reference holds a second time
ETA0, ETA1, ETA2 = 1e-4, 0.25, 0.75
SIGMA1, SIGMA2, SIGMA3 = 0.25, 0.5, 4.0
CG_XI = 0.1
MAX_CG = 20
MAX_FAILURES = 5


def _loss(link: str):
    if link == "logistic":
        return (lambda z, y: jnp.logaddexp(0.0, z) - y * z,
                lambda z, y: jax.nn.sigmoid(z) - y,
                lambda z, y: jax.nn.sigmoid(z) * (1.0 - jax.nn.sigmoid(z)))
    if link == "poisson":
        return (lambda z, y: jnp.exp(z) - y * z,
                lambda z, y: jnp.exp(z) - y,
                lambda z, y: jnp.exp(z))
    raise ValueError(f"unknown link {link!r}")


def optimizer_of(optimizer: str) -> dict:
    """'maxIter,tol,regWeight,downSampling,type,L2' -> cap, tolerance and
    L2 weight of a TRON string."""
    parts = [p.strip() for p in optimizer.split(",")]
    if (parts[4].upper() != "TRON" or parts[5].upper() != "L2"
            or float(parts[3]) != 1.0):
        raise ValueError("the reference covers TRON with L2 and no "
                         f"down-sampling: {optimizer!r}")
    return {"cap": int(parts[0]), "tol": float(parts[1]),
            "l2": float(parts[2])}


def _row_blocks(x, n_vectors, body, init):
    """``body(acc, xb, vecs_b, mask_b, start)`` over blocks of
    ``BLOCK_ROWS`` rows of ``x`` and of each ``[n]`` vector. Every block has
    one shape: the last is shifted back to end at row n, and ``mask_b`` is 0
    on the rows an earlier block has already had."""
    n, d = x.shape
    b = min(n, BLOCK_ROWS)

    def step(i, acc):
        start = jnp.minimum(i * b, n - b)
        xb = jax.lax.dynamic_slice(x, (start, 0), (b, d))
        vecs = [jax.lax.dynamic_slice(v, (start,), (b,)) for v in n_vectors]
        mask = (start + jnp.arange(b) >= i * b).astype(x.dtype)
        return body(acc, xb, vecs, mask, start)

    return jax.lax.fori_loop(0, -(-n // b), step, init)


@jax.jit
def matvec(x, v):
    """X v."""
    def body(out, xb, _, mask, start):
        # rows an earlier block wrote are written again with equal values
        return jax.lax.dynamic_update_slice(
            out, jnp.matmul(xb, v, precision=HI), (start,))

    return _row_blocks(x, [], body, jnp.zeros((x.shape[0],), x.dtype))


@jax.jit
def rmatvec(x, u):
    """X^T u."""
    def body(acc, xb, vecs, mask, start):
        return acc + jnp.matmul(mask * vecs[0], xb, precision=HI)

    return _row_blocks(x, [u], body, jnp.zeros((x.shape[1],), x.dtype))


@functools.partial(jax.jit, static_argnames=("link",))
def _value(x, y, off, wts, w, l2, link: str):
    loss, _, _ = _loss(link)
    z = matvec(x, w) + off
    return jnp.sum(wts * loss(z, y)) + 0.5 * l2 * jnp.vdot(w, w)


@functools.partial(jax.jit, static_argnames=("link",))
def _value_and_grad(x, y, off, wts, w, l2, link: str):
    loss, d1, _ = _loss(link)
    z = matvec(x, w) + off
    value = jnp.sum(wts * loss(z, y)) + 0.5 * l2 * jnp.vdot(w, w)
    return value, rmatvec(x, wts * d1(z, y)) + l2 * w


@functools.partial(jax.jit, static_argnames=("link",))
def _curvature(x, y, off, wts, w, link: str):
    """D = weight * l''(margins at w): one pass over X."""
    _, _, d2 = _loss(link)
    return wts * d2(matvec(x, w) + off, y)


@jax.jit
def _hvp(x, curv, v, l2):
    """X^T (D * (X v)) + l2 v: two passes over X."""
    return rmatvec(x, curv * matvec(x, v)) + l2 * v


def _arrays(problem):
    return (problem.x, problem.labels, problem.offsets, problem.weights)


def _l2(config: dict) -> float:
    return optimizer_of(config["fixed"]["optimizer"])["l2"]


def value_and_grad(problem, config: dict, w):
    """The objective and its gradient at ``w``."""
    with jax.default_matmul_precision(HI):
        return _value_and_grad(*_arrays(problem),
                               jnp.asarray(w, jnp.float32), _l2(config),
                               link=config["link"])


def value(problem, config: dict, w) -> float:
    with jax.default_matmul_precision(HI):
        return float(_value(*_arrays(problem), jnp.asarray(w, jnp.float32),
                            _l2(config), link=config["link"]))


def hvp(problem, config: dict, w, v):
    """The Hessian at ``w`` times ``v``."""
    x, y, off, wts = _arrays(problem)
    with jax.default_matmul_precision(HI):
        curv = _curvature(x, y, off, wts, jnp.asarray(w, jnp.float32),
                          link=config["link"])
        return _hvp(x, curv, jnp.asarray(v, jnp.float32), _l2(config))


def scores_of(problem, config: dict, coefs: Dict[str, object]) -> jax.Array:
    """The training scores X w of a model (no offsets)."""
    w = jnp.asarray(coefs[config["fixed"]["name"]], jnp.float32)
    with jax.default_matmul_precision(HI):
        return matvec(problem.x, w)


def _norm(v) -> float:
    return float(jnp.linalg.norm(v))


def _steihaug(hvp_of, g, delta: float):
    """Algorithm 2 of the paper: CG on H s = -g inside |s| <= delta.
    Returns the step, the final residual, the CG steps taken and how near
    the stop came to another count, by each of its two exits: the least
    relative distance of the residual from 0.1 |g| and of the step's length
    from the radius, over the steps it took."""
    s = jnp.zeros_like(g)
    r = -g
    d = r
    rtr = float(jnp.vdot(r, r))
    cg_tol = CG_XI * _norm(g)
    steps = 0
    by_residual = abs(np.sqrt(rtr) / cg_tol - 1.0)
    by_boundary = np.inf
    while np.sqrt(rtr) > cg_tol and steps < MAX_CG:
        steps += 1
        hd = hvp_of(d)
        alpha = rtr / float(jnp.vdot(d, hd))
        s_next = s + np.float32(alpha) * d
        by_boundary = min(by_boundary, abs(_norm(s_next) / delta - 1.0))
        if _norm(s_next) > delta:
            # the step that would leave the region ends on its boundary
            std, sts, dtd = (float(jnp.vdot(s, d)), float(jnp.vdot(s, s)),
                             float(jnp.vdot(d, d)))
            gap = delta * delta - sts
            rad = np.sqrt(std * std + dtd * gap)
            alpha = (gap / (std + rad) if std >= 0
                     else (rad - std) / dtd)
            s = s + np.float32(alpha) * d
            r = r - np.float32(alpha) * hd
            break
        s = s_next
        r = r - np.float32(alpha) * hd
        rtr_new = float(jnp.vdot(r, r))
        by_residual = min(by_residual, abs(np.sqrt(rtr_new) / cg_tol - 1.0))
        d = r + np.float32(rtr_new / rtr) * d
        rtr = rtr_new
    return s, r, steps, (by_residual, by_boundary)


def fit(problem, config: dict) -> dict:
    """TRON from zero under the configuration's optimizer string. Returns
    the iterate, the value at every accepted iterate (``values[0]`` at
    zero), as ``history`` what a one-sweep coordinate descent records (the
    final value), and the work: ``cg_steps`` (all outer steps),
    ``cg_per_step`` (one entry an attempted outer step), ``attempted``,
    ``accepted``, ``passes`` (reads of X: 2 for the first value and
    gradient, 3 an attempted step, 2 a CG step), why it stopped, and, an
    attempted step each, how near its CG came to another count by its
    residual test and by the trust region's boundary
    (``cg_residual_margins``, ``cg_boundary_margins``: ``_steihaug``'s)."""
    opt = optimizer_of(config["fixed"]["optimizer"])
    x, y, off, wts = _arrays(problem)
    link, l2, tol = config["link"], opt["l2"], opt["tol"]
    with jax.default_matmul_precision(HI):
        w = jnp.zeros((x.shape[1],), jnp.float32)
        f, g = _value_and_grad(x, y, off, wts, w, l2, link=link)
        f = float(f)
        f0, gnorm0 = abs(f), _norm(g)
        delta = gnorm0
        values, cg_per_step, accepted_steps = [f], [], []
        cg_margins = {"residual": [], "boundary": []}
        fails, accepted, stopped = 0, 0, None
        while stopped is None:
            curv = _curvature(x, y, off, wts, w, link=link)
            s, r, steps, margins = _steihaug(
                lambda v: _hvp(x, curv, v, l2), g, delta)
            cg_per_step.append(steps)
            cg_margins["residual"].append(float(margins[0]))
            cg_margins["boundary"].append(float(margins[1]))
            w_new = w + s
            f_new, g_new = _value_and_grad(x, y, off, wts, w_new, l2,
                                           link=link)
            f_new = float(f_new)
            gs = float(jnp.vdot(g, s))
            prered = -0.5 * (gs - float(jnp.vdot(s, r)))
            actred = f - f_new
            snorm = _norm(s)
            if len(cg_per_step) == 1:
                delta = min(delta, snorm)
            denom = f_new - f - gs
            alpha = (SIGMA3 if denom <= 0
                     else max(SIGMA1, -0.5 * (gs / denom)))
            if actred < ETA0 * prered:
                delta = min(max(alpha, SIGMA1) * snorm, SIGMA2 * delta)
            elif actred < ETA1 * prered:
                delta = max(SIGMA1 * delta, min(alpha * snorm,
                                                SIGMA2 * delta))
            elif actred < ETA2 * prered:
                delta = max(SIGMA1 * delta, min(alpha * snorm,
                                                SIGMA3 * delta))
            else:
                delta = max(delta, min(alpha * snorm, SIGMA3 * delta))
            if actred > ETA0 * prered and np.isfinite(f_new):
                accepted += 1
                accepted_steps.append(True)
                fails = 0
                moved = abs(f - f_new)
                w, f, g = w_new, f_new, g_new
                values.append(f)
                if _norm(g) <= tol * gnorm0:
                    stopped = "gradient converged"
                elif moved <= tol * f0:
                    stopped = "function values converged"
                elif accepted >= opt["cap"]:
                    stopped = "max iterations"
            else:
                accepted_steps.append(False)
                fails += 1
                if fails > MAX_FAILURES:
                    stopped = "objective not improving"
    attempted = len(cg_per_step)
    return {"coefs": {config["fixed"]["name"]: w},
            "history": np.asarray(values[-1:], np.float64),
            "values": np.asarray(values, np.float64),
            "cg_steps": int(sum(cg_per_step)), "cg_per_step": cg_per_step,
            "attempted": attempted, "accepted": accepted,
            "accepted_steps": accepted_steps,
            "cg_residual_margins": cg_margins["residual"],
            "cg_boundary_margins": cg_margins["boundary"],
            "passes": 2 + 3 * attempted + 2 * int(sum(cg_per_step)),
            "stopped": stopped}
