"""The plain reference of an L2 GLM fit over a hashed sparse matrix.

Straightforward ``jax.numpy`` in float32: no kernels, no program code, no
program data, no layout. It reads the problem's plain arrays
(``recipes/sparse_glm.py``: ``cols i32[n, k]``, ``vals f32[n, k]``, labels,
offsets, weights) and the configuration file:

    margins_i = sum_s vals[i, s] * w[cols[i, s]] + offset_i      (row blocks)
    value     = sum_i weight_i * l(margins_i, y_i) + 0.5 * l2 * |w|^2
    gradient  = zeros(d).at[cols].add(vals * (weight * l'(margins, y))[:, None])
                + l2 * w                              (one plain scatter-add)

and a textbook L-BFGS (Nocedal & Wright, algorithm 7.4: the two-loop
recursion; Armijo backtracking) run from zero for the configuration's
iteration cap. ``jax.default_matmul_precision("highest")`` is set around
every evaluation, as for every reference here, although this one multiplies
no matrices: the products are gathers, elementwise multiplies and sums,
which the chip does in float32 as written.

Departures from ``glm_cd.py``'s conventions, stated:

- ``glm_cd`` solves each coordinate update to its MINIMISER (safeguarded
  Newton) and the program's stopping distance is the gap. Here the
  configuration's cap (2 iterations) ends the solve far from the minimiser,
  and a Newton system over a million columns is not plain: the reference
  runs its own L-BFGS for the same cap, and the program's iterate is held
  to an independently written optimizer's. What the two share is the
  published method and its constants, the reference library's
  (LBFGS.scala:152-156: history 10; Breeze's backtracking: sufficient
  decrease 1e-4, halving, a first step of ``1 / max(|p|, 1)``), not code.
- The line search prices every trial by a full evaluation of the value
  (margins from the matrix), where the program caches margins.
- The optimizer string's tolerance is read and not used: at 1e-12 no test
  it feeds can end a solve before the cap (a reference run that fails its
  line search stops, and says so in ``stopped``).
- Besides the fit, ``value_and_grad`` and ``scores_of`` evaluate at ANY
  ``w``: the check holds the program's objective and scores at the
  program's own coefficients to them.
"""

from __future__ import annotations

import functools
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

BLOCK_ROWS = 1 << 19  # rows whose gathered values the reference holds
HISTORY = 10
C1 = 1e-4
SHRINK = 0.5
MAX_LINE_SEARCH = 30
CAUTIOUS_EPS = 1e-10  # a pair is kept where s.y > eps |s| |y|


def _loss(link: str):
    if link == "logistic":
        return (lambda z, y: jnp.logaddexp(0.0, z) - y * z,
                lambda z, y: jax.nn.sigmoid(z) - y)
    if link == "poisson":
        return (lambda z, y: jnp.exp(z) - y * z,
                lambda z, y: jnp.exp(z) - y)
    raise ValueError(f"unknown link {link!r}")


def optimizer_of(optimizer: str) -> dict:
    """'maxIter,tol,regWeight,downSampling,type,L2' -> cap and L2 weight."""
    parts = [p.strip() for p in optimizer.split(",")]
    if (parts[4].upper() != "LBFGS" or parts[5].upper() != "L2"
            or float(parts[3]) != 1.0):
        raise ValueError("the reference covers L-BFGS with L2 and no "
                         f"down-sampling: {optimizer!r}")
    return {"cap": int(parts[0]), "l2": float(parts[2])}


@jax.jit
def scores(cols, vals, w):
    """X w, ``BLOCK_ROWS`` rows at a time (the last block shifted back to
    end at row n: the rows it overlaps are written again, equal)."""
    n, k = cols.shape
    b = min(n, BLOCK_ROWS)

    def step(i, out):
        start = jnp.minimum(i * b, n - b)
        cb = jax.lax.dynamic_slice(cols, (start, 0), (b, k))
        vb = jax.lax.dynamic_slice(vals, (start, 0), (b, k))
        return jax.lax.dynamic_update_slice(
            out, jnp.sum(vb * w[cb], axis=1), (start,))

    return jax.lax.fori_loop(0, -(-n // b), step, jnp.zeros((n,), w.dtype))


@functools.partial(jax.jit, static_argnames=("link",))
def _value(cols, vals, y, off, wts, w, l2, link: str):
    loss, _ = _loss(link)
    z = scores(cols, vals, w) + off
    return jnp.sum(wts * loss(z, y)) + 0.5 * l2 * jnp.vdot(w, w)


@functools.partial(jax.jit, static_argnames=("link",))
def _value_and_grad(cols, vals, y, off, wts, w, l2, link: str):
    loss, d1 = _loss(link)
    z = scores(cols, vals, w) + off
    value = jnp.sum(wts * loss(z, y)) + 0.5 * l2 * jnp.vdot(w, w)
    u = wts * d1(z, y)
    grad = jnp.zeros_like(w).at[cols].add(vals * u[:, None]) + l2 * w
    return value, grad


def _arrays(problem):
    return (problem.cols, problem.vals, problem.labels, problem.offsets,
            problem.weights)


def value_and_grad(problem, config: dict, w):
    """The objective and its gradient at ``w``."""
    l2 = optimizer_of(config["fixed"]["optimizer"])["l2"]
    with jax.default_matmul_precision("highest"):
        return _value_and_grad(*_arrays(problem), jnp.asarray(w, jnp.float32),
                               l2, link=config["link"])


def value(problem, config: dict, w) -> float:
    l2 = optimizer_of(config["fixed"]["optimizer"])["l2"]
    with jax.default_matmul_precision("highest"):
        return float(_value(*_arrays(problem), jnp.asarray(w, jnp.float32),
                            l2, link=config["link"]))


def scores_of(problem, config: dict, coefs: Dict[str, object]) -> jax.Array:
    """The training scores X w of a model (no offsets), as ``glm_cd``'s."""
    w = jnp.asarray(coefs[config["fixed"]["name"]], jnp.float32)
    with jax.default_matmul_precision("highest"):
        return scores(problem.cols, problem.vals, w)


def _direction(g, pairs):
    """-H g by the two-loop recursion over the kept (s, y), oldest first."""
    q = g
    alphas = []
    for s, y in reversed(pairs):
        a = jnp.vdot(s, q) / jnp.vdot(y, s)
        q = q - a * y
        alphas.append(a)
    if pairs:
        s, y = pairs[-1]
        q = q * (jnp.vdot(s, y) / jnp.vdot(y, y))
    for (s, y), a in zip(pairs, reversed(alphas)):
        b = jnp.vdot(y, q) / jnp.vdot(y, s)
        q = q + s * (a - b)
    return -q


def fit(problem, config: dict) -> dict:
    """L-BFGS from zero for the configuration's cap. Returns the iterate,
    the value at every iterate (``values[0]`` at zero) and, as ``history``,
    what a one-sweep coordinate descent records: the final value."""
    opt = optimizer_of(config["fixed"]["optimizer"])
    arrays, link, l2 = _arrays(problem), config["link"], opt["l2"]
    with jax.default_matmul_precision("highest"):
        w = jnp.zeros((problem.n_features,), jnp.float32)
        f, g = _value_and_grad(*arrays, w, l2, link=link)
        f = float(f)
        values, trials, pairs, stopped = [f], [], [], None
        for _ in range(opt["cap"]):
            p = _direction(g, pairs)
            slope = float(jnp.vdot(p, g))
            if slope >= 0:
                p, slope = -g, -float(jnp.vdot(g, g))
            t = 1.0 if pairs else 1.0 / max(float(jnp.linalg.norm(p)), 1.0)
            for trial in range(MAX_LINE_SEARCH + 1):
                w_t = w + np.float32(t) * p
                f_t = float(_value(*arrays, w_t, l2, link=link))
                if np.isfinite(f_t) and f_t <= f + C1 * t * slope:
                    break
                t *= SHRINK
            else:
                stopped = "line search failed"
                break
            trials.append(trial + 1)
            f_new, g_new = _value_and_grad(*arrays, w_t, l2, link=link)
            s, y = w_t - w, g_new - g
            if float(jnp.vdot(s, y)) > CAUTIOUS_EPS * float(
                    jnp.linalg.norm(s)) * float(jnp.linalg.norm(y)):
                pairs = (pairs + [(s, y)])[-HISTORY:]
            w, f, g = w_t, float(f_new), g_new
            values.append(f)
    return {"coefs": {config["fixed"]["name"]: w},
            "history": np.asarray(values[-1:], np.float64),
            "values": np.asarray(values, np.float64),
            "line_search_trials": trials, "stopped": stopped}
