"""The plain reference of a GLM / GLMix coordinate-descent fit.

Straightforward ``jax.numpy`` in float32 with every contraction at
``precision="highest"``: no kernels, no program code, no program data.
It reads the problem's plain arrays (``recipes/``) and the configuration
file, and follows the published semantics (CoordinateDescent.scala:41-271,
as ``photon_ml_tpu/algorithm/coordinate_descent.py`` restates them): for
each iteration, for each coordinate in the updating sequence, re-solve
that coordinate's L2-regularised GLM against the other coordinates'
scores as offsets, then record

    objective = sum_i w_i l(total_score_i + offset_i, y_i)
                + sum_c 0.5 * l2_c * ||coef_c||^2.

Departure, stated: each coordinate update is solved to its minimiser by
a safeguarded Newton iteration, where the program stops its L-BFGS / TRON
at the configuration's tolerance. The problems are strictly convex, so
the minimiser is the one point both aim at; the gap that remains is the
program's stopping distance, and ``PERF.md`` gives its readings.

Rows go through in blocks, and entities bucket by bucket, so that the
reference fits beside the data it checks.
"""

from __future__ import annotations

import functools
from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np

HI = "highest"
STEPS = (1.0, 0.5, 0.25, 0.125, 0.03125, 0.0)  # Newton step lengths tried
BLOCK_ROWS = 1 << 19  # rows of X the reference holds a second time


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


def l2_of(optimizer: str) -> float:
    """The L2 weight of 'maxIter,tol,regWeight,downSampling,type,L2'."""
    parts = [p.strip() for p in optimizer.split(",")]
    if parts[5].upper() != "L2" or float(parts[3]) != 1.0:
        raise ValueError("the reference covers L2, no down-sampling: "
                         f"{optimizer!r}")
    return float(parts[2])


def _block_rows(n: int) -> int:
    return min(n, BLOCK_ROWS)


def _row_blocks(x, n_vectors, body, init):
    """``body(acc, xb, vecs_b, mask_b, start)`` over blocks of
    ``_block_rows(n)`` rows of ``x`` and of each ``[n]`` vector. Every
    block has one shape: the last is shifted back to end at row n, and
    ``mask_b`` is 0 on the rows an earlier block has already had."""
    n, d = x.shape
    b = _block_rows(n)

    def step(i, acc):
        start = jnp.minimum(i * b, n - b)
        xb = jax.lax.dynamic_slice(x, (start, 0), (b, d))
        vecs = [jax.lax.dynamic_slice(v, (start,), (b,)) for v in n_vectors]
        mask = (start + jnp.arange(b) >= i * b).astype(x.dtype)
        return body(acc, xb, vecs, mask, start)

    return jax.lax.fori_loop(0, -(-n // b), step, init)


# -- fixed effect -------------------------------------------------------------


@jax.jit
def matvec(x, v):
    """X v, row blocks at a time, at the highest precision."""
    def body(out, xb, _, mask, start):
        # rows an earlier block wrote are written again with equal values
        return jax.lax.dynamic_update_slice(
            out, jnp.matmul(xb, v, precision=HI), (start,))

    return _row_blocks(x, [], body, jnp.zeros((x.shape[0],), x.dtype))


@functools.partial(jax.jit, static_argnames=("link",))
def _fe_newton_system(x, y, wts, off, coef, l2, link: str):
    _, d1, d2 = _loss(link)
    n, d = x.shape

    def body(acc, xb, vecs, mask, start):
        z, g, h = acc
        yb, wb, ob = vecs
        zb = jnp.matmul(xb, coef, precision=HI) + ob
        r = mask * wb * d1(zb, yb)
        c = mask * wb * d2(zb, yb)
        g = g + jnp.matmul(r, xb, precision=HI)
        h = h + jnp.matmul((xb * c[:, None]).T, xb, precision=HI)
        return jax.lax.dynamic_update_slice(z, zb, (start,)), g, h

    z, g, h = _row_blocks(
        x, [y, wts, off], body,
        (jnp.zeros((n,), x.dtype), jnp.zeros((d,), x.dtype),
         jnp.zeros((d, d), x.dtype)))
    g = g + l2 * coef
    h = h + l2 * jnp.eye(d, dtype=x.dtype)
    return z, g, jnp.linalg.solve(h, g)


@functools.partial(jax.jit, static_argnames=("link",))
def _fe_line_values(z, zp, y, wts, coef, p, l2, link: str):
    loss, _, _ = _loss(link)
    ts = jnp.asarray(STEPS, z.dtype)

    def at(t):
        c = coef - t * p
        return (jnp.sum(wts * loss(z - t * zp, y))
                + 0.5 * l2 * jnp.vdot(c, c))

    return jax.lax.map(at, ts)


def solve_fixed(x, y, wts, off, l2: float, link: str,
                max_newton: int = 20) -> jax.Array:
    """argmin_c sum w l(Xc + off, y) + l2/2 ||c||^2, from zero."""
    coef = jnp.zeros((x.shape[1],), x.dtype)
    for _ in range(max_newton):
        z, _, p = _fe_newton_system(x, y, wts, off, coef, l2, link)
        zp = matvec(x, p)
        vals = np.asarray(_fe_line_values(z, zp, y, wts, coef, p, l2, link))
        vals = np.where(np.isfinite(vals), vals, np.inf)
        best = int(np.argmin(vals))
        # Stop at the floor of float32: no tried step lowers the value.
        if STEPS[best] == 0.0:
            break
        coef = coef - STEPS[best] * p
    return coef


# -- random effect ------------------------------------------------------------


def _cg(h, g, iters: int):
    """Batched conjugate gradients on explicit SPD ``h[E, d, d]``."""
    def mv(v):
        return jnp.einsum("eij,ej->ei", h, v, precision=HI)

    def body(_, s):
        xk, r, p, rs = s
        hp = mv(p)
        alpha = rs / jnp.maximum(jnp.sum(p * hp, -1), 1e-30)
        xk = xk + alpha[:, None] * p
        r = r - alpha[:, None] * hp
        rs_new = jnp.sum(r * r, -1)
        p = r + (rs_new / jnp.maximum(rs, 1e-30))[:, None] * p
        return xk, r, p, rs_new

    x0 = jnp.zeros_like(g)
    return jax.lax.fori_loop(
        0, iters, body, (x0, g, g, jnp.sum(g * g, -1)))[0]


@functools.partial(jax.jit, static_argnames=("link", "newton"))
def _solve_bucket(x, y, wts, off, l2, link: str, newton: int):
    """Per-entity minimisers ``[E, d_pad]`` of one bucket, from zero."""
    loss, d1, d2 = _loss(link)
    e, r, d = x.shape
    ts = jnp.asarray(STEPS, x.dtype)

    def value(c, z):
        return (jnp.sum(wts * loss(z, y), -1)
                + 0.5 * l2 * jnp.sum(c * c, -1))

    def step(_, coef):
        z = jnp.einsum("erd,ed->er", x, coef, precision=HI) + off
        g = (jnp.einsum("erd,er->ed", x, wts * d1(z, y), precision=HI)
             + l2 * coef)
        h = (jnp.einsum("erd,er,erf->edf", x, wts * d2(z, y), x,
                        precision=HI)
             + l2 * jnp.eye(d, dtype=x.dtype))
        p = _cg(h, g, d + 8)
        zp = jnp.einsum("erd,ed->er", x, p, precision=HI)
        vals = jnp.stack([value(coef - t * p, z - t * zp) for t in STEPS])
        vals = jnp.where(jnp.isfinite(vals), vals, jnp.inf)
        t = ts[jnp.argmin(vals, axis=0)]
        return coef - t[:, None] * p

    return jax.lax.fori_loop(0, newton, step, jnp.zeros((e, d), x.dtype))


@jax.jit
def gather_rows(vec, row_ids):
    ext = jnp.concatenate([vec, jnp.zeros((1,), vec.dtype)])
    return ext[row_ids]


@functools.partial(jax.jit, static_argnames=("n",))
def random_scores(buckets_x, buckets_rid, coefs, n: int):
    """Per-row score of a random-effect group: every row belongs to one
    entity, so each margin lands in its own slot (padding in slot n)."""
    s = jnp.zeros((n + 1,), jnp.float32)
    for x, rid, c in zip(buckets_x, buckets_rid, coefs):
        m = jnp.einsum("erd,ed->er", x, c, precision=HI)
        s = s.at[rid.reshape(-1)].set(m.reshape(-1))
    return s[:n]


@functools.partial(jax.jit, static_argnames=("link",))
def _data_loss(total, off, y, wts, link: str):
    return jnp.sum(wts * _loss(link)[0](total + off, y))


# -- the fit ------------------------------------------------------------------


def scores_of(problem, config: dict, coefs: Dict[str, object]) -> jax.Array:
    """Total per-row score X.c_fixed + sum of the groups' scores, of any
    coefficients in the layout the fit returns."""
    n = problem.n_rows
    fixed = config["fixed"]["name"]
    total = matvec(problem.x, jnp.asarray(coefs[fixed], jnp.float32))
    for g in config.get("random", []):
        total = total + random_scores(
            tuple(b.x for b in problem.buckets),
            tuple(b.row_ids for b in problem.buckets),
            tuple(jnp.asarray(c, jnp.float32) for c in coefs[g["name"]]), n)
    return total


def fit(problem, config: dict, re_newton: int = 10) -> dict:
    """The coordinate-descent fit from zero: objective after every
    coordinate update, final coefficients per coordinate, final scores."""
    link = config["link"]
    n = problem.n_rows
    fixed = config["fixed"]
    groups = {g["name"]: g for g in config.get("random", [])}
    l2 = {fixed["name"]: l2_of(fixed["optimizer"])}
    l2.update({k: l2_of(g["optimizer"]) for k, g in groups.items()})
    zeros = jnp.zeros((n,), jnp.float32)
    score = {name: zeros for name in config["updating_sequence"]}
    coefs: Dict[str, object] = {
        name: (jnp.zeros((problem.x.shape[1],), jnp.float32)
               if name == fixed["name"] else
               [jnp.zeros(b.x.shape[::2], jnp.float32)
                for b in problem.buckets])
        for name in config["updating_sequence"]}
    history: List[float] = []
    for _ in range(int(config["iterations"])):
        for name in config["updating_sequence"]:
            residual = zeros
            for other, s in score.items():
                if other != name:
                    residual = residual + s
            off = problem.offsets + residual
            if name == fixed["name"]:
                coefs[name] = solve_fixed(
                    problem.x, problem.labels, problem.weights, off,
                    l2[name], link)
                score[name] = matvec(problem.x, coefs[name])
            else:
                coefs[name] = [
                    _solve_bucket(b.x, b.labels, b.weights,
                                  gather_rows(off, b.row_ids), l2[name],
                                  link, re_newton)
                    for b in problem.buckets]
                score[name] = random_scores(
                    tuple(b.x for b in problem.buckets),
                    tuple(b.row_ids for b in problem.buckets),
                    tuple(coefs[name]), n)
            total = zeros
            for s in score.values():
                total = total + s
            obj = float(_data_loss(total, problem.offsets, problem.labels,
                                   problem.weights, link))
            for cname, c in coefs.items():
                leaves = [c] if cname == fixed["name"] else c
                obj += 0.5 * l2[cname] * sum(
                    float(jnp.vdot(a, a)) for a in leaves)
            history.append(obj)
    total = zeros
    for s in score.values():
        total = total + s
    return {"history": np.asarray(history, np.float64), "coefs": coefs,
            "scores": total}
