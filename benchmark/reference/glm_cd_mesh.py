"""``glm_cd``'s reference for a problem whose arrays lie over several chips.

The same fit, the same departure (each coordinate update solved to its
minimiser by safeguarded Newton), the same float32 at
``precision="highest"``. What differs is how the fixed effect's passes
read X. ``glm_cd`` cuts row blocks out of ``x[n, d]`` with a dynamic
slice; over a mesh that slices the sharded axis, and the partitioner
answers by gathering the whole of X onto every device (looked at in the
compiled text, PR 31: ``all-gather f32[n, d]``). Here X is read as
``x[K, m, d]``, one leading index a device (a reshape that moves nothing:
device ``k`` holds rows ``[k*m, (k+1)*m)``), and blocks are cut along the
second axis, which no device shares: every device walks its own rows
``BLOCK_ROWS`` at a time, and the sums over rows end in one all-reduce the
compiler puts there. The random effect's per-entity solves and the score
gather and scatter, and the fit's loop itself, are ``glm_cd``'s own
functions: they are elementwise over entities and partition as they stand
(``fit`` and ``scores_of`` here are ``glm_cd``'s with the two passes over X
swapped for this file's).

Plain ``jax.numpy`` under ``jit``; no ``shard_map``, no kernel, nothing of
the program. On one device (K = 1) it is ``glm_cd`` with one more axis.
"""

from __future__ import annotations

import contextlib
import functools
from typing import Dict
from unittest import mock

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference import glm_cd
from benchmark.reference.glm_cd import HI, STEPS

BLOCK_ROWS = 1 << 17  # rows of X each device holds a second time


def _devices_of(x) -> int:
    """How many pieces axis 0 of ``x`` lies in."""
    return x.shape[0] // x.sharding.shard_shape(x.shape)[0]


def _per_device(x, k: int):
    return x.reshape((k, x.shape[0] // k) + x.shape[1:])


def _blocks(x3, vectors, body, init):
    """``body(acc, xb, vecs_b, mask_b, start)`` over blocks of rows of
    ``x3[K, m, d]`` and of each ``[K, m]`` vector, cut along axis 1; one
    shape a block, the last shifted back and masked (``glm_cd._row_blocks``)."""
    k, m, d = x3.shape
    b = min(m, BLOCK_ROWS)

    def step(i, acc):
        start = jnp.minimum(i * b, m - b)
        xb = jax.lax.dynamic_slice(x3, (0, start, 0), (k, b, d))
        vecs = [jax.lax.dynamic_slice(v, (0, start), (k, b))
                for v in vectors]
        mask = (start + jnp.arange(b) >= i * b).astype(x3.dtype)
        return body(acc, xb, vecs, mask, start)

    return jax.lax.fori_loop(0, -(-m // b), step, init)


@functools.partial(jax.jit, static_argnames=("k",))
def matvec(x, v, k: int):
    """X v, every device its own rows, blocks at a time."""
    def body(out, xb, _, mask, start):
        return jax.lax.dynamic_update_slice(
            out, jnp.einsum("kbd,d->kb", xb, v, precision=HI), (0, start))

    x3 = _per_device(x, k)
    return _blocks(x3, [], body,
                   jnp.zeros(x3.shape[:2], x.dtype)).reshape(-1)


@functools.partial(jax.jit, static_argnames=("link", "k"))
def _fe_newton_system(x, y, wts, off, coef, l2, link: str, k: int):
    _, d1, d2 = glm_cd._loss(link)
    x3 = _per_device(x, k)
    d = x3.shape[2]

    def body(acc, xb, vecs, mask, start):
        z, g, h = acc
        yb, wb, ob = vecs
        zb = jnp.einsum("kbd,d->kb", xb, coef, precision=HI) + ob
        r = mask * wb * d1(zb, yb)
        c = mask * wb * d2(zb, yb)
        g = g + jnp.einsum("kb,kbd->d", r, xb, precision=HI)
        h = h + jnp.einsum("kbd,kb,kbe->de", xb, c, xb, precision=HI)
        return jax.lax.dynamic_update_slice(z, zb, (0, start)), g, h

    z, g, h = _blocks(
        x3, [_per_device(v, k) for v in (y, wts, off)], body,
        (jnp.zeros(x3.shape[:2], x.dtype), jnp.zeros((d,), x.dtype),
         jnp.zeros((d, d), x.dtype)))
    g = g + l2 * coef
    h = h + l2 * jnp.eye(d, dtype=x.dtype)
    return z.reshape(-1), g, jnp.linalg.solve(h, g)


def solve_fixed(x, y, wts, off, l2: float, link: str,
                max_newton: int = 20) -> jax.Array:
    """argmin_c sum w l(Xc + off, y) + l2/2 ||c||^2, from zero
    (``glm_cd.solve_fixed`` over this file's passes)."""
    k = _devices_of(x)
    coef = jnp.zeros((x.shape[1],), x.dtype)
    for _ in range(max_newton):
        z, _, p = _fe_newton_system(x, y, wts, off, coef, l2, link, k)
        zp = matvec(x, p, k)
        vals = np.asarray(glm_cd._fe_line_values(z, zp, y, wts, coef, p, l2,
                                                 link))
        vals = np.where(np.isfinite(vals), vals, np.inf)
        best = int(np.argmin(vals))
        if STEPS[best] == 0.0:  # the floor of float32: no step lowers it
            break
        coef = coef - STEPS[best] * p
    return coef


@contextlib.contextmanager
def _own_passes(x):
    """``glm_cd``'s fit and scoring with the fixed effect's two passes over
    X taken from this file: everything else of the reference is shared,
    letter for letter."""
    k = _devices_of(x)
    with mock.patch.object(glm_cd, "solve_fixed", solve_fixed), \
            mock.patch.object(glm_cd, "matvec",
                              lambda x, v: matvec(x, v, k)):
        yield


def scores_of(problem, config: dict, coefs: Dict[str, object]) -> jax.Array:
    """Total per-row score of any coefficients in the fit's layout."""
    with _own_passes(problem.x):
        return glm_cd.scores_of(problem, config, coefs)


def fit(problem, config: dict, re_newton: int = 10) -> dict:
    """``glm_cd.fit``: objective after every coordinate update, final
    coefficients per coordinate, final scores."""
    with _own_passes(problem.x):
        return glm_cd.fit(problem, config, re_newton)
