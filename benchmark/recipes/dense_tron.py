"""Recipe ``dense_tron``: one dense data set of unit rows over columns of
power-law scale, made on the device and dealt out by the seed.

The shape of LIBSVM's ``epsilon`` (PASCAL Large Scale Learning Challenge
2008: dense rows, every feature standardized, then every row scaled to unit
length), with a stated law in place of the data, which is not here:

    z_r ~ N(0, I_d),  x_r = (z_r * s) / |z_r * s|,  s_j = j ** -exponent

(base columns j = 1..d; ``fixed.spectrum_exponent``). Standardized columns
of independent draws would make ``X^T X`` nearly a multiple of the identity
(d / n = 0.005: a condition number near 1.3, by Marchenko-Pastur), and the
trust-region solver's conjugate gradient would stop after two steps; the
published data needs many. The power law gives ``X^T D X`` the spread of
scales a real feature set has, and the row normalization is the source's.
The truth ``w0`` has signs of its own and the size ``c / s_j`` on base
column j, c set so that a row's true margin ``x . w0`` spreads by
``fixed.truth_margin_sd`` whatever the column law: every column carries
the same share of the margin, so the gradient at zero spreads over the whole
spectrum (a truth of one size a column puts it on the few widest columns,
and the first CG then stops after about ten steps). No intercept
(LIBLINEAR's default, as the source is used); labels Bernoulli(sigmoid(x .
w0)): balanced classes, as the source's.

**One data set, dealt out by the seed.** As the source is ONE published
file, the base rows, truth and labels come from the configuration's own
``fixed.data_seed``; ``--seed`` deals them out: the order of the rows, the
order of the columns and each column's sign (the truth's coordinates follow
their columns, so every row keeps its margin and its label). Every seed
thus hands the program the same problem in another layout, and the
trust-region path (how many CG steps each outer step takes, which steps are
accepted) is the same one whatever the seed: only the order of a float32
sum moves, which a CG stop decided by more than that rounding does not see
(``PERF.md`` gives the stops' distances from their thresholds). A row is
made from its base index's own key, so dealing costs no copy of X.

X is drawn in row chunks into one preallocated buffer inside one jitted
loop (``dense_glm``'s way), so the generator's peak is X and a chunk. It
imports nothing of the program; it returns ``dense_glm.Problem``, whose
plain arrays the job kind wraps in the program's containers and the
reference reads as they are.
"""

from __future__ import annotations

import functools
import json

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.recipes.dense_glm import Problem, seed_key

CHUNK_ROWS = 1 << 14  # rows drawn a loop step: 131 MB of bits at d=2,000


def column_scales(d: int, exponent: float) -> np.ndarray:
    """``s_j = j ** -exponent``, j = 1..d: the base columns' scales before
    the rows are normalized."""
    return np.arange(1, d + 1, dtype=np.float64) ** -float(exponent)


def base_truth(config: dict) -> np.ndarray:
    """``w0``: the data set's own signs, ``c / s_j`` on base column j with
    ``c = truth_margin_sd * sqrt(sum(s^2) / d)``: a unit row's margin is
    then ``c * sum_j(+-z_j) / |z * s|``, of spread ``truth_margin_sd``."""
    fixed = config["fixed"]
    d = int(fixed["d"])
    s = column_scales(d, fixed["spectrum_exponent"])
    signs = np.random.default_rng([int(fixed["data_seed"]), 4]).choice(
        np.asarray([-1.0, 1.0]), d)
    return signs * (float(fixed["truth_margin_sd"])
                    * np.sqrt((s * s).sum() / d)) / s


def deal(config: dict, seed: int):
    """What ``seed`` deals: ``row_of[i]``, the base row at row i;
    ``col_of[j]``, the base column at column j; and column j's sign."""
    n, d = int(config["n_rows"]), int(config["fixed"]["d"])
    rng = np.random.default_rng([int(seed), 1])
    return (rng.permutation(n).astype(np.int32),
            rng.permutation(d).astype(np.int32),
            rng.choice(np.asarray([-1.0, 1.0], np.float32), d))


@functools.partial(jax.jit, static_argnames=("chunk",))
def _draw(key, row_of, col_of, col_signs, scales, w0, chunk: int):
    """X, dealt, and its labels, chunk by chunk into one buffer (the last
    chunk drawn whole and shifted back: the rows it overlaps are
    overwritten with equal values)."""
    n, d = row_of.shape[0], w0.shape[0]
    row_key, label_key = jax.random.split(key)

    def body(i, carry):
        x, labels = carry
        start = jnp.minimum(i * chunk, n - chunk)
        rows = jax.lax.dynamic_slice(row_of, (start,), (chunk,))
        z = jax.vmap(lambda r: jax.random.normal(
            jax.random.fold_in(row_key, r), (d,), jnp.float32))(rows)
        zs = z * scales[None, :]
        base = zs / jnp.sqrt(jnp.sum(zs * zs, axis=1, keepdims=True))
        margin = jnp.matmul(base, w0, precision="highest")
        u = jax.vmap(lambda r: jax.random.uniform(
            jax.random.fold_in(label_key, r), (), jnp.float32))(rows)
        yc = (u < jax.nn.sigmoid(margin)).astype(jnp.float32)
        xc = base[:, col_of] * col_signs[None, :]
        x = jax.lax.dynamic_update_slice(x, xc, (start, 0))
        labels = jax.lax.dynamic_update_slice(labels, yc, (start,))
        return x, labels

    x0 = jnp.zeros((n, d), jnp.float32)
    y0 = jnp.zeros((n,), jnp.float32)
    return jax.lax.fori_loop(0, -(-n // chunk), body, (x0, y0))


def scale_down(config: dict, rows: int) -> dict:
    """A rehearsal's configuration: the same width, law and truth on
    ``rows`` rows, the L2 weight scaled with the rows so that
    ``X^T D X + l2 I`` keeps the shape of its spectrum (at the
    configuration's weight a small problem is solved to float32's
    resolution within the budget, and its steps are then rejected by
    rounding)."""
    config = json.loads(json.dumps(config))
    ratio = rows / config["n_rows"]
    parts = config["fixed"]["optimizer"].split(",")
    parts[2] = repr(float(parts[2]) * ratio)
    config["fixed"]["optimizer"] = ",".join(parts)
    config["n_rows"] = int(rows)
    return config


def make(config: dict, seed: int) -> Problem:
    """The configuration's data set as ``seed`` deals it, on the default
    device."""
    if config.get("random"):
        raise ValueError("dense_tron makes a fixed effect alone")
    if config["link"] != "logistic":
        raise ValueError("dense_tron draws logistic labels")
    n = int(config["n_rows"])
    fixed = config["fixed"]
    row_of, col_of, col_signs = deal(config, seed)
    x, labels = _draw(
        seed_key(int(fixed["data_seed"]), 1), jnp.asarray(row_of),
        jnp.asarray(col_of), jnp.asarray(col_signs),
        jnp.asarray(column_scales(int(fixed["d"]),
                                  fixed["spectrum_exponent"]), jnp.float32),
        jnp.asarray(base_truth(config), jnp.float32), min(CHUNK_ROWS, n))
    prob = Problem(n_rows=n, x=x, labels=labels,
                   offsets=jnp.zeros((n,), jnp.float32),
                   weights=jnp.ones((n,), jnp.float32))
    jax.block_until_ready((x, labels))
    return prob
