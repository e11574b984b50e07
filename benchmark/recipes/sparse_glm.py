"""Recipe ``sparse_glm``: a wide hashed sparse GLM problem made on the device.

A click log as a hashed categorical matrix: every row holds ONE column for
each of the configuration's fields (the field's value, hashed together with
the field's index into ``n_hash`` slots) and, where the configuration says
``"intercept": "last"``, the column ``n_hash`` with value 1 in its last
slot. What the job kind is handed is what a device holds of such a matrix:

    cols  i32[n, k]   a row's columns side by side, k = fields + intercept
    vals  f32[n, k]   their values (every field's ``1 / sqrt(fields)``: the
                      source scales a row to unit norm)

and labels, offsets and weights ``f32[n]``. Nothing here knows a
configuration by name, and nothing of the program is imported.

**The law** (the configuration's ``fields``: one cardinality ``V_f`` each).
Field ``f`` of a row draws a value of rank ``floor(V_f ** u)``, ``u``
uniform on [0, 1): ``P(rank = r) = log_V((r + 1) / r)``, Zipf with exponent
1, ranks 1 .. V_f - 1. The column is ``mix(rank, f) mod n_hash`` with a
fixed multiplicative hash, so two values (of one field or of two) may meet
in a slot, as in any hashed log, and a row may hold a column twice. Fields
with a handful of values make columns that sit in half of the rows; fields
with millions make a long tail.

**Everything random comes from ``--seed``**, and every random number belongs
to a ROW: row ``i``'s uniforms are ``uniform(fold_in(key, i), (fields,))``,
its label's is ``uniform(fold_in(label_key, i))``, so a rehearsal of the
first rows of a seed is dealt the rows the full run is dealt. What every
seed shares is what the configuration fixes: n, k, d, the fields' laws and
the norm of the truth. ``notes`` carries the column-degree summary (the
largest degree, the share of columns of degree <= 1, the ten hottest
columns), so that two seeds can be seen to have been handed the same work.

**The truth**: ``w*_j = norm * g_j * sqrt(deg_j) / |g * sqrt(deg)|`` on the
hashed columns (``g`` standard normal from the seed, ``deg_j`` the column's
degree in this draw): larger on popular columns, so that a row's true
margin has a standard deviation near 1 at the configured norm, whatever the
hash does; with an intercept the margins are centred on the configuration's
``truth.intercept`` (the intercept's true coefficient is that, less the
hashed part's mean margin: with a margin of unit spread, -1.23 gives the
log's ~26% positives on every seed). Labels are Bernoulli(sigmoid(margin)).

Rows are drawn in chunks inside one jitted loop into preallocated buffers.
"""

from __future__ import annotations

import dataclasses
import functools
import json
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.recipes.dense_glm import seed_key

CHUNK_ROWS = 1 << 18  # rows drawn per loop step


@dataclasses.dataclass
class SparseProblem:
    n_rows: int
    n_features: int
    cols: jax.Array  # i32[n, k]
    vals: jax.Array  # f32[n, k]
    labels: jax.Array  # f32[n]
    offsets: jax.Array
    weights: jax.Array
    col_degree: jax.Array  # i32[d]: stored entries a column
    notes: Dict = dataclasses.field(default_factory=dict)

    def steady_bytes(self) -> int:
        return (self.cols.nbytes + self.vals.nbytes
                + 3 * self.labels.nbytes + self.col_degree.nbytes)


def shape_of(config: dict):
    """``(n, k, d)``: rows, stored slots a row, columns."""
    fixed = config["fixed"]
    with_intercept = fixed.get("intercept", "none") == "last"
    k = len(fixed["fields"]) + int(with_intercept)
    d = int(fixed["n_hash"]) + int(with_intercept)
    if k != int(fixed["nnz_per_row"]) or d != int(fixed["d"]):
        raise ValueError(
            f"the configuration states nnz_per_row {fixed['nnz_per_row']} "
            f"and d {fixed['d']}; its fields and intercept make {k} and {d}")
    return int(config["n_rows"]), k, d


def scale_down(config: dict, rows: int) -> dict:
    """A rehearsal's configuration: fewer rows; d, k and the law kept."""
    config = json.loads(json.dumps(config))
    config["n_rows"] = int(rows)
    return config


def _mix(rank, field):
    """A fixed multiplicative hash of (rank, field) on 32 bits."""
    h = rank * jnp.uint32(0x9E3779B1) + field * jnp.uint32(0x85EBCA6B)
    h = (h ^ (h >> 16)) * jnp.uint32(0x7FEB352D)
    h = (h ^ (h >> 15)) * jnp.uint32(0x846CA68B)
    return h ^ (h >> 16)


def _columns_of(key, ids, log_cards, n_hash: int):
    """``i32[len(ids), fields]``: each row's hashed columns, from the
    row's own key."""
    fields = log_cards.shape[0]
    u = jax.vmap(lambda i: jax.random.uniform(
        jax.random.fold_in(key, i), (fields,), jnp.float32))(ids)
    rank = jnp.floor(jnp.exp(u * log_cards[None, :])).astype(jnp.uint32)
    h = _mix(rank, jnp.arange(fields, dtype=jnp.uint32)[None, :])
    return (h % jnp.uint32(n_hash)).astype(jnp.int32)


@functools.partial(
    jax.jit, static_argnames=("n", "chunk", "n_hash", "with_intercept"))
def _draw_rows(key, log_cards, n: int, chunk: int, n_hash: int,
               with_intercept: bool):
    """``cols`` and ``vals``, chunk by chunk into one buffer each (the last
    chunk drawn whole and shifted back, so every step has one shape; the
    rows it overlaps are drawn again from their own keys, equal)."""
    fields = log_cards.shape[0]
    k = fields + int(with_intercept)
    scale = np.float32(1.0 / np.sqrt(fields))

    def body(i, cols):
        start = jnp.minimum(i * chunk, n - chunk)
        ids = start + jnp.arange(chunk, dtype=jnp.int32)
        cc = _columns_of(key, ids, log_cards, n_hash)
        if with_intercept:
            cc = jnp.concatenate(
                [cc, jnp.full((chunk, 1), n_hash, jnp.int32)], axis=1)
        return jax.lax.dynamic_update_slice(cols, cc, (start, 0))

    cols = jax.lax.fori_loop(0, -(-n // chunk), body,
                             jnp.zeros((n, k), jnp.int32))
    vals = jnp.full((n, k), scale, jnp.float32)
    if with_intercept:
        vals = vals.at[:, -1].set(1.0)
    return cols, vals


@functools.partial(jax.jit, static_argnames=("d",))
def _degrees(cols, d: int):
    """Stored entries a column: one scatter-add over every slot."""
    return jnp.zeros((d,), jnp.int32).at[cols].add(1)


@functools.partial(jax.jit, static_argnames=("n_hash",))
def _truth(key, deg, norm, n_hash: int):
    """The hashed columns' true coefficients; 0 on the intercept's column,
    whose share of the margin ``_labels`` sets."""
    g = jax.random.normal(key, (n_hash,), jnp.float32)
    w = g * jnp.sqrt(deg[:n_hash].astype(jnp.float32))
    w = w * (norm / jnp.linalg.norm(w))
    return jnp.pad(w, (0, deg.shape[0] - n_hash))


@jax.jit
def _labels(key, cols, vals, w_true, intercept):
    margin = jnp.sum(vals * w_true[cols], axis=1)
    n = margin.shape[0]
    if intercept is not None:  # the base rate is the configuration's
        margin = margin - jnp.mean(margin) + intercept
    u = jax.vmap(lambda i: jax.random.uniform(
        jax.random.fold_in(key, i), (), jnp.float32))(
            jnp.arange(n, dtype=jnp.int32))
    return (u < jax.nn.sigmoid(margin)).astype(jnp.float32), jnp.std(margin)


def degree_summary(deg) -> dict:
    """What the issue asks to be told of the columns' degrees: a few
    numbers and the ten hottest columns, fetched as scalars."""
    d = deg.shape[0]
    top_deg, top_col = jax.lax.top_k(deg, min(10, d))
    return {
        "max_col_degree": int(jnp.max(deg)),
        "columns_degree_le_1": int(jnp.sum(deg <= 1)),
        "share_degree_le_1": float(jnp.mean(deg <= 1)),
        "columns_degree_0": int(jnp.sum(deg == 0)),
        "hottest": [[int(c), int(g)] for c, g in
                    zip(np.asarray(top_col), np.asarray(top_deg))],
    }


def make(config: dict, seed: int) -> SparseProblem:
    """The configuration's problem as ``seed`` draws it, on the default
    device."""
    n, k, d = shape_of(config)
    fixed = config["fixed"]
    n_hash = int(fixed["n_hash"])
    with_intercept = d > n_hash
    log_cards = jnp.log(jnp.asarray(fixed["fields"], jnp.float32))
    cols, vals = _draw_rows(seed_key(seed, 1), log_cards, n, min(CHUNK_ROWS, n),
                            n_hash, with_intercept)
    deg = _degrees(cols, d)
    truth = config["truth"]
    w_true = _truth(seed_key(seed, 4), deg, float(truth["norm"]), n_hash)
    labels, margin_sd = _labels(
        seed_key(seed, 5), cols, vals, w_true,
        float(truth["intercept"]) if with_intercept else None)
    notes = degree_summary(deg)
    notes.update(n_rows=n, nnz_per_row=k, n_features=d,
                 true_margin_sd=float(margin_sd),
                 positives_share=float(jnp.mean(labels)))
    prob = SparseProblem(
        n_rows=n, n_features=d, cols=cols, vals=vals, labels=labels,
        offsets=jnp.zeros((n,), jnp.float32),
        weights=jnp.ones((n,), jnp.float32), col_degree=deg, notes=notes)
    jax.block_until_ready((cols, vals, labels))
    return prob
