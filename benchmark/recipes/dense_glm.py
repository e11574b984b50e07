"""Recipe ``dense_glm``: a dense GLM / GLMix problem made on the device.

One general generator for every configuration whose ``recipe`` is
``dense_glm``: a dense fixed-effect matrix, optionally one random-effect
group (an id per row, dense per-entity features), labels drawn from a
known truth. The sizes, scales, the link and the law of the entities'
activity come from the configuration file; nothing here knows a
configuration by name.

Everything random comes from ``--seed``: the features, the truth, the
labels, which rows belong to which entity and how active each entity is.
What every seed shares is what the configuration fixes, so that every run
of a cell is handed the same amount of work (``PERF.md``): the shapes; the
SET of rows-per-entity counts (``activity_counts``: the quantiles of the
configured law, so the size classes, their padding and the compiled
programs are the same for every seed, while the seed decides which entity
gets which count and which rows); and the norm of the true coefficients
(the direction is the seed's), which fixes how sharp the problem is and
so, nearly, how many iterations a solver needs.

The recipe is the headline one of ``bench.py:96-131`` (logistic GLMix)
and ``bench.py:326-341`` (Poisson), moved onto the device:

- X ``f32[n, d]`` is drawn in row chunks into ONE preallocated buffer
  inside one jitted loop (a whole-array draw would hold the random bits
  beside the result, and the generator, not the cell, would be the
  process's peak). The true fixed margin X.w is taken chunk by chunk in
  the same loop, so X is never read again by the generator.
- The random-effect blocks ``x[E, n_pad, d_pad]`` are gathered from the
  per-row entity features by ``row_ids`` BEFORE X exists, and the per-row
  features are freed, so the generator's peak stays under the cell's
  steady bytes. The bucket layout (power-of-two rows, min 4; power-of-two
  columns, min 8; padding rows ``row_id == n``, weight 0; padding columns
  zero with ``feat_idx == -1``) is the program's own contract
  (``photon_ml_tpu/data/random_effect.py:86-91``), built with vectorised
  numpy: no per-entity Python loop.

It imports nothing of the program. What it returns is plain arrays; the
job kind wraps them in the program's containers and the plain reference
reads them as they are.
"""

from __future__ import annotations

import dataclasses
import functools
import json
import statistics
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

CHUNK_ROWS = 1 << 18  # rows drawn per loop step: 200 MB of bits at d=200
ENTITY_CHUNK = 1 << 14  # entities gathered per loop step


@dataclasses.dataclass
class Bucket:
    """One size class of entities, in the program's padding contract."""

    codes: np.ndarray  # i32[E] entity code per slot, ascending
    row_ids: jax.Array  # i32[E, n_pad], == n_rows for padding
    feat_idx: jax.Array  # i32[E, d_pad], == -1 for padding
    x: jax.Array  # f32[E, n_pad, d_pad]
    labels: Optional[jax.Array] = None  # f32[E, n_pad]
    offsets: Optional[jax.Array] = None
    weights: Optional[jax.Array] = None


@dataclasses.dataclass
class Problem:
    n_rows: int
    x: jax.Array  # f32[n, d]
    labels: jax.Array  # f32[n]
    offsets: jax.Array
    weights: jax.Array
    entity_of_row: Optional[np.ndarray] = None  # i32[n] (host)
    n_entities: int = 0
    d_entity: int = 0
    buckets: List[Bucket] = dataclasses.field(default_factory=list)

    def steady_bytes(self) -> int:
        total = self.x.nbytes + 3 * self.labels.nbytes
        for b in self.buckets:
            total += (b.x.nbytes + b.row_ids.nbytes + b.feat_idx.nbytes
                      + 3 * b.labels.nbytes)
        return total


def next_size(v: np.ndarray, minimum: int) -> np.ndarray:
    """Smallest power of two >= max(v, minimum), elementwise."""
    v = np.maximum(np.asarray(v, np.int64), minimum)
    return (1 << np.ceil(np.log2(v)).astype(np.int64)).astype(np.int64)


def bucket_layout(entity_of_row: np.ndarray, n_entities: int, n_rows: int,
                  min_rows_pad: int = 4) -> List[Dict[str, np.ndarray]]:
    """``codes[E]`` and ``row_ids[E, n_pad]`` per size class, vectorised.

    Entities in ascending code inside a bucket, rows in ascending row
    index inside an entity, buckets in ascending ``n_pad``: the order
    ``build_random_effect_dataset`` gives. An entity with no row gets no
    slot."""
    counts = np.bincount(entity_of_row, minlength=n_entities)
    order = np.argsort(entity_of_row, kind="stable").astype(np.int32)
    ent_sorted = entity_of_row[order]
    starts = np.cumsum(counts) - counts
    pos = np.arange(n_rows, dtype=np.int64) - starts[ent_sorted]
    n_pad_of = next_size(counts, min_rows_pad)
    n_pad_of[counts == 0] = 0
    out = []
    for n_pad in np.unique(n_pad_of[counts > 0]):
        codes = np.flatnonzero(n_pad_of == n_pad).astype(np.int32)
        slot_of = np.full(n_entities, -1, np.int64)
        slot_of[codes] = np.arange(len(codes))
        mask = n_pad_of[ent_sorted] == n_pad
        row_ids = np.full((len(codes), int(n_pad)), n_rows, np.int32)
        row_ids[slot_of[ent_sorted[mask]], pos[mask]] = order[mask]
        out.append({"codes": codes, "row_ids": row_ids})
    return out


def _with_intercept(x, intercept: str):
    if intercept == "last":
        return x.at[:, -1].set(1.0)
    if intercept == "first":
        return x.at[:, 0].set(1.0)
    return x


@functools.partial(jax.jit, static_argnames=("n", "chunk", "intercept"))
def _draw_x(key, w_true, x_sd, n: int, chunk: int, intercept: str):
    """X and its true margin, chunk by chunk into one buffer."""
    d = w_true.shape[0]
    n_chunks = -(-n // chunk)

    def body(i, carry):
        x, margin = carry
        # The last chunk is drawn whole and lands shifted back, so every
        # step has one shape; the rows it overlaps are overwritten, in X
        # and in the margin alike.
        start = jnp.minimum(i * chunk, n - chunk)
        xc = _with_intercept(x_sd * jax.random.normal(
            jax.random.fold_in(key, i), (chunk, d), jnp.float32), intercept)
        mc = jnp.matmul(xc, w_true, precision="highest")
        x = jax.lax.dynamic_update_slice(x, xc, (start, 0))
        margin = jax.lax.dynamic_update_slice(margin, mc, (start,))
        return x, margin

    x0 = jnp.zeros((n, d), jnp.float32)
    m0 = jnp.zeros((n,), jnp.float32)
    return jax.lax.fori_loop(0, n_chunks, body, (x0, m0))


@functools.partial(jax.jit, static_argnames=("d_pad", "chunk"))
def _gather_x_blocks(xu, row_ids, d_pad: int, chunk: int):
    """``x[E, n_pad, d_pad]`` from per-row features, entity chunks at a
    time; the sentinel row id gathers a zero row."""
    e, n_pad = row_ids.shape
    d = xu.shape[1]
    n_chunks = -(-e // chunk)
    xu_ext = jnp.concatenate([xu, jnp.zeros((1, d), xu.dtype)])

    def body(i, x):
        start = jnp.minimum(i * chunk, e - chunk)
        rid = jax.lax.dynamic_slice(row_ids, (start, 0), (chunk, n_pad))
        xc = jnp.pad(xu_ext[rid], ((0, 0), (0, 0), (0, d_pad - d)))
        return jax.lax.dynamic_update_slice(x, xc, (start, 0, 0))

    return jax.lax.fori_loop(
        0, n_chunks, body, jnp.zeros((e, n_pad, d_pad), xu.dtype))


@jax.jit
def _gather_rows(vec, row_ids):
    """``vec[row_ids]`` with 0 in the padding slots (``row_id == n``)."""
    ext = jnp.concatenate([vec, jnp.zeros((1,), vec.dtype)])
    return ext[row_ids]


@functools.partial(jax.jit, static_argnames=("link",))
def _draw_labels(key, margin, link: str):
    if link == "logistic":
        u = jax.random.uniform(key, margin.shape, jnp.float32)
        return (u < jax.nn.sigmoid(margin)).astype(jnp.float32)
    if link == "poisson":
        lam = jnp.exp(jnp.clip(margin, -4.0, 4.0))
        return jax.random.poisson(key, lam).astype(jnp.float32)
    raise ValueError(f"unknown link {link!r}")


@functools.partial(
    jax.jit, static_argnames=("n", "n_entities", "d", "intercept"))
def _entity_features(key, wu_key, entity_of_row, w_sd, x_sd, n: int,
                     n_entities: int, d: int, intercept: str):
    """Per-row entity features and their true margin xu . wu[entity]."""
    xu = _with_intercept(
        x_sd * jax.random.normal(key, (n, d), jnp.float32), intercept)
    wu = w_sd * jax.random.normal(wu_key, (n_entities, d), jnp.float32)
    margin = jnp.einsum("nd,nd->n", xu, wu[entity_of_row],
                        precision="highest")
    return xu, margin


def seed_key(seed: int, stream: int) -> jax.Array:
    """A key of ``--seed``, which may pass 32 signed bits."""
    seed = int(seed)
    key = jax.random.fold_in(
        jax.random.PRNGKey(seed & 0x7FFFFFFF), seed >> 31)
    return jax.random.fold_in(key, stream)


def activity_counts(group: dict) -> np.ndarray:
    """Rows per entity, ascending: the quantiles of the configured law.

    ``lognormal``: ``min`` plus a log-normal excess of median
    ``median - min`` and shape ``sigma``, cut at ``max``; entity i of E
    takes the quantile (i + 1/2) / E. The set is the configuration's; the
    seed only deals it out."""
    e = int(group["n_entities"])
    law = group["activity"]
    if law["law"] != "lognormal":
        raise ValueError(f"unknown activity law {law['law']!r}")
    nd = statistics.NormalDist()
    z = np.asarray([nd.inv_cdf((i + 0.5) / e) for i in range(e)])
    excess = (law["median"] - law["min"]) * np.exp(law["sigma"] * z)
    return np.minimum(law["max"], law["min"] + np.floor(excess)).astype(
        np.int64)


def n_rows_of(config: dict) -> int:
    """The rows a configuration holds: the sum of its entities' activity
    where it has a random-effect group, else its ``n_rows``."""
    groups = config.get("random", [])
    if groups:
        return int(activity_counts(groups[0]).sum())
    return int(config["n_rows"])


def scale_down(config: dict, rows: int) -> dict:
    """A rehearsal's configuration: the same widths and the same law of
    activity on about ``rows`` rows, the entities cut in that ratio and
    the longest entity to an eighth of the rows."""
    config = json.loads(json.dumps(config))
    ratio = rows / config["n_rows"]
    for g in config.get("random", []):
        g["n_entities"] = max(2, int(round(g["n_entities"] * ratio)))
        g["activity"]["max"] = max(
            g["activity"]["min"], min(g["activity"]["max"], rows // 8))
    config["n_rows"] = int(rows)
    config["n_rows"] = n_rows_of(config)
    return config


def entity_of_row(config: dict, seed: int) -> np.ndarray:
    """``i32[n]``: the entity of every row. The seed deals the counts out
    to the entity codes and the rows to the entities."""
    group = config["random"][0]
    rng = np.random.default_rng([int(seed), 1])
    counts = rng.permutation(activity_counts(group))
    codes = np.repeat(np.arange(len(counts), dtype=np.int32), counts)
    return rng.permutation(codes)


def entity_features(config: dict, seed: int, codes: np.ndarray):
    """Per-row features ``f32[n, d]`` of the random-effect group and their
    true margin."""
    group = config["random"][0]
    return _entity_features(
        seed_key(seed, 2), seed_key(seed, 3), jnp.asarray(codes),
        float(group["w_sd"]), float(group["x_sd"]), len(codes),
        int(group["n_entities"]), int(group["d"]),
        group.get("intercept", "none"))


def true_fixed(config: dict, seed: int) -> jax.Array:
    """The fixed effect's true coefficients: the seed's direction at the
    configuration's norm, ``w_sd * sqrt(d)``."""
    fixed = config["fixed"]
    d = int(fixed["d"])
    w = jax.random.normal(seed_key(seed, 4), (d,), jnp.float32)
    return w * (float(fixed["w_sd"]) * np.sqrt(d) / jnp.linalg.norm(w))


def make(config: dict, seed: int) -> Problem:
    """The configuration's problem as ``seed`` draws it, on the default
    device."""
    n = int(config["n_rows"])
    if n != n_rows_of(config):
        raise ValueError(f"the configuration states n_rows {n}; its "
                         f"entities' activity sums to {n_rows_of(config)}")
    fixed = config["fixed"]
    chunk = min(CHUNK_ROWS, n)

    margin_re = None
    buckets: List[Bucket] = []
    codes = None
    n_entities = d_entity = 0
    groups = config.get("random", [])
    if len(groups) > 1:
        raise ValueError("dense_glm makes at most one random-effect group")
    if groups:
        g = groups[0]
        n_entities, d_entity = int(g["n_entities"]), int(g["d"])
        codes = entity_of_row(config, seed)
        xu, margin_re = entity_features(config, seed, codes)
        d_pad = int(next_size(np.asarray([d_entity]), 8)[0])
        feat_row = np.full(d_pad, -1, np.int32)
        feat_row[:d_entity] = np.arange(d_entity)
        for lay in bucket_layout(codes, n_entities, n):
            row_ids = jnp.asarray(lay["row_ids"])
            e = row_ids.shape[0]
            buckets.append(Bucket(
                codes=lay["codes"], row_ids=row_ids,
                feat_idx=jnp.asarray(np.tile(feat_row, (e, 1))),
                x=_gather_x_blocks(xu, row_ids, d_pad,
                                   min(ENTITY_CHUNK, e))))
        jax.block_until_ready([b.x for b in buckets])
        del xu  # the blocks hold what the cell needs of it

    x, margin = _draw_x(seed_key(seed, 1), true_fixed(config, seed),
                        float(fixed["x_sd"]), n, chunk,
                        fixed.get("intercept", "none"))
    if margin_re is not None:
        margin = margin + margin_re
    labels = _draw_labels(seed_key(seed, 5), margin, config["link"])
    offsets = jnp.zeros((n,), jnp.float32)
    weights = jnp.ones((n,), jnp.float32)
    for b in buckets:
        b.labels = _gather_rows(labels, b.row_ids)
        b.offsets = _gather_rows(offsets, b.row_ids)
        b.weights = _gather_rows(weights, b.row_ids)
    prob = Problem(n_rows=n, x=x, labels=labels, offsets=offsets,
                   weights=weights, entity_of_row=codes,
                   n_entities=n_entities, d_entity=d_entity, buckets=buckets)
    jax.block_until_ready((x, labels, [b.labels for b in buckets]))
    return prob
