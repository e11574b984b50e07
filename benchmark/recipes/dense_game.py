"""Recipe ``dense_game``: a dense GAME problem with several random-effect
groups, made on the device.

``dense_glm``'s problem (a dense fixed-effect matrix, labels drawn from a
known truth) with a LIST of groups where that recipe has at most one: each
group has its own id per row, its own per-row feature shard, its own bucket
layout and its own gathered blocks. The groups are the configuration's
``random`` entries (each also a full-rank random-effect coordinate) and then
its ``groups`` entries (data only: what a factored coordinate reads), in
that order (``groups_of``). They are dealt to the rows independently of
each other (which user rated which movie is not in the source's counts; a
user may meet a movie twice, which a GLM does not mind). The recipe makes
blocks a group, not a coordinate.

Two laws of activity:

- ``lognormal`` (``dense_glm.activity_counts``): the first group's, whose
  quantiles also fix ``n_rows``.
- ``stretched_rank``: a popularity law by rank. The item at rank fraction u
  (0 the most rated, 1 the least) of ``n_published`` has
  ``floor(max ** (1 - u ** beta))`` rows: ``max`` for exactly one item, 1
  for the last, and ``beta`` set so that the counts sum to ``total`` (what
  is left over goes one each to the items behind the first). The
  configuration runs a ``share`` of the source: every count is thinned to
  ``floor(share * c + 1/2)``, items of no row are dropped, and what the
  rounding leaves over against ``n_rows`` goes one each to the largest.

Every seed deals the same SET of counts in every group (the same size
classes, the same compiled programs); the seed decides which entity has
which count and which rows are whose.

The truth of a group that a factored coordinate reads (``truth.rank``) is
low rank plus noise: entity e's coefficients are ``gamma_e B + eps_e`` with
``gamma ~ N(0, gamma_sd)[rank]``, ``B ~ N(0, b_sd)[rank, d]`` and
``eps ~ N(0, w_sd)[d]``; a group without ``truth`` has ``dense_glm``'s
``N(0, w_sd)``.

The generator's peak stays under the cell's steady bytes as in
``dense_glm``: a group's per-row features are gathered into its blocks and
freed before the next group's are drawn, and X is drawn last, in chunks.
Imports ``dense_glm``'s functions and edits nothing there; imports nothing
of the program.
"""

from __future__ import annotations

import dataclasses
import functools
import json
from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.recipes import dense_glm
from benchmark.recipes.dense_glm import Bucket


@dataclasses.dataclass
class Group:
    """One random-effect group: who each row belongs to, and the group's
    size classes in the program's padding contract."""

    name: str
    entity_of_row: np.ndarray  # i32[n] (host)
    n_entities: int
    d_entity: int
    buckets: List[Bucket]


@dataclasses.dataclass
class Problem:
    n_rows: int
    x: jax.Array  # f32[n, d]
    labels: jax.Array  # f32[n]
    offsets: jax.Array
    weights: jax.Array
    groups: Dict[str, Group]  # by the name of the group's entry

    def steady_bytes(self) -> int:
        total = self.x.nbytes + 3 * self.labels.nbytes
        for g in self.groups.values():
            for b in g.buckets:
                total += (b.x.nbytes + b.row_ids.nbytes + b.feat_idx.nbytes
                          + 3 * b.labels.nbytes)
        return total


# -- the laws of activity -------------------------------------------------------


def _spread(counts: np.ndarray, diff: int, first: int = 0) -> np.ndarray:
    """``counts`` (descending) with ``diff`` rows more (fewer if negative),
    one each to the largest from position ``first`` on, round and round."""
    counts = counts.copy()
    step = 1 if diff > 0 else -1
    span = len(counts) - first
    for lap in range(-(-abs(diff) // span)):
        take = min(span, abs(diff) - lap * span)
        counts[first:first + take] += step
    return counts


def published_counts(law: dict) -> np.ndarray:
    """The ``stretched_rank`` law's counts at the source's own scale,
    descending: ``n_published`` counts that sum to ``total``, one of them
    ``max``, the last 1."""
    e = int(law["n_published"])
    u = np.arange(e, dtype=np.float64) / (e - 1)
    counts = np.floor(
        float(law["max"]) ** (1.0 - u ** float(law["beta"])) + 1e-9
    ).astype(np.int64)
    # what beta's digits leave over: one each to the items behind the first
    return _spread(counts, int(law["total"]) - int(counts.sum()), first=1)


def thinned_counts(law: dict, n_rows: int) -> np.ndarray:
    """Rows per entity of a ``stretched_rank`` group as the configuration
    runs it, descending: the published counts thinned to ``share``, the
    empty items dropped, the sum brought to ``n_rows``."""
    counts = np.floor(float(law["share"]) * published_counts(law)
                      + 0.5).astype(np.int64)
    counts = counts[counts > 0]
    counts = _spread(counts, int(n_rows) - int(counts.sum()))
    if counts.min() < 1:
        raise ValueError("the remainder emptied an entity: the share is "
                         "too far from n_rows / total")
    return counts


def groups_of(config: dict) -> List[dict]:
    """Every group of the configuration: the ``random`` entries, then the
    data-only ``groups`` entries."""
    return list(config.get("random", [])) + list(config.get("groups", []))


def group_counts(group: dict, n_rows: int) -> np.ndarray:
    """Rows per entity of any group, in the law's own order."""
    law = group["activity"]["law"]
    if law == "stretched_rank":
        return thinned_counts(group["activity"], n_rows)
    return dense_glm.activity_counts(group)


def scale_down(config: dict, rows: int) -> dict:
    """A rehearsal's configuration: ``dense_glm.scale_down`` for the first
    group (the same law on about ``rows`` rows), and every ``stretched_rank``
    group thinned further in the same ratio: the thinning rule itself."""
    full_rows = config["n_rows"]
    if len(config["random"]) != 1:
        raise ValueError("scale_down: one random entry, whose law fixes "
                         "n_rows, and any number of data-only groups")
    others = json.loads(json.dumps(config.get("groups", [])))
    config = dense_glm.scale_down(config, rows)
    for g in others:
        g["activity"]["share"] *= config["n_rows"] / full_rows
        g["n_entities"] = len(group_counts(g, config["n_rows"]))
    config["groups"] = others
    return config


def entity_of_row(config: dict, seed: int, index: int) -> np.ndarray:
    """``i32[n]``: the entity of every row in group ``index``. The first
    group is dealt as ``dense_glm`` deals it; every other from a stream of
    its own, so the groups are independent."""
    if index == 0:
        return dense_glm.entity_of_row(config, seed)
    group = groups_of(config)[index]
    rng = np.random.default_rng([int(seed), 10 + index])
    counts = rng.permutation(group_counts(group, config["n_rows"]))
    codes = np.repeat(np.arange(len(counts), dtype=np.int32), counts)
    return rng.permutation(codes)


# -- features and truth -----------------------------------------------------------


@functools.partial(
    jax.jit, static_argnames=("n", "n_entities", "d", "rank", "intercept"))
def _low_rank_features(key, entity_of_row, x_sd, w_sd, gamma_sd, b_sd,
                       n: int, n_entities: int, d: int, rank: int,
                       intercept: str):
    """Per-row features of a group whose truth is low rank plus noise, and
    their true margin x . (gamma_e B + eps_e)."""
    kx, kg, kb, ke = jax.random.split(key, 4)
    x = dense_glm._with_intercept(
        x_sd * jax.random.normal(kx, (n, d), jnp.float32), intercept)
    gamma = gamma_sd * jax.random.normal(kg, (n_entities, rank), jnp.float32)
    b = b_sd * jax.random.normal(kb, (rank, d), jnp.float32)
    w = (jnp.matmul(gamma, b, precision="highest")
         + w_sd * jax.random.normal(ke, (n_entities, d), jnp.float32))
    margin = jnp.einsum("nd,nd->n", x, w[entity_of_row], precision="highest")
    return x, margin


def group_features(config: dict, seed: int, index: int, codes: np.ndarray):
    """Per-row features ``f32[n, d]`` of group ``index`` and their true
    margin."""
    group = groups_of(config)[index]
    truth = group.get("truth")
    if truth is None:
        return dense_glm._entity_features(
            dense_glm.seed_key(seed, 2 + 10 * index),
            dense_glm.seed_key(seed, 3 + 10 * index), jnp.asarray(codes),
            float(group["w_sd"]), float(group["x_sd"]), len(codes),
            int(group["n_entities"]), int(group["d"]),
            group.get("intercept", "none"))
    return _low_rank_features(
        dense_glm.seed_key(seed, 2 + 10 * index), jnp.asarray(codes),
        float(group["x_sd"]), float(group["w_sd"]),
        float(truth["gamma_sd"]), float(truth["b_sd"]), len(codes),
        int(group["n_entities"]), int(group["d"]), int(truth["rank"]),
        group.get("intercept", "none"))


def make(config: dict, seed: int) -> Problem:
    """The configuration's problem as ``seed`` draws it, on the default
    device."""
    n = int(config["n_rows"])
    if n != dense_glm.n_rows_of(config):
        raise ValueError(f"the configuration states n_rows {n}; its first "
                         "group's activity sums to "
                         f"{dense_glm.n_rows_of(config)}")
    fixed = config["fixed"]
    margin_re = jnp.zeros((n,), jnp.float32)
    groups: Dict[str, Group] = {}
    for index, g in enumerate(groups_of(config)):
        counts = group_counts(g, n)
        if len(counts) != int(g["n_entities"]) or int(counts.sum()) != n:
            raise ValueError(
                f"group {g['name']!r} states {g['n_entities']} entities; "
                f"its law deals {len(counts)} over {int(counts.sum())} rows")
        codes = entity_of_row(config, seed, index)
        xg, margin_g = group_features(config, seed, index, codes)
        margin_re = margin_re + margin_g
        d_entity = int(g["d"])
        d_pad = int(dense_glm.next_size(np.asarray([d_entity]), 8)[0])
        feat_row = np.full(d_pad, -1, np.int32)
        feat_row[:d_entity] = np.arange(d_entity)
        buckets = []
        for lay in dense_glm.bucket_layout(codes, len(counts), n):
            row_ids = jnp.asarray(lay["row_ids"])
            e = row_ids.shape[0]
            buckets.append(Bucket(
                codes=lay["codes"], row_ids=row_ids,
                feat_idx=jnp.asarray(np.tile(feat_row, (e, 1))),
                x=dense_glm._gather_x_blocks(
                    xg, row_ids, d_pad, min(dense_glm.ENTITY_CHUNK, e))))
        jax.block_until_ready([b.x for b in buckets])
        del xg  # the blocks hold what the cell needs of it
        groups[g["name"]] = Group(g["name"], codes, len(counts), d_entity,
                                  buckets)

    x, margin = dense_glm._draw_x(
        dense_glm.seed_key(seed, 1), dense_glm.true_fixed(config, seed),
        float(fixed["x_sd"]), n, min(dense_glm.CHUNK_ROWS, n),
        fixed.get("intercept", "none"))
    labels = dense_glm._draw_labels(dense_glm.seed_key(seed, 5),
                                    margin + margin_re, config["link"])
    offsets = jnp.zeros((n,), jnp.float32)
    weights = jnp.ones((n,), jnp.float32)
    for g in groups.values():
        for b in g.buckets:
            b.labels = dense_glm._gather_rows(labels, b.row_ids)
            b.offsets = dense_glm._gather_rows(offsets, b.row_ids)
            b.weights = dense_glm._gather_rows(weights, b.row_ids)
    prob = Problem(n_rows=n, x=x, labels=labels, offsets=offsets,
                   weights=weights, groups=groups)
    jax.block_until_ready((x, labels, [b.labels for g in groups.values()
                                       for b in g.buckets]))
    return prob
