"""Recipe ``dense_glm_mesh``: ``dense_glm``'s problem dealt out over the
chips, every array made where it will live.

The same model of data as ``recipes/dense_glm.py`` (a dense fixed-effect
matrix, one random-effect group of dense per-entity features, labels drawn
from a known truth; the sizes, the law of activity and the norms are the
configuration's, and the set of rows-per-entity counts is the same for
every seed), for a configuration whose X no single chip can hold:

- rows are split by range: device ``k`` of ``K`` holds rows
  ``[k*m, (k+1)*m)`` with ``m = ceil(n / K)``; the ``K*m - n`` rows past
  the last carry weight 0, label 0 and a zero row of X (the padding the
  program's own ``shard_batch`` would add);
- the entities of every size class are split by slot range, the class
  filled up to a multiple of ``K`` with empty entities (every row id the
  sentinel, weight 0, ``feat_idx`` -1: the program's ``shard_block``
  contract);
- every device draws ITS rows and ITS slots, under ``shard_map``: nothing
  of size n x d exists on one device or on the host. What the host holds
  is the entity of every row (``i32[n]``) and the row ids of the slots.

So that the problem does not follow the device count, every random number
belongs to a row, not to a position in a draw: row ``i``'s features are
``normal(fold_in(key, i), (d,))``, its label's uniform is
``uniform(fold_in(key, i))``. At equal configuration and seed the first n
rows and the first E entities of a class are the same numbers on one
device and on four (``tests/test_mesh_cell.py`` holds it to that).

It imports nothing of the program.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from benchmark.recipes import dense_glm
from benchmark.recipes.dense_glm import Bucket, seed_key

AXIS = "data"
CHUNK_ROWS = 1 << 17  # rows a device draws per loop step

scale_down = dense_glm.scale_down


@dataclasses.dataclass
class MeshProblem(dense_glm.Problem):
    """``dense_glm.Problem`` whose arrays lie over ``mesh``. ``n_rows`` is
    what the arrays hold (a multiple of the mesh size, and the sentinel of
    the slots' row ids); ``true_rows`` of them are the configuration's."""

    mesh: Optional[Mesh] = None
    true_rows: int = 0


def _rows_of(key, ids, d: int, sd, intercept: str, n: int):
    """Feature rows ``f32[len(ids), d]`` of the rows ``ids``: each row from
    its own key; a zero row where ``ids >= n``."""
    draw = jax.vmap(lambda i: jax.random.normal(
        jax.random.fold_in(key, i), (d,), jnp.float32))
    x = dense_glm._with_intercept(sd * draw(ids), intercept)
    return jnp.where((ids < n)[:, None], x, 0.0)


def _draw_fixed(mesh: Mesh, key, w_true, x_sd, n: int, m: int,
                intercept: str):
    """X ``f32[K*m, d]`` and its true margin, each device its own rows,
    chunk by chunk into one buffer (the last chunk shifted back, so every
    step has one shape)."""
    d = w_true.shape[0]
    chunk = min(CHUNK_ROWS, m)

    def local(key, w_true):
        lo = jax.lax.axis_index(AXIS) * m

        def body(i, carry):
            x, margin = carry
            start = jnp.minimum(i * chunk, m - chunk)
            xc = _rows_of(key, lo + start + jnp.arange(chunk), d, x_sd,
                          intercept, n)
            mc = jnp.matmul(xc, w_true, precision="highest")
            return (jax.lax.dynamic_update_slice(x, xc, (start, 0)),
                    jax.lax.dynamic_update_slice(margin, mc, (start,)))

        return jax.lax.fori_loop(
            0, -(-m // chunk), body,
            (jnp.zeros((m, d), jnp.float32), jnp.zeros((m,), jnp.float32)))

    return jax.jit(jax.shard_map(
        local, mesh=mesh, in_specs=(P(), P()),
        out_specs=(P(AXIS, None), P(AXIS)), check_vma=False))(key, w_true)


def _entity_margin(mesh: Mesh, key, wu, entity_of_row, x_sd, n: int,
                   m: int, d: int, intercept: str):
    """The random-effect group's true margin of every row,
    ``xu_i . wu[entity_i]``, each device its own rows."""
    chunk = min(CHUNK_ROWS, m)

    def local(key, wu, ent):
        lo = jax.lax.axis_index(AXIS) * m

        def body(i, margin):
            start = jnp.minimum(i * chunk, m - chunk)
            xc = _rows_of(key, lo + start + jnp.arange(chunk), d, x_sd,
                          intercept, n)
            ec = jax.lax.dynamic_slice(ent, (start,), (chunk,))
            mc = jnp.einsum("nd,nd->n", xc, wu[ec], precision="highest")
            return jax.lax.dynamic_update_slice(margin, mc, (start,))

        return jax.lax.fori_loop(0, -(-m // chunk), body,
                                 jnp.zeros((m,), jnp.float32))

    return jax.jit(jax.shard_map(
        local, mesh=mesh, in_specs=(P(), P(), P(AXIS)),
        out_specs=P(AXIS), check_vma=False))(key, wu, entity_of_row)


def _draw_blocks(mesh: Mesh, key, row_ids, x_sd, n: int, d: int,
                 d_pad: int, intercept: str):
    """``x[E, n_pad, d_pad]`` of one size class, each device its own slots:
    a slot's features are its row's, drawn again from the row's key."""
    def local(key, rid):
        e, r = rid.shape
        x = _rows_of(key, rid.reshape(-1), d, x_sd, intercept, n)
        return jnp.pad(x.reshape(e, r, d), ((0, 0), (0, 0), (0, d_pad - d)))

    return jax.jit(jax.shard_map(
        local, mesh=mesh, in_specs=(P(), P(AXIS, None)),
        out_specs=P(AXIS, None, None)))(key, row_ids)


def _draw_labels(mesh: Mesh, key, margin, n: int, m: int, link: str):
    if link != "logistic":
        raise ValueError(f"dense_glm_mesh draws logistic labels, not {link!r}")

    def local(key, margin):
        ids = jax.lax.axis_index(AXIS) * m + jnp.arange(m)
        u = jax.vmap(lambda i: jax.random.uniform(
            jax.random.fold_in(key, i), (), jnp.float32))(ids)
        live = ids < n
        return (jnp.where(live, u < jax.nn.sigmoid(margin), False).astype(
            jnp.float32), live.astype(jnp.float32))

    return jax.jit(jax.shard_map(
        local, mesh=mesh, in_specs=(P(), P(AXIS)),
        out_specs=(P(AXIS), P(AXIS))))(key, margin)


@functools.partial(jax.jit, static_argnames=("sharding",))
def _gather_rows(vec, row_ids, sharding):
    """``vec[row_ids]`` with 0 in the padding slots, laid out as the
    slots are (the vector is gathered whole on every device: n floats)."""
    ext = jnp.concatenate([vec, jnp.zeros((1,), vec.dtype)])
    return jax.lax.with_sharding_constraint(ext[row_ids], sharding)


def _put_rows(host: np.ndarray, sharding: NamedSharding, rows: int, fill):
    """A host array laid over the mesh along axis 0, filled up to ``rows``
    with ``fill``; every device is handed its own piece."""
    shape = (rows,) + host.shape[1:]

    def piece(index):
        lo, hi, _ = index[0].indices(rows)
        out = host[lo:min(hi, len(host))]
        if len(out) < hi - lo:
            out = np.concatenate([out, np.full(
                (hi - lo - len(out),) + host.shape[1:], fill, host.dtype)])
        return out

    return jax.make_array_from_callback(shape, sharding, piece)


def activity_counts(config: dict) -> np.ndarray:
    """Rows per entity, ascending: the law's quantiles
    (``dense_glm.activity_counts``), held to the row count the
    configuration states. A published count need not be the sum of a law's
    quantiles: where the quantiles sum to a little more, the rows over are
    taken one each from the most active entities below the law's maximum
    (the minimum, the median and the maximum stay as stated). The set is
    the configuration's; the seed only deals it out."""
    group = config["random"][0]
    counts = dense_glm.activity_counts(group)
    over = int(counts.sum()) - int(config["n_rows"])
    below = np.flatnonzero(counts < group["activity"]["max"])
    if not 0 <= over <= len(below) // 10:
        raise ValueError(
            f"the configuration states n_rows {config['n_rows']}; its "
            f"entities' activity sums to {int(counts.sum())}")
    counts[below[len(below) - over:]] -= 1
    return np.sort(counts)


def entity_of_row(config: dict, seed: int) -> np.ndarray:
    """``i32[n]``: the entity of every row (``dense_glm.entity_of_row``
    over this file's counts)."""
    rng = np.random.default_rng([int(seed), 1])
    counts = rng.permutation(activity_counts(config))
    codes = np.repeat(np.arange(len(counts), dtype=np.int32), counts)
    return rng.permutation(codes)


def make(config: dict, seed: int,
         devices: Optional[Sequence[jax.Device]] = None) -> MeshProblem:
    """The configuration's problem as ``seed`` draws it, over ``devices``
    (the configuration's ``chips`` first local devices)."""
    if devices is None:
        devices = jax.local_devices()[:int(config["chips"])]
    mesh = Mesh(np.asarray(devices), (AXIS,))
    k = len(devices)
    n = int(config["n_rows"])
    groups = config.get("random", [])
    if len(groups) != 1:
        raise ValueError("dense_glm_mesh makes one random-effect group")
    fixed, g = config["fixed"], groups[0]
    m = -(-n // k)
    rows = k * m
    row_sh = NamedSharding(mesh, P(AXIS))
    slot_sh = NamedSharding(mesh, P(AXIS, None))

    n_entities, d_entity = int(g["n_entities"]), int(g["d"])
    codes = entity_of_row(config, seed)
    wu = float(g["w_sd"]) * jax.random.normal(
        seed_key(seed, 3), (n_entities, d_entity), jnp.float32)
    margin_re = _entity_margin(
        mesh, seed_key(seed, 2), wu, _put_rows(codes, row_sh, rows, 0),
        float(g["x_sd"]), n, m, d_entity, g.get("intercept", "none"))
    d_pad = int(dense_glm.next_size(np.asarray([d_entity]), 8)[0])
    feat_row = np.full(d_pad, -1, np.int32)
    feat_row[:d_entity] = np.arange(d_entity)
    buckets: List[Bucket] = []
    for lay in dense_glm.bucket_layout(codes, n_entities, n):
        ids = np.where(lay["row_ids"] == n, rows, lay["row_ids"])
        e = len(ids)
        slots = -(-e // k) * k
        row_ids = _put_rows(ids.astype(np.int32), slot_sh, slots, rows)
        feat_idx = _put_rows(np.tile(feat_row, (e, 1)), slot_sh, slots, -1)
        buckets.append(Bucket(
            codes=lay["codes"], row_ids=row_ids, feat_idx=feat_idx,
            x=_draw_blocks(mesh, seed_key(seed, 2), row_ids,
                           float(g["x_sd"]), n, d_entity, d_pad,
                           g.get("intercept", "none"))))

    x, margin = _draw_fixed(
        mesh, seed_key(seed, 1), dense_glm.true_fixed(config, seed),
        float(fixed["x_sd"]), n, m, fixed.get("intercept", "none"))
    labels, weights = _draw_labels(mesh, seed_key(seed, 5),
                                   margin + margin_re, n, m, config["link"])
    offsets = jax.jit(lambda: jnp.zeros((rows,), jnp.float32),
                      out_shardings=row_sh)()
    for b in buckets:
        b.labels = _gather_rows(labels, b.row_ids, slot_sh)
        b.offsets = _gather_rows(offsets, b.row_ids, slot_sh)
        b.weights = _gather_rows(weights, b.row_ids, slot_sh)
    prob = MeshProblem(
        n_rows=rows, x=x, labels=labels, offsets=offsets, weights=weights,
        entity_of_row=codes, n_entities=n_entities, d_entity=d_entity,
        buckets=buckets, mesh=mesh, true_rows=n)
    jax.block_until_ready((x, labels, [(b.x, b.labels) for b in buckets]))
    return prob
