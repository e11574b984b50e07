"""Sharding the GLM/GAME workloads over a device mesh.

The communication design (SURVEY §2.3) — what the reference does with Spark
primitives, expressed as XLA collectives over ICI:

- **Fixed effect (data parallel)**: batch rows (dense layout) or the nnz
  stream + row vector (CSR layout) shard over the ``data`` mesh axis;
  coefficients replicate. The gradient contraction ``x.T @ (w * dz)`` then
  compiles to per-device partial products + an ICI all-reduce — exactly the
  role of RDD.treeAggregate + coefficient broadcast in the reference
  (ValueAndGradientAggregator.scala:243-247,
  DistributedObjectiveFunction.scala:56-72), minus the per-step host round
  trip: parameters never leave HBM between L-BFGS iterations.
- **Random effects (entity sharding)**: bucketed entity blocks shard along
  their leading entity axis; the vmapped solver is elementwise over entities,
  so XLA partitions it with zero communication — the analog of the
  co-partitioned mapValues solve (RandomEffectCoordinate.scala:104-113).
  The score exchange between rows and entity slots is divided by the
  program (``algorithm/coordinates.py``: each device gathers and scatters
  its own slots, one n-vector all-reduce each way); left to the partitioner
  it is replicated on every device.

Everything uses plain ``jax.sharding.NamedSharding`` + jit: XLA's SPMD
partitioner inserts psum/all-gather where the math requires, which is the
"pick a mesh, annotate shardings, let XLA insert collectives" recipe.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from photon_ml_tpu.data.random_effect import EntityBlock
from photon_ml_tpu.ops.features import (
    BlockedCSRFeatures,
    BlockedEllFeatures,
    CSRFeatures,
    DenseFeatures,
    SlotMajorEllFeatures,
)
from photon_ml_tpu.ops.glm_objective import GLMBatch

Array = jax.Array

DATA_AXIS = "data"
MODEL_AXIS = "model"


def make_mesh(num_devices: Optional[int] = None,
              axis: str = DATA_AXIS) -> Mesh:
    """1-D mesh over the first ``num_devices`` devices (default: all)."""
    devs = jax.devices()
    if num_devices is not None:
        if num_devices > len(devs):
            raise ValueError(
                f"requested {num_devices} devices, have {len(devs)}")
        devs = devs[:num_devices]
    return Mesh(np.asarray(devs), (axis,))


def mesh_device_list(mesh: Mesh) -> list:
    """Devices of a 1-D mesh in axis order — the round-robin assignment
    and fixed fold order of the mesh-parallel streamed objective
    (ops/sharded_objective.py): shard-cache block i lives on
    ``mesh_device_list(mesh)[i % D]``, and cross-device partials combine
    in this order. Rejects 2-D meshes: the streamed fold's device axis
    is one-dimensional (the feature/column axis composes separately via
    :func:`shard_batch_csr_feature_dim`)."""
    if len(mesh.shape) != 1:
        raise ValueError(
            f"expected a 1-D mesh, got axes {tuple(mesh.shape)} — the "
            "streamed device fold round-robins blocks over one axis")
    return list(np.asarray(mesh.devices).flat)


def make_mesh_2d(num_data: int, num_model: int,
                 data_axis: str = DATA_AXIS,
                 model_axis: str = MODEL_AXIS) -> Mesh:
    """2-D (data, model) mesh: batch rows shard over ``data_axis``, the
    feature/coefficient dimension over ``model_axis``. The TPU analog of the
    reference's two scale axes — #examples via partitioned RDDs and #features
    via treeAggregate depth-2 beyond 200k features
    (GameEstimator.scala:330-334, 523-525)."""
    devs = jax.devices()
    need = num_data * num_model
    if need > len(devs):
        raise ValueError(f"requested {need} devices, have {len(devs)}")
    grid = np.asarray(devs[:need]).reshape(num_data, num_model)
    return Mesh(grid, (data_axis, model_axis))


def mesh_grid_2d(mesh: Mesh) -> tuple:
    """``(R, C, grid)`` of a 1-D or 2-D mesh: ``R`` data-axis devices,
    ``C`` model-axis devices, ``grid`` the row-major ``[R][C]`` device
    lists. A 1-D mesh is the ``C = 1`` column — the streamed fold's
    round-robin data axis with no coefficient sharding. This is the one
    mesh-shape accessor of the 2-D streamed objective
    (ops/sharded_objective.py): cache shard ``i``'s column block ``c``
    lives on ``grid[i % R][c]`` and the flat row-major order
    (:func:`mesh_fold_devices`) is the cache's ``devices=`` list."""
    arr = np.asarray(mesh.devices)
    if arr.ndim == 1:
        return int(arr.shape[0]), 1, [[d] for d in arr.flat]
    if arr.ndim != 2:
        raise ValueError(
            f"expected a 1-D or 2-D mesh, got axes {tuple(mesh.shape)}")
    return (int(arr.shape[0]), int(arr.shape[1]),
            [list(row) for row in arr])


def mesh_fold_devices(mesh: Mesh) -> list:
    """Flat ROW-MAJOR device list of a 1-D or 2-D (data, model) mesh —
    the ``devices=`` placement list for `DeviceShardCache`: slot
    ``(i % R) * C + c`` holds shard ``i``'s column block ``c``. For a
    1-D mesh this is exactly :func:`mesh_device_list`."""
    r, c, grid = mesh_grid_2d(mesh)
    return [d for row in grid for d in row]


def split_csr_columns(mat, num_blocks: int) -> tuple:
    """Host-side twin of :func:`shard_batch_csr_feature_dim`'s column
    routing for a scipy CSR matrix: ``(block_size, [sub_0..sub_{C-1}])``
    where ``block_size = ceil(d / num_blocks)`` (the
    `blocked_csr_from_scipy` rule — ``owner = col // block_size``) and
    ``sub_c`` is the canonical CSR slice ``mat[:, c*bs:(c+1)*bs]`` with
    LOCAL column ids. Scipy column slicing preserves canonical (row-
    major, column-ascending) entry order, so each block's nnz stream is
    an order-preserving subsequence of the full stream — the property
    that makes the streamed objective's chained per-block scatters
    bitwise-reproduce the unblocked contraction
    (ops/sharded_objective.py module docstring)."""
    import scipy.sparse as sp

    if num_blocks < 1:
        raise ValueError(f"num_blocks must be >= 1, got {num_blocks}")
    mat = sp.csr_matrix(mat)
    d = mat.shape[1]
    block = -(-d // num_blocks)
    subs = []
    for c in range(num_blocks):
        lo = min(c * block, d)
        hi = min(lo + block, d)
        sub = mat[:, lo:hi].tocsr()
        sub.sort_indices()
        subs.append(sub)
    return block, subs


def _pad_to_multiple(a: np.ndarray | Array, k: int, axis: int,
                     fill) -> Array:
    n = a.shape[axis]
    pad = (-n) % k
    if pad == 0:
        return jnp.asarray(a)
    widths = [(0, 0)] * a.ndim
    widths[axis] = (0, pad)
    return jnp.pad(jnp.asarray(a), widths, constant_values=fill)


def replicate(x, mesh: Mesh):
    return jax.device_put(x, NamedSharding(mesh, P()))


def _lay_over(a, sharding: NamedSharding, fill) -> Array:
    """``a`` padded along axis 0 to a multiple of the sharded axis'
    extent and laid out under ``sharding``, without ever holding the whole
    (or the whole padded) array on one device. The function reads the
    array's own placement: one that already lies over the mesh with this
    partitioning, with nothing to pad, is handed back as it is, the same
    buffers; of any other (on the host, on one device, over other
    devices) every device's shard is cut from the source, padded alone
    and put where it belongs.
    """
    k = sharding.mesh.shape[sharding.spec[0]]
    n = a.shape[0]
    pad = (-n) % k
    if (pad == 0 and isinstance(a, jax.Array)
            and a.sharding.is_equivalent_to(sharding, a.ndim)):
        return a
    shape = (n + pad,) + tuple(a.shape[1:])

    def shard_of(index):
        lo, hi, _ = index[0].indices(shape[0])
        piece = a[lo:min(hi, n)]
        short = (hi - lo) - piece.shape[0]
        if short:
            lib = jnp if isinstance(piece, jax.Array) else np
            piece = lib.pad(piece, [(0, short)] + [(0, 0)] * (a.ndim - 1),
                            constant_values=fill)
        return piece

    return jax.make_array_from_callback(shape, sharding, shard_of)


def shard_batch(batch: GLMBatch, mesh: Mesh, axis: str = DATA_AXIS
                ) -> GLMBatch:
    """Shard a GLMBatch's row (or nnz) dimension over the mesh.

    Rows are padded to a multiple of the mesh size with weight-0 rows
    (inert in the objective). For CSR the nnz stream is padded with zero
    values pointing at row/col 0; a slot-major ELL is sharded as the flat
    triplet it unrolls to (``to_csr``: its stored slots, entry by entry).
    Each device's shard is padded and placed alone, and a batch that
    already lies over the mesh row by row comes back with the buffers it
    came with (``_lay_over``).
    """
    row_sh = NamedSharding(mesh, P(axis))

    labels = _lay_over(batch.labels, row_sh, 0.0)
    feats = batch.features
    if isinstance(feats, SlotMajorEllFeatures):
        feats = feats.to_csr()
    if isinstance(feats, DenseFeatures):
        new_feats = DenseFeatures(_lay_over(
            feats.x, NamedSharding(mesh, P(axis, None)), 0.0))
    elif isinstance(feats, CSRFeatures):
        new_feats = CSRFeatures(
            values=_lay_over(feats.values, row_sh, 0.0),
            col_ids=_lay_over(feats.col_ids, row_sh, 0),
            row_ids=_lay_over(feats.row_ids, row_sh, 0),
            n_rows=int(labels.shape[0]),
            n_features=feats.n_features,
            counts=feats.counts,
        )
    else:
        raise TypeError(f"unsupported feature type {type(feats)}")

    return GLMBatch(
        features=new_feats,
        labels=labels,
        offsets=_lay_over(batch.offsets, row_sh, 0.0),
        weights=_lay_over(batch.weights, row_sh, 0.0),
    )


def shard_batch_feature_dim(
    batch: GLMBatch,
    mesh: Mesh,
    col_axis: str = DATA_AXIS,
    row_axis: Optional[str] = None,
) -> GLMBatch:
    """Shard a dense GLMBatch's FEATURE (column) dimension over the mesh —
    the coefficient-sharded mode for d beyond per-chip HBM (SURVEY §5: the
    reference's #features scale axis, treeAggregate depth 2 past 200k
    features).

    Columns are zero-padded to a multiple of the mesh extent; the matching
    coefficient layout comes from :func:`shard_coef`. With X sharded
    ``P(row?, col_axis)`` and coefficients ``P(col_axis)``, the margin
    ``X @ w`` compiles to per-device partial products + an ICI psum of
    partial margins, and the gradient contraction comes back sharded over
    the coefficient axis — parameters never materialize unsharded anywhere.

    Padded coordinates stay exactly zero during optimization: their data
    columns are zero, so their smooth gradient is identically zero.

    Pass ``row_axis`` on a 2-D mesh (:func:`make_mesh_2d`) to shard rows and
    columns simultaneously; rows are padded with weight-0 rows.
    """
    feats = batch.features
    if isinstance(feats, SlotMajorEllFeatures):
        feats = feats.to_csr()
        batch = GLMBatch(feats, batch.labels, batch.offsets, batch.weights)
    if isinstance(feats, (CSRFeatures, BlockedCSRFeatures,
                          BlockedEllFeatures)):
        # Sparse huge-d regime: route through the column-blocked sparse
        # layouts instead of densifying.
        return shard_batch_csr_feature_dim(batch, mesh, col_axis=col_axis,
                                           row_axis=row_axis)
    if not isinstance(feats, DenseFeatures):
        raise TypeError(
            f"unsupported feature type {type(feats)} for feature-dimension "
            "sharding")
    kc = mesh.shape[col_axis]
    x = _pad_to_multiple(feats.x, kc, 1, 0.0)
    labels, offsets, weights = batch.labels, batch.offsets, batch.weights
    if row_axis is not None:
        kr = mesh.shape[row_axis]
        x = _pad_to_multiple(x, kr, 0, 0.0)
        labels = _pad_to_multiple(labels, kr, 0, 0.0)
        offsets = _pad_to_multiple(offsets, kr, 0, 0.0)
        weights = _pad_to_multiple(weights, kr, 0, 0.0)
    row_sh = NamedSharding(mesh, P(row_axis)) if row_axis else \
        NamedSharding(mesh, P())
    return GLMBatch(
        features=DenseFeatures(jax.device_put(
            x, NamedSharding(mesh, P(row_axis, col_axis)))),
        labels=jax.device_put(labels, row_sh),
        offsets=jax.device_put(offsets, row_sh),
        weights=jax.device_put(weights, row_sh),
    )


def shard_batch_csr_feature_dim(
    batch: GLMBatch,
    mesh: Mesh,
    col_axis: str = DATA_AXIS,
    row_axis: Optional[str] = None,
) -> GLMBatch:
    """Feature-dimension sharding for SPARSE features: nnz entries are
    partitioned into per-device column blocks (BlockedCSRFeatures) whose
    leading block axis shards over ``col_axis``. Margins compile to
    per-device partial segment-sums + an ICI psum over the block axis;
    the gradient scatter stays entirely local to each device's coefficient
    slice. This is the layout for the reference's "hundreds of billions of
    coefficients" sparse regime (README §GAME), where densifying X is
    impossible — only the nnz stream and the sharded coefficient vector
    ever exist in HBM.

    The nnz stream cannot shard over rows simultaneously (entries are
    routed by column), so ``row_axis`` must be None; n-vectors replicate.
    """
    from photon_ml_tpu.ops.features import blocked_csr_from_scipy

    if row_axis is not None:
        raise ValueError(
            "CSR feature-dim sharding routes nnz by column; a 2-D "
            "(row x col) layout is only available for dense features")
    kc = mesh.shape[col_axis]
    feats = batch.features
    if isinstance(feats, CSRFeatures):
        import scipy.sparse as sp

        host = sp.coo_matrix(
            (np.asarray(feats.values), (np.asarray(feats.row_ids),
                                        np.asarray(feats.col_ids))),
            shape=feats.shape)
        feats = blocked_csr_from_scipy(host, kc,
                                       dtype=feats.values.dtype)
    if not isinstance(feats, (BlockedCSRFeatures, BlockedEllFeatures)):
        raise TypeError(f"expected CSR/ELL features, got {type(feats)}")
    if feats.num_blocks != kc:
        raise ValueError(
            f"features have {feats.num_blocks} column blocks, mesh axis "
            f"{col_axis!r} has {kc} devices — rebuild with num_blocks={kc}")
    rep = NamedSharding(mesh, P())
    if isinstance(feats, BlockedEllFeatures):
        blk3 = NamedSharding(mesh, P(col_axis, None, None))
        new_feats = BlockedEllFeatures(
            vals_r=jax.device_put(feats.vals_r, blk3),
            col_local_r=jax.device_put(feats.col_local_r, blk3),
            vals_c=jax.device_put(feats.vals_c, blk3),
            row_ids_c=jax.device_put(feats.row_ids_c, blk3),
            n_rows=feats.n_rows,
            n_features=feats.n_features,
            block_size=feats.block_size,
        )
    else:
        blk_sh = NamedSharding(mesh, P(col_axis, None))
        new_feats = BlockedCSRFeatures(
            values=jax.device_put(feats.values, blk_sh),
            col_local=jax.device_put(feats.col_local, blk_sh),
            row_ids=jax.device_put(feats.row_ids, blk_sh),
            n_rows=feats.n_rows,
            n_features=feats.n_features,
            block_size=feats.block_size,
        )
    return GLMBatch(
        features=new_feats,
        labels=jax.device_put(batch.labels, rep),
        offsets=jax.device_put(batch.offsets, rep),
        weights=jax.device_put(batch.weights, rep),
    )


def shard_coef(coef, mesh: Mesh, axis: str = DATA_AXIS) -> Array:
    """Zero-pad a coefficient vector to a multiple of the mesh extent and
    shard it over ``axis`` — the layout matching
    :func:`shard_batch_feature_dim`. Replaces the reference's per-evaluation
    driver broadcast of coefficients
    (DistributedObjectiveFunction.scala:56-72) with a permanently
    device-resident sharded vector."""
    k = mesh.shape[axis]
    coef = _pad_to_multiple(jnp.asarray(coef), k, 0, 0.0)
    return jax.device_put(coef, NamedSharding(mesh, P(axis)))


def unpad_coef(coef, num_features: int) -> Array:
    """Strip feature-dim padding from a (possibly sharded) coefficient
    vector or [k, d_padded] stack."""
    return jnp.asarray(coef)[..., :num_features]


def shard_block(block: EntityBlock, mesh: Mesh, sentinel_row: int,
                axis: str = DATA_AXIS) -> EntityBlock:
    """Shard an entity block along its entity axis.

    Entities are padded to a multiple of the mesh size with all-padding
    entities (weight 0 everywhere, row_ids == sentinel, feat_idx == -1);
    their solves converge instantly and their scatter contributions land in
    the sentinel slot. Shard by shard, as ``shard_batch`` does it: a block
    already laid over the mesh by entity comes back with its own buffers.
    """
    sh2 = NamedSharding(mesh, P(axis, None))
    sh3 = NamedSharding(mesh, P(axis, None, None))
    return EntityBlock(
        x=_lay_over(block.x, sh3, 0.0),
        labels=_lay_over(block.labels, sh2, 0.0),
        offsets=_lay_over(block.offsets, sh2, 0.0),
        weights=_lay_over(block.weights, sh2, 0.0),
        row_ids=_lay_over(block.row_ids, sh2, sentinel_row),
        feat_idx=_lay_over(block.feat_idx, sh2, -1),
    )
