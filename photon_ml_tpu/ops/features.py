"""Device-resident feature matrix representations.

The reference keeps features as per-row Breeze sparse vectors inside RDDs
(ml/data/LabeledPoint.scala). On TPU the analogous choice is struct-of-arrays
in HBM, in one of two layouts:

- ``DenseFeatures``: padded dense ``f32[n, d]`` — the right layout whenever d
  is modest (per-entity blocks after feature selection, tutorial datasets).
  Margins are a single MXU matmul.
- ``CSRFeatures``: flat ``values/col_ids/row_ids`` triplet (COO-sorted-by-row,
  i.e. expanded CSR) padded to a static nnz — the layout for very wide sparse
  fixed-effect problems. Margins are a segment-sum; the transpose product is a
  scatter-add. Both are static-shape and jit/vmap-safe.

Both are registered pytrees, so they flow through ``jit``/``vmap``/``pjit``
and can be sharded with ``NamedSharding`` like any other array.

A sparse matrix that is already ON the device (a row's non-zeros side by
side, ``cols i32[n, k]`` / ``vals f[n, k]``) comes in through
``sparse_rows_to_device``, which counts it there and chooses its layout
(``choose_layout``); ``features_to_device``, the host path, ends in the same
chooser.
"""

from __future__ import annotations

import dataclasses
import functools
import itertools
from typing import Optional, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from photon_ml_tpu.telemetry import scopes

Array = jax.Array


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass(frozen=True)
class DenseFeatures:
    """Dense feature matrix x: [n_rows, n_features].

    ``x`` may be stored in bfloat16 (``DenseFeatures.bf16(...)`` or
    ``features_to_device(..., storage_dtype=jnp.bfloat16)``): products
    then read HALF the HBM bytes — the fixed-effect iteration is
    bandwidth-bound, so this is ~2x on the dominant term — while every
    contraction accumulates in the coefficient dtype via
    ``preferred_element_type`` (the MXU natively takes bf16 inputs with
    f32 accumulation; see docs/F32_PARITY.md for the loss-parity
    validation recipe)."""

    x: Array

    @property
    def shape(self) -> Tuple[int, int]:
        return self.x.shape

    @property
    def num_features(self) -> int:
        return self.x.shape[-1]

    @classmethod
    def bf16(cls, x) -> "DenseFeatures":
        return cls(jnp.asarray(x, jnp.bfloat16))

    def _acc(self, v: Array):
        # Accumulate in the solver dtype, never in the storage dtype.
        return jnp.promote_types(v.dtype, jnp.float32)

    def matvec(self, v: Array) -> Array:
        """x @ v -> [n_rows]. v may have a leading batch dim under vmap.

        With bf16 storage, jnp.matmul's type promotion inserts a
        convert(x)->f32 — verified HARMLESS on the v5e compile: the
        convert stays inside the product fusion (temp bytes = 0, X read
        at storage width), so traffic halves while the multiply-
        accumulate stays f32. Do NOT 'fix' this by down-casting v to
        bf16 — that loses precision for zero traffic gain. (XLA's
        cost-analysis 'bytes accessed' counts the fused convert's
        virtual output and will claim the bf16 ratio is ~1.0; see
        bench.aot_fe_cost_analysis.)"""
        return jnp.matmul(self.x, v, preferred_element_type=self._acc(v))

    def rmatvec(self, u: Array) -> Array:
        """x.T @ u -> [n_features]."""
        return jnp.matmul(u, self.x, preferred_element_type=self._acc(u))

    def row_sq_matvec(self, v: Array) -> Array:
        """(x*x) @ v — used for Hessian-diagonal aggregation. The square
        is formed in the accumulation dtype (an elementwise convert XLA
        fuses into the matmul's operand read — traffic stays at storage
        width)."""
        acc = self._acc(v)
        xsq = self.x.astype(acc) * self.x.astype(acc)
        return jnp.matmul(xsq, v, preferred_element_type=acc)

    def sq_rmatvec(self, u: Array) -> Array:
        """(x*x).T @ u -> [n_features] — per-feature weighted square sums."""
        acc = self._acc(u)
        xsq = self.x.astype(acc) * self.x.astype(acc)
        return jnp.matmul(u, xsq, preferred_element_type=acc)

    def tree_flatten(self):
        return (self.x,), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass(frozen=True)
class CSRFeatures:
    """Sparse feature matrix in expanded-CSR (row-sorted COO) layout.

    values[k] at (row_ids[k], col_ids[k]); padded entries carry value 0 and
    point at row 0 / col 0, so they contribute nothing to any product.

    n_rows / n_features are static Python ints (aux data) — they fix the
    output shapes for XLA.

    Kernel note (TPU v5e, the cell ``sparse-lr.fit``, PERF.md section 5): a
    gather and a scatter-add (which is what XLA makes of ``segment_sum``)
    each cost ~6.7 ns an index or more, and each product here is one of
    each: two index operations a non-zero (timed on that cell's data: 20.2
    ns a non-zero ``matvec``, 14.2 ``rmatvec``), where
    ``SlotMajorEllFeatures`` below pays one a stored slot (6.65 / 6.75).
    For ragged rows; ``choose_layout`` decides.
    """

    values: Array  # f[nnz]
    col_ids: Array  # i32[nnz]
    row_ids: Array  # i32[nnz]
    n_rows: int
    n_features: int
    # what the chooser counted, where it built this matrix (static, aux data)
    counts: Optional["LayoutCounts"] = None

    @property
    def shape(self) -> Tuple[int, int]:
        return (self.n_rows, self.n_features)

    @property
    def num_features(self) -> int:
        return self.n_features

    def matvec(self, v: Array) -> Array:
        contrib = self.values * v[self.col_ids]
        return jax.ops.segment_sum(contrib, self.row_ids, num_segments=self.n_rows)

    def rmatvec(self, u: Array) -> Array:
        contrib = self.values * u[self.row_ids]
        return jax.ops.segment_sum(
            contrib, self.col_ids, num_segments=self.n_features
        )

    def row_sq_matvec(self, v: Array) -> Array:
        sq = self.values * self.values
        contrib = sq * v[self.col_ids]
        return jax.ops.segment_sum(contrib, self.row_ids, num_segments=self.n_rows)

    def sq_rmatvec(self, u: Array) -> Array:
        sq = self.values * self.values
        contrib = sq * u[self.row_ids]
        return jax.ops.segment_sum(
            contrib, self.col_ids, num_segments=self.n_features
        )

    def to_dense(self) -> DenseFeatures:
        x = jnp.zeros((self.n_rows, self.n_features), dtype=self.values.dtype)
        x = x.at[self.row_ids, self.col_ids].add(self.values)
        return DenseFeatures(x)

    def tree_flatten(self):
        return (self.values, self.col_ids, self.row_ids), (
            self.n_rows,
            self.n_features,
            self.counts,
        )

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children, *aux)


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass(frozen=True)
class KroneckerFeatures:
    """Lazy row-wise Kronecker product: virtual row i = vec(γ_i ⊗ x_i).

    The latent-matrix refit of a factored random effect solves a GLM whose
    coefficient vector is the flattened projection matrix B[k, d] and whose
    features are x_i ⊗ γ_entity(i) (reference:
    ml/algorithm/FactoredRandomEffectCoordinate.scala:269-287, which
    materializes the product per datum and shuffles it). Here the product is
    never materialized: every matvec/rmatvec contracts through einsum, so the
    MXU sees [n,d]x[k,d] contractions instead of an [n, k*d] blow-up.

    Flattening convention: coefficient index (a, j) -> a * d + j, i.e.
    ``B.reshape(-1)`` of a [k, d] matrix.

    Every contraction asks for ``Precision.HIGHEST``: these are true matrix
    products, which the TPU's MXU multiplies in bfloat16 by default (a
    matrix-VECTOR product, ``DenseFeatures``', compiles to an exact float32
    multiply-reduce and needs no such word). A float32 refit at bfloat16
    products ends 1e-3 from its own objective's minimiser (PR 37's chip
    runs, read again in PR 38); the products are k*d = 200 multiply-adds a
    row of 33 floats read, far under the MXU's peak at any precision.
    """

    PRECISION = jax.lax.Precision.HIGHEST

    x: Array  # f[n, d]
    gamma: Array  # f[n, k]

    @property
    def shape(self) -> Tuple[int, int]:
        return (self.x.shape[0], self.num_features)

    @property
    def num_features(self) -> int:
        return self.gamma.shape[-1] * self.x.shape[-1]

    def _as_matrix(self, v: Array) -> Array:
        return v.reshape(self.gamma.shape[-1], self.x.shape[-1])

    def matvec(self, v: Array) -> Array:
        """margin_i = γ_iᵀ B x_i."""
        return jnp.einsum("nd,kd,nk->n", self.x, self._as_matrix(v),
                          self.gamma, precision=self.PRECISION)

    def rmatvec(self, u: Array) -> Array:
        """Σ_i u_i γ_i x_iᵀ, flattened."""
        return jnp.einsum("n,nk,nd->kd", u, self.gamma, self.x,
                          precision=self.PRECISION).reshape(-1)

    def row_sq_matvec(self, v: Array) -> Array:
        return jnp.einsum("nd,kd,nk->n", jnp.square(self.x),
                          self._as_matrix(v), jnp.square(self.gamma),
                          precision=self.PRECISION)

    def sq_rmatvec(self, u: Array) -> Array:
        return jnp.einsum("n,nk,nd->kd", u, jnp.square(self.gamma),
                          jnp.square(self.x),
                          precision=self.PRECISION).reshape(-1)

    def tree_flatten(self):
        return (self.x, self.gamma), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass(frozen=True)
class BlockedCSRFeatures:
    """CSR partitioned into column blocks — the SPARSE feature-dimension-
    sharded layout for d beyond per-chip HBM (SURVEY §5: the reference's
    #features axis, treeAggregate depth 2 past 200k features,
    GameEstimator.scala:330-334; README "hundreds of billions of
    coefficients" is a sparse regime, so densifying is a non-starter).

    nnz entries are routed to the block owning their column; each block
    stores LOCAL column ids (col - block*block_size) padded to the max
    block nnz.
    With the leading block axis sharded over the mesh and coefficients
    sharded to match ([kb, block_size]):

    - ``matvec``: per-block partial margins (gather + segment_sum over the
      full row space) then a sum over blocks — XLA lowers the block-axis
      reduction to an ICI psum of partial margins.
    - ``rmatvec``: per-block scatter into the block's OWN coefficient
      slice — no communication; the gradient comes back sharded exactly
      like the coefficients.

    Also a fine single-device layout (blocks just batch).
    """

    values: Array  # f[kb, m]
    col_local: Array  # i32[kb, m] — column - block_start, in [0, block)
    row_ids: Array  # i32[kb, m]
    n_rows: int
    n_features: int  # padded: kb * block_size
    block_size: int

    @property
    def num_blocks(self) -> int:
        return self.values.shape[0]

    @property
    def shape(self) -> Tuple[int, int]:
        return (self.n_rows, self.n_features)

    @property
    def num_features(self) -> int:
        return self.n_features

    def _coef_blocks(self, v: Array) -> Array:
        return v.reshape(self.num_blocks, self.block_size)

    def matvec(self, v: Array) -> Array:
        vb = self._coef_blocks(v)
        contrib = self.values * jnp.take_along_axis(
            vb, self.col_local, axis=1)
        partial = jax.vmap(
            lambda c, r: jax.ops.segment_sum(c, r, num_segments=self.n_rows)
        )(contrib, self.row_ids)  # [kb, n_rows]
        return jnp.sum(partial, axis=0)

    def rmatvec(self, u: Array) -> Array:
        contrib = self.values * u[self.row_ids]
        out = jax.vmap(
            lambda c, col: jax.ops.segment_sum(
                c, col, num_segments=self.block_size)
        )(contrib, self.col_local)  # [kb, block]
        return out.reshape(-1)

    def row_sq_matvec(self, v: Array) -> Array:
        vb = self._coef_blocks(v)
        contrib = (self.values * self.values) * jnp.take_along_axis(
            vb, self.col_local, axis=1)
        partial = jax.vmap(
            lambda c, r: jax.ops.segment_sum(c, r, num_segments=self.n_rows)
        )(contrib, self.row_ids)
        return jnp.sum(partial, axis=0)

    def sq_rmatvec(self, u: Array) -> Array:
        contrib = (self.values * self.values) * u[self.row_ids]
        out = jax.vmap(
            lambda c, col: jax.ops.segment_sum(
                c, col, num_segments=self.block_size)
        )(contrib, self.col_local)
        return out.reshape(-1)

    def tree_flatten(self):
        return (self.values, self.col_local, self.row_ids), (
            self.n_rows, self.n_features, self.block_size)

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children, *aux)


def blocked_csr_from_scipy(mat, num_blocks: int,
                           dtype=jnp.float32) -> BlockedCSRFeatures:
    """Partition a scipy.sparse matrix's nnz by column block (host-side
    ingest for the feature-dim-sharded mode). Columns are implicitly
    zero-padded to a multiple of ``num_blocks``."""
    coo = mat.tocoo()
    n_rows, d = coo.shape
    block = -(-d // num_blocks)  # ceil
    owner = coo.col // block
    # Vectorized routing: stable-sort nnz by owner, then each block's
    # entries are a contiguous run placed at consecutive slots
    # (position-within-run via the shared _ell_pack helper).
    order = np.argsort(owner, kind="stable")
    o_sorted = owner[order]
    slot, m = _ell_pack(o_sorted, num_blocks)
    values = np.zeros((num_blocks, m), dtype=coo.data.dtype)
    col_local = np.zeros((num_blocks, m), dtype=np.int32)
    row_ids = np.zeros((num_blocks, m), dtype=np.int32)
    values[o_sorted, slot] = coo.data[order]
    col_local[o_sorted, slot] = coo.col[order] - o_sorted * block
    row_ids[o_sorted, slot] = coo.row[order]
    return BlockedCSRFeatures(
        values=jnp.asarray(values, dtype),
        col_local=jnp.asarray(col_local),
        row_ids=jnp.asarray(row_ids),
        n_rows=int(n_rows),
        n_features=int(num_blocks * block),
        block_size=int(block),
    )


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass(frozen=True)
class BlockedEllFeatures:
    """Dual ELLPACK sparse layout, partitioned into column blocks — the
    TPU-FAST sparse layout: BOTH products are gather + fixed-width
    reductions, with NO scatter anywhere.

    Motivation (measured, TPU v5e via this repo's bench): XLA's
    scatter-add (`segment_sum`) runs at ~120M updates/s and gathers at
    ~148M lookups/s — both flat (docs/SCALE.md) — and a scatter-based
    CSR transpose product additionally pays sort/duplicate handling
    (measured 6.7x slower end-to-end on the d=2M solve). ELLPACK turns
    the transpose product into the same gather shape as the forward
    product by keeping a second, column-major copy of the nnz:

    - row-major: ``vals_r[kb, n, kr]`` + in-block column ids
      ``col_local_r`` — matvec gathers the block's coefficient slice and
      sums over the fixed kr axis; block partials sum (psum when the
      leading axis is sharded).
    - col-major: ``vals_c[kb, block, kc]`` + row ids ``row_ids_c`` —
      rmatvec gathers the (replicated) residual vector and sums over kc,
      landing directly in the block's own coefficient slice.

    Padding entries carry value 0 and index 0. Padding waste is bounded by
    the max row/column degree within a block; heavy-tailed degree
    distributions should bucket columns by degree before blocking (same
    recipe as the random-effect size buckets).
    """

    vals_r: Array  # f[kb, n, kr]
    col_local_r: Array  # i32[kb, n, kr]
    vals_c: Array  # f[kb, block, kc]
    row_ids_c: Array  # i32[kb, block, kc]
    n_rows: int
    n_features: int  # padded: kb * block_size
    block_size: int

    @property
    def num_blocks(self) -> int:
        return self.vals_r.shape[0]

    @property
    def shape(self) -> Tuple[int, int]:
        return (self.n_rows, self.n_features)

    @property
    def num_features(self) -> int:
        return self.n_features

    def _gather_coef(self, v: Array) -> Array:
        """[kb, n, kr] coefficient gather. A single flat gather with
        per-block offsets folded into the indices — a vmapped/batched
        gather lowers ~9x slower on TPU (measured: 95 ms vs 10.7 ms for
        12M lookups)."""
        # Index arithmetic must not wrap: beyond 2^31 coefficients the
        # i32 block offsets overflow, so promote to i64 (n_features is
        # static, so the choice costs nothing below the threshold). With
        # jax_enable_x64 off, an int64 request silently downgrades to
        # int32 — fail loudly rather than gather from wrapped indices.
        if self.n_features > np.iinfo(np.int32).max:
            if not jax.config.jax_enable_x64:
                raise ValueError(
                    f"n_features={self.n_features} needs int64 gather "
                    "indices; enable jax_enable_x64 (or shard into more "
                    "column blocks)")
            idx_dtype = jnp.int64
        else:
            idx_dtype = self.col_local_r.dtype
        offs = (jnp.arange(self.num_blocks, dtype=idx_dtype)
                * self.block_size)[:, None, None]
        return v[self.col_local_r.astype(idx_dtype) + offs]

    # Single-block (single-device) calls strip the leading block axis:
    # a unit batch dim makes the gather+multiply+axis-reduce lower 4-6x
    # slower on TPU (measured: 87 ms vs 15 ms matvec, 324 ms vs 77 ms
    # rmatvec at 12M nnz). The multi-block 3-D form is kept for the
    # mesh-sharded path, where the leading axis is the sharding axis.

    def matvec(self, v: Array) -> Array:
        if self.num_blocks == 1:
            gath = v[self.col_local_r[0]]  # [n, kr]
            return jnp.sum(self.vals_r[0] * gath, axis=-1)
        gath = self._gather_coef(v)  # [kb, n, kr]
        return jnp.einsum("bnk,bnk->n", self.vals_r, gath)

    def rmatvec(self, u: Array) -> Array:
        if self.num_blocks == 1:
            gath = u[self.row_ids_c[0]]  # [block, kc]
            return jnp.sum(self.vals_c[0] * gath, axis=-1)
        gath = u[self.row_ids_c]  # [kb, block, kc]
        return jnp.einsum("bck,bck->bc", self.vals_c, gath).reshape(-1)

    def row_sq_matvec(self, v: Array) -> Array:
        if self.num_blocks == 1:
            gath = v[self.col_local_r[0]]
            return jnp.sum(self.vals_r[0] * self.vals_r[0] * gath, axis=-1)
        gath = self._gather_coef(v)
        return jnp.einsum("bnk,bnk,bnk->n", self.vals_r, self.vals_r, gath)

    def sq_rmatvec(self, u: Array) -> Array:
        if self.num_blocks == 1:
            gath = u[self.row_ids_c[0]]
            return jnp.sum(self.vals_c[0] * self.vals_c[0] * gath, axis=-1)
        gath = u[self.row_ids_c]
        return jnp.einsum("bck,bck,bck->bc", self.vals_c, self.vals_c,
                          gath).reshape(-1)

    def tree_flatten(self):
        return (self.vals_r, self.col_local_r, self.vals_c,
                self.row_ids_c), (self.n_rows, self.n_features,
                                  self.block_size)

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children, *aux)


def _ell_pack(ids: np.ndarray, minlength: int):
    """For sorted ids, return (position-within-run, max run length)."""
    counts = np.bincount(ids, minlength=minlength)
    width = int(counts.max()) if len(ids) else 1
    starts = np.concatenate([[0], np.cumsum(counts)[:-1]])
    pos = np.arange(len(ids)) - np.repeat(starts, counts)
    return pos, max(width, 1)


def blocked_ell_from_arrays(rows, cols, vals, n_rows: int, n_cols: int,
                            num_blocks: int = 1,
                            dtype=jnp.float32) -> BlockedEllFeatures:
    """Build the dual-ELL layout from COO triplets (host-side ingest)."""
    rows = np.asarray(rows, np.int64)
    cols = np.asarray(cols, np.int64)
    vals = np.asarray(vals)
    block = -(-n_cols // num_blocks)
    owner = cols // block
    col_local = (cols - owner * block).astype(np.int64)

    # Row-major copy: sort by (owner, row), place at per-run positions.
    order_r = np.lexsort((rows, owner))
    run_ids = owner[order_r] * n_rows + rows[order_r]
    pos_r, kr = _ell_pack(run_ids, num_blocks * n_rows)
    vals_r = np.zeros((num_blocks, n_rows, kr), vals.dtype)
    col_r = np.zeros((num_blocks, n_rows, kr), np.int32)
    vals_r[owner[order_r], rows[order_r], pos_r] = vals[order_r]
    col_r[owner[order_r], rows[order_r], pos_r] = col_local[order_r]

    # Col-major copy: sort by global column, place at per-run positions.
    order_c = np.argsort(cols, kind="stable")
    pos_c, kc = _ell_pack(cols[order_c], num_blocks * block)
    vals_c = np.zeros((num_blocks, block, kc), vals.dtype)
    row_c = np.zeros((num_blocks, block, kc), np.int32)
    vals_c[owner[order_c], col_local[order_c], pos_c] = vals[order_c]
    row_c[owner[order_c], col_local[order_c], pos_c] = rows[order_c]

    return BlockedEllFeatures(
        vals_r=jnp.asarray(vals_r, dtype),
        col_local_r=jnp.asarray(col_r),
        vals_c=jnp.asarray(vals_c, dtype),
        row_ids_c=jnp.asarray(row_c),
        n_rows=int(n_rows),
        n_features=int(num_blocks * block),
        block_size=int(block),
    )


def blocked_ell_from_scipy(mat, num_blocks: int = 1,
                           dtype=jnp.float32) -> BlockedEllFeatures:
    coo = mat.tocoo()
    return blocked_ell_from_arrays(coo.row, coo.col, coo.data,
                                   coo.shape[0], coo.shape[1],
                                   num_blocks=num_blocks, dtype=dtype)


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass(frozen=True)
class BucketedEllFeatures:
    """Degree-bucketed dual ELLPACK — the single-device layout for LARGE
    sparse problems (d in the millions), superseding the flat-width
    ``BlockedEllFeatures`` when the degree distribution has any spread.

    Measured law of this chip (TPU v5e, see docs/SCALE.md): random-access
    lookups run at ~148M elem/s FLAT — independent of gather-table size
    (1 MB or 8 MB), index count, index sortedness, and whether the gather
    is issued as one op or many independent ops (XLA does not overlap
    them). A sparse product's cost is therefore simply

        time ≈ (stored slots) / 148M/s

    so the ONLY lever is slot count. A flat ELL pads every row (column)
    to the max degree; with a Poisson(6) degree distribution that is
    3.3x the true nnz. This layout instead sorts rows/columns by degree,
    partitions them into <= max_groups width classes (optimal split by
    dynamic programming over the degree histogram), and pads only within
    a class — slot count approaches nnz, and both products stay
    gather + fixed-width-reduction with NO scatter:

    - matvec: per row-group, gather w at the group's column ids and
      reduce over the group width; concatenate group outputs (packed,
      degree-sorted row order) and un-permute with one [n]-sized gather.
    - rmatvec: symmetric on the column side, un-permute with one
      [d]-sized gather.

    The packed vector carries one extra zero slot at the end; rows
    (columns) with degree 0 map there.
    """

    row_vals: Tuple[Array, ...]  # each f[nr_g, w_g]
    row_cols: Tuple[Array, ...]  # each i32[nr_g, w_g] global col ids
    row_inv: Array  # i32[n_rows] -> position in packed row outputs
    col_vals: Tuple[Array, ...]  # each f[nc_g, w_g]
    col_rows: Tuple[Array, ...]  # each i32[nc_g, w_g] row ids
    col_inv: Array  # i32[n_features] -> position in packed col outputs
    n_rows: int
    n_features: int

    @property
    def shape(self) -> Tuple[int, int]:
        return (self.n_rows, self.n_features)

    @property
    def num_features(self) -> int:
        return self.n_features

    @property
    def num_slots(self) -> int:
        return (sum(v.size for v in self.row_vals)
                + sum(v.size for v in self.col_vals))

    @staticmethod
    def _apply(vals, idx_arrays, table, inv, square: bool):
        parts = []
        for v, ix in zip(vals, idx_arrays):
            g = table[ix]
            parts.append(jnp.sum((v * v if square else v) * g, axis=-1))
        parts.append(jnp.zeros((1,), table.dtype))  # degree-0 slot
        packed = jnp.concatenate(parts)
        return packed[inv]

    def matvec(self, v: Array) -> Array:
        return self._apply(self.row_vals, self.row_cols, v, self.row_inv,
                           square=False)

    def rmatvec(self, u: Array) -> Array:
        return self._apply(self.col_vals, self.col_rows, u, self.col_inv,
                           square=False)

    def row_sq_matvec(self, v: Array) -> Array:
        return self._apply(self.row_vals, self.row_cols, v, self.row_inv,
                           square=True)

    def sq_rmatvec(self, u: Array) -> Array:
        return self._apply(self.col_vals, self.col_rows, u, self.col_inv,
                           square=True)

    def tree_flatten(self):
        return ((self.row_vals, self.row_cols, self.row_inv,
                 self.col_vals, self.col_rows, self.col_inv),
                (self.n_rows, self.n_features))

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children, *aux)


def _degree_groups(degrees: np.ndarray, max_groups: int):
    """Partition degree-sorted entities into <= max_groups width classes
    minimizing total padded slots: DP over the distinct-degree histogram
    (group cost = member count x max degree in group). Returns a list of
    (width, sorted_entity_ids) with width > 0, descending."""
    nz = degrees > 0
    if not nz.any():
        return []
    distinct, counts = np.unique(degrees[nz], return_counts=True)
    distinct, counts = distinct[::-1], counts[::-1]  # descending degree
    k = len(distinct)
    if k > 512:  # compress the DP to candidate boundaries by mass
        keep = np.unique(np.concatenate(
            [[0, k - 1], np.searchsorted(
                np.cumsum(counts), np.linspace(0, counts.sum(), 511))]))
        keep = keep[keep < k]
        merged_counts = np.add.reduceat(counts, keep)
        distinct, counts = distinct[keep], merged_counts
        k = len(distinct)
    g = min(max_groups, k)
    csum = np.concatenate([[0], np.cumsum(counts)])
    inf = np.inf
    cost = np.full((g + 1, k + 1), inf)
    back = np.zeros((g + 1, k + 1), np.int64)
    cost[0, 0] = 0.0
    for gi in range(1, g + 1):
        for j in range(1, k + 1):
            # group covers distinct[i..j), width = distinct[i]
            prev = cost[gi - 1, :j]
            cand = prev + (csum[j] - csum[:j]) * distinct[:j]
            i = int(np.argmin(cand))
            cost[gi, j], back[gi, j] = cand[i], i
    # fewer groups can never help but handle k < max_groups
    bounds = []
    j = k
    for gi in range(g, 0, -1):
        i = back[gi, j]
        bounds.append((i, j))
        j = i
    bounds.reverse()

    order = np.argsort(-degrees, kind="stable")  # degree-desc entity ids
    order = order[degrees[order] > 0]
    out = []
    # map distinct-degree ranges back to entity index ranges
    ent_csum = 0
    for i, j in bounds:
        cnt = int(csum[j] - csum[i])
        ids = order[ent_csum:ent_csum + cnt]
        out.append((int(distinct[i]), ids))
        ent_csum += cnt
    return out


def _degree_bucketed_pack(major, vals, nmaj: int, max_groups: int):
    """Shared degree-bucketed ELL packing core (both the gather and the
    sort-permute layouts build on it — the parity tests assert identical
    slot counts, so there must be exactly ONE copy of this algorithm).
    ELL-packs along `major`, grouped by degree; only GROUPING by major
    is needed (slot order within an entity's run is irrelevant to the
    fixed-width reduction), so a single-key stable sort suffices.
    Returns (groups_iter, inv): groups_iter YIELDS one
    (width, ids, sl, mask, nv) at a time — per-group intermediates are
    ~100s of MB at the d=2M bench shape, so they must stream, not
    accumulate — where sl are original nnz indices laid into the
    [len(ids), width] grid and nv the masked values; inv is the
    entity -> packed-position map (degree-0 entities map to the
    trailing zero slot)."""
    deg = np.bincount(major, minlength=nmaj)
    order = np.argsort(major, kind="stable")
    starts = np.concatenate([[0], np.cumsum(deg)[:-1]])
    groups = _degree_groups(deg, max_groups)
    inv = np.full(nmaj, -1, np.int64)
    ent_off = 0
    for _, ids in groups:
        inv[ids] = ent_off + np.arange(len(ids))
        ent_off += len(ids)
    inv[inv < 0] = ent_off  # degree-0 entities -> trailing zero slot

    def gen():
        for width, ids in groups:
            pos = starts[ids][:, None] + np.arange(width)[None, :]
            mask = np.arange(width)[None, :] < deg[ids][:, None]
            sl = order[np.minimum(pos, len(order) - 1)]
            nv = np.where(mask, vals[sl], 0).astype(vals.dtype)
            yield width, ids, sl, mask, nv

    return gen(), jnp.asarray(inv.astype(np.int32))


def bucketed_ell_from_arrays(rows, cols, vals, n_rows: int, n_cols: int,
                             max_groups: int = 8,
                             dtype=jnp.float32) -> BucketedEllFeatures:
    """Build the degree-bucketed dual-ELL layout from COO triplets."""
    rows = np.asarray(rows, np.int64)
    cols = np.asarray(cols, np.int64)
    vals = np.asarray(vals)
    if n_cols > np.iinfo(np.int32).max or n_rows > np.iinfo(np.int32).max:
        raise ValueError("bucketed ELL uses int32 ids; shard the problem "
                         "into column blocks past 2^31")

    def pack(major, minor, nmaj):
        packed, inv = _degree_bucketed_pack(major, vals, nmaj, max_groups)
        vlist, ilist = [], []
        for _, _, sl, mask, nv in packed:  # single streaming pass
            vlist.append(jnp.asarray(nv, dtype))
            ilist.append(
                jnp.asarray(np.where(mask, minor[sl], 0).astype(np.int32)))
        return tuple(vlist), tuple(ilist), inv

    rv, rc, rinv = pack(rows, cols, n_rows)
    cv, cr, cinv = pack(cols, rows, n_cols)
    return BucketedEllFeatures(
        row_vals=rv, row_cols=rc, row_inv=rinv,
        col_vals=cv, col_rows=cr, col_inv=cinv,
        n_rows=int(n_rows), n_features=int(n_cols))


def bucketed_ell_from_scipy(mat, max_groups: int = 8,
                            dtype=jnp.float32) -> BucketedEllFeatures:
    coo = mat.tocoo()
    return bucketed_ell_from_arrays(coo.row, coo.col, coo.data,
                                    coo.shape[0], coo.shape[1],
                                    max_groups=max_groups, dtype=dtype)


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass(frozen=True)
class SortPermuteEllFeatures:
    """Dual degree-bucketed ELL whose cross-order data movement is a
    KEY-SORT instead of a gather — the sort-permutation alternative to
    the random-access wall (docs/SCALE.md §Attacking the gather wall).

    The dual-ELL iteration (``BucketedEllFeatures``) pays one
    random-access lookup per stored slot per pass (~115-148 M lookups/s
    flat on TPU v5e), because each pass gathers an m-sized operand in
    the other order's arbitrary slot order. But the two slot orders are
    FIXED at layout-build time, so moving values between them is a
    fixed bijection — and a known permutation can be applied by
    ``lax.sort`` over precomputed i32 keys carrying the f32 payload:
    sequential-access sorting-network machinery, no random access of
    the large operand at all. Per pass, the only remaining wall-rate
    accesses are ENTITY-sized (d or n lookups), not slot-sized:

    - matvec:  w[col_owner] (d-sized gather) broadcast over each
      column's ELL run, x vals (col order, pads hold 0) -> flat [P] ->
      sort by keys_c2r -> row order -> fixed-width row sums ->
      un-permute ([n] gather).
    - rmatvec: u[row_owner] (n-sized) broadcast, x vals (row order) ->
      sort by keys_r2c -> col order -> fixed-width column sums ->
      un-permute ([d] gather).

    Win condition (measured by dev_scripts/sort_primitives.py): a
    P~12.4M (i32, f32) key-sort in S ms makes the iteration
    ~ 2S + ~40 ms vs the gather layout's ~187 ms at the d=2M bench
    shape — 2x at S ~ 25 ms, break-even at S ~ 70 ms. This class is the
    complete, parity-tested implementation either way; whether it
    replaces the gather layout is a one-number chip decision.

    Both slot spaces are padded to the same length P; the key arrays
    are permutations of [0, P) mapping source slot -> destination slot
    (pad slots map onto pad slots, and padded values are 0 on entry).
    """

    row_vals: Tuple[Array, ...]  # f[nr_g, w_g], row-ELL slot order
    row_owner: Tuple[Array, ...]  # i32[nr_g] row id of each packed entity
    row_inv: Array  # i32[n_rows] -> packed row-entity position
    col_vals: Tuple[Array, ...]  # f[nc_g, w_g], col-ELL slot order
    col_owner: Tuple[Array, ...]  # i32[nc_g] col id of each packed entity
    col_inv: Array  # i32[n_features] -> packed col-entity position
    keys_c2r: Array  # i32[P]: col-slot position -> row-slot position
    keys_r2c: Array  # i32[P]: row-slot position -> col-slot position
    n_rows: int
    n_features: int

    @property
    def shape(self) -> Tuple[int, int]:
        return (self.n_rows, self.n_features)

    @property
    def num_features(self) -> int:
        return self.n_features

    @property
    def num_slots(self) -> int:
        return (sum(v.size for v in self.row_vals)
                + sum(v.size for v in self.col_vals))

    @property
    def sort_domain(self) -> int:
        return self.keys_c2r.shape[0]

    def _permuted(self, src_vals, src_owner, table, keys, square: bool):
        """Expand table (entity space) over the source ELL runs, weight
        by the source-order values, and key-sort the flat payload into
        DESTINATION slot order. The sort's key output is the iota (keys
        are a permutation), so position j of the payload output holds
        the source slot whose key == j."""
        p = keys.shape[0]
        parts = []
        for v, own in zip(src_vals, src_owner):
            vv = v * v if square else v
            parts.append((table[own][:, None] * vv).reshape(-1))
        flat = jnp.concatenate(parts) if parts else jnp.zeros(
            (0,), table.dtype)
        flat = jnp.concatenate(
            [flat, jnp.zeros((p - flat.shape[0],), table.dtype)])
        _, moved = jax.lax.sort((keys, flat), num_keys=1)
        return moved

    @staticmethod
    def _reduce(moved, dst_vals_shapes, inv, dtype):
        """Fixed-width sums over the destination side's ELL runs, then
        the [entities]-sized inverse-permutation gather."""
        parts, off = [], 0
        for ng, wg in dst_vals_shapes:
            seg = jax.lax.dynamic_slice_in_dim(moved, off, ng * wg)
            parts.append(seg.reshape(ng, wg).sum(axis=-1))
            off += ng * wg
        parts.append(jnp.zeros((1,), dtype))  # degree-0 entities
        return jnp.concatenate(parts)[inv]

    def matvec(self, v: Array) -> Array:
        moved = self._permuted(self.col_vals, self.col_owner, v,
                               self.keys_c2r, square=False)
        return self._reduce(moved, [a.shape for a in self.row_vals],
                            self.row_inv, v.dtype)

    def rmatvec(self, u: Array) -> Array:
        moved = self._permuted(self.row_vals, self.row_owner, u,
                               self.keys_r2c, square=False)
        return self._reduce(moved, [a.shape for a in self.col_vals],
                            self.col_inv, u.dtype)

    def row_sq_matvec(self, v: Array) -> Array:
        moved = self._permuted(self.col_vals, self.col_owner, v,
                               self.keys_c2r, square=True)
        return self._reduce(moved, [a.shape for a in self.row_vals],
                            self.row_inv, v.dtype)

    def sq_rmatvec(self, u: Array) -> Array:
        moved = self._permuted(self.row_vals, self.row_owner, u,
                               self.keys_r2c, square=True)
        return self._reduce(moved, [a.shape for a in self.col_vals],
                            self.col_inv, u.dtype)

    def tree_flatten(self):
        return ((self.row_vals, self.row_owner, self.row_inv,
                 self.col_vals, self.col_owner, self.col_inv,
                 self.keys_c2r, self.keys_r2c),
                (self.n_rows, self.n_features))

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children, *aux)


def sort_permute_ell_from_arrays(
        rows, cols, vals, n_rows: int, n_cols: int, max_groups: int = 8,
        dtype=jnp.float32) -> SortPermuteEllFeatures:
    """Build the sort-permutation dual-ELL layout from COO triplets."""
    rows = np.asarray(rows, np.int64)
    cols = np.asarray(cols, np.int64)
    vals = np.asarray(vals)
    if n_cols > np.iinfo(np.int32).max or n_rows > np.iinfo(np.int32).max:
        raise ValueError("sort-permute ELL uses int32 ids; shard the "
                         "problem into column blocks past 2^31")
    nnz = len(vals)

    def pack(major, nmaj):
        """Like bucketed_ell's pack (same _degree_bucketed_pack core), but returns
        each packed entity's major id (owner) and each original nnz's
        flat slot position in this side's packed [P_side] space instead
        of the minor-id arrays (the sort keys replace them)."""
        packed, inv = _degree_bucketed_pack(major, vals, nmaj, max_groups)
        vlist, olist = [], []
        slot_of = np.empty(nnz, np.int64)
        slot_off = 0
        for width, ids, sl, mask, nv in packed:
            vlist.append(jnp.asarray(nv, dtype))
            olist.append(jnp.asarray(ids.astype(np.int32)))
            flat_pos = (slot_off + np.arange(len(ids))[:, None] * width
                        + np.arange(width)[None, :])
            slot_of[sl[mask]] = flat_pos[mask]
            slot_off += len(ids) * width
        return tuple(vlist), tuple(olist), inv, slot_of, slot_off

    rv, ro, rinv, row_slot, p_rows = pack(rows, n_rows)
    cv, co, cinv, col_slot, p_cols = pack(cols, n_cols)

    # One shared sort domain: true nnz map slot<->slot; the remaining
    # (pad / extension) positions of each side pair up in order, so the
    # keys are full permutations of [0, P).
    p = max(p_rows, p_cols)
    if p > np.iinfo(np.int32).max:
        raise ValueError(
            f"sort-permute ELL keys are int32 but the padded slot space "
            f"has {p} positions (> 2^31-1); shard the problem into "
            f"column blocks first (parallel/distributed.py)")
    c2r = np.full(p, -1, np.int64)
    c2r[col_slot] = row_slot
    free_src = np.setdiff1d(np.arange(p), col_slot, assume_unique=False)
    free_dst = np.setdiff1d(np.arange(p), row_slot, assume_unique=False)
    c2r[free_src] = free_dst
    r2c = np.empty(p, np.int64)
    r2c[c2r] = np.arange(p)
    return SortPermuteEllFeatures(
        row_vals=rv, row_owner=ro, row_inv=rinv,
        col_vals=cv, col_owner=co, col_inv=cinv,
        keys_c2r=jnp.asarray(c2r.astype(np.int32)),
        keys_r2c=jnp.asarray(r2c.astype(np.int32)),
        n_rows=int(n_rows), n_features=int(n_cols))


def sort_permute_ell_from_scipy(mat, max_groups: int = 8,
                                dtype=jnp.float32) -> SortPermuteEllFeatures:
    coo = mat.tocoo()
    return sort_permute_ell_from_arrays(coo.row, coo.col, coo.data,
                                        coo.shape[0], coo.shape[1],
                                        max_groups=max_groups, dtype=dtype)




#: The widest table a coded slot's dictionary may be padded to
#: (``SlotMajorEllFeatures``, coded slots): a slot that names at most this
#: many distinct columns over all rows is read by code, its dictionary padded
#: to the smallest power of two that holds it (its CLASS, at least the 128
#: lanes of a vector register), so that the compiled products depend on which
#: slots are coded and on their classes, never on their counts. A coded slot
#: pays for its class. Measured on a TPU v5e, one slot of 9,168,123 rows
#: (PERF.md section 5, PR 39; ``dev_scripts/sparse_products_probe.py <rows>
#: codes`` reads it again), ``out + x * table[code]`` bitwise the gather's
#: result at every class: by gather from ``f32[1000001]`` 61.9 ms; by
#: ``_lookup`` 0.95 ms at 128 entries, 0.91 at 256, 1.06 at 512, 1.18 at
#: 1,024, 1.54 at 2,048, 2.14 at 4,096, 3.59 at 8,192, 6.11 at 16,384, 11.2 at
#: 32,768, 21.8 at 65,536 (0.9 ms of passes over n-vectors + 0.041 ms a group
#: of 128 entries: 4.5 ns a vector register of codes and group). Alone, every
#: class a ``uint16`` code can name costs under half a slot by gather; a
#: class is admitted where it has also been read IN A JOB (PR 36: a form
#: right alone can be wrong there), and 16,384 is the widest that has: the
#: cell ``sparse-lr.fit`` codes 32 slots in the classes 128 to 16,384, 0.73 ms
#: a slot in its ``cd_block`` (its next fields name 89,000 columns and more).
#: 32,768 and 65,536 wait for a matrix that has such a slot.
CODED_SLOT_TOP_CLASS = 16384
_CODE_DTYPE = jnp.uint16  # the narrowest that holds 0 .. CODED_SLOT_TOP_CLASS - 1
_LANES = 128  # a vector register's lanes: a group of a table, the least class
_CODE_ALIGN = 4096  # a slot's codes are whole tiles of the narrow type
_LOOKUP_BLOCK_ROWS = 512  # rows of 128 codes a grid step of ``_lookup``
_LOOKUP_GROUPS = 8  # groups of the table a step of the kernel's loop


def _slot_class(distinct: int) -> int:
    """The table width of a slot that names ``distinct`` columns: the
    smallest power of two that holds them, at least one group of lanes."""
    return max(_LANES, 1 << (int(distinct) - 1).bit_length())


def _runs(coded: Tuple[int, ...], k: int):
    """The k slots in slot order as runs of one kind: ``(first, stop, at)``,
    ``at`` the place in ``coded`` of a coded run's first slot, None for a
    run of gathered slots."""
    at = {s: j for j, s in enumerate(coded)}
    for is_coded, run in itertools.groupby(range(k), key=at.__contains__):
        run = list(run)
        yield run[0], run[-1] + 1, at[run[0]] if is_coded else None


def _code_stride(n_rows: int) -> int:
    """The codes a coded slot stores: ``n_rows`` rounded up to whole grid
    steps of ``_lookup`` (to whole tiles where one step holds them all)."""
    step = _LOOKUP_BLOCK_ROWS * _LANES
    align = step if n_rows > step else _CODE_ALIGN
    return -(-n_rows // align) * align


def _off_tpu() -> bool:
    """Whether ``_lookup``'s kernel is interpreted (asked while tracing)."""
    return jax.default_backend() != "tpu"


def _lookup_kernel(j_ref, code_ref, table_ref, out_ref, *, groups: int):
    """A block of codes against the whole table, which lies along the
    lanes, 128 entries a group: one lane-local dynamic gather by the code's
    low seven bits and one select on its high bits a group, ``groups`` of
    them a step of the loop (a step takes ~100 ns however little it holds:
    PERF.md section 6, PR 39)."""
    del j_ref  # the block's place, read by the index map
    code = code_ref[...].astype(jnp.int32)
    lane, high = code & (_LANES - 1), code >> 7

    def some_groups(i, found):
        for g in range(groups):
            g = i.astype(jnp.int32) * groups + g
            row = jnp.broadcast_to(table_ref[pl.ds(g, 1), :], code.shape)
            found = jnp.where(
                high == g, jnp.take_along_axis(row, lane, axis=1), found)
        return found

    out_ref[...] = lax.fori_loop(
        0, table_ref.shape[0] // groups, some_groups,
        jnp.zeros(code.shape, out_ref.dtype))


@functools.partial(jax.jit, static_argnames=("interpret", "block", "groups"))
def _lookup_call(j, codes, table, interpret: bool,
                 block: int = _LOOKUP_BLOCK_ROWS,
                 groups: int = _LOOKUP_GROUPS):
    """One compiled program a class, whichever slot (traced once, so that
    a product run eagerly does not compile its kernels anew a call).
    ``block`` and ``groups`` are the program's two constants; the probe
    that chose them (``dev_scripts/sparse_products_probe.py``) passes
    others."""
    rows = codes.shape[1]
    block = min(block, rows)
    table = table.reshape(-1, _LANES)
    return pl.pallas_call(
        functools.partial(_lookup_kernel,
                          groups=min(groups, table.shape[0])),
        out_shape=jax.ShapeDtypeStruct((rows, _LANES), table.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(rows // block,),
            in_specs=[
                pl.BlockSpec((None, block, _LANES), lambda i, j: (j[0], i, 0)),
                pl.BlockSpec(table.shape, lambda i, j: (0, 0))],
            out_specs=pl.BlockSpec((block, _LANES), lambda i, j: (i, 0))),
        name="coded_slot_lookup",
        interpret=interpret,
    )(j, codes, table)


@jax.custom_jvp
def _lookup(codes: Array, table: Array, j: Array) -> Array:
    """``table[codes[j[0]]]`` by the vector unit's lane-local dynamic
    gather, in a Pallas kernel (interpreted off the TPU): ``codes`` ``[m,
    rows, 128]``, read in place, ``table`` a whole number of groups of 128
    entries, resident in VMEM (64 KB at the top class). Exact: the entry
    the code names, or 0 for a code past the table. Linear in ``table``:
    differentiated, it is XLA's gather by code, whose transpose is the
    scatter-add of the cotangent into the table (what a gathered slot
    differentiates to). Called inside a staged loop it differentiates once
    and not twice (``SlotMajorEllFeatures`` says why), so ``_by_row`` calls
    it slot by slot and in no loop of its own."""
    return _lookup_call(j, codes, table, interpret=_off_tpu())


@_lookup.defjvp
def _lookup_jvp(primals, tangents):
    codes, table, j = primals
    return _lookup(codes, table, j), tangents[1].at[
        codes[j[0]].astype(jnp.int32)].get(mode="promise_in_bounds")


@functools.partial(jax.jit, static_argnames=("fenced",))
def _add_term(out: Array, x: Array, found: Array, fenced: bool) -> Array:
    """``out + x * found``: a coded slot's term of every row's sum, after
    the terms before it. ``fenced`` (off the TPU), the sum so far passes an
    optimization barrier first, so that the multiply-add is compiled alone,
    as a step of the gathered loop is, and rounds as that step rounds. The
    CPU backend contracts a multiply and the add it feeds into one rounding
    where it finds them in one fusion, and a run of terms fused into ONE
    expression it is free to contract otherwise than the loop's one term a
    step: unfenced, a sum with coded slots differed from the all-gathered
    one by a rounding in a row in sixteen, and two fits that should agree
    to 3e-7 ended 3.8e-4 apart. The TPU rounds every product and every sum
    (bitwise equal unfenced, PERF.md section 6, PR 39), and there a run of
    terms stays one pass over the n-vectors."""
    if fenced:
        out = lax.optimization_barrier(out)
    return out + x * found.reshape(-1)[:out.shape[0]]


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass(frozen=True)
class SlotMajorEllFeatures:
    """Sparse rows of one stored width ``k``, slot by slot: slot ``s`` of
    every row lies end to end, ``cols[s * n + i]`` / ``vals[s * n + i]``
    being row ``i``'s ``s``-th stored entry (ELLPACK, transposed and flat).
    A padded slot holds value 0 at column 0 and adds nothing to any product.

    Why this shape (TPU v5e, compiled and timed at 9.2M rows x 40 slots,
    PERF.md section 5): an index operation costs ~6.7 ns an index, so a
    product should be ONE a stored slot: ``matvec`` is a loop over the k
    slots of one gather of n values of ``v``, ``rmatvec`` a loop of one
    scatter-add of n updates into ``f[d]``. The loops walk flat vectors, so
    neither product has a temporary larger than an n-vector, where the same
    products over ``[n, k]`` arrays first copy both into a layout padded
    from k to 128 lanes (9.4 GB at that size). Columns that collide cost the
    scatter-add 1% at that log's skew (30% if EVERY update lands on one
    address), so the scatter side treats no column apart.

    **Coded slots** (PR 36, width classes since PR 39; the row-wise products
    only). Where rows come field by field, a slot names few columns: the
    intercept's one, a binned field's 64, a categorical field's few
    thousand. Such a slot (at most ``CODED_SLOT_TOP_CLASS`` distinct columns
    over ALL rows, counted where the matrix is built) also carries a code a
    row and a dictionary of its columns, padded to the slot's class
    (``_slot_class``), and ``matvec`` / ``row_sq_matvec`` read it as
    ``lookup(code, v[dictionary])``: the vector unit's lane gather against
    the slot's table, held in VMEM, in place of n gathers from ``v``, and
    the number looked up is the number the gather fetches. Slots are
    visited in slot order, coded or not, so a row's sum has the same terms
    in the same order; a matrix with no such slot runs the one loop it ran
    before. ``cols`` and ``vals`` stay whole: the column-wise products,
    ``to_csr`` and everything that unrolls the layout read them and ignore
    the codes.

    Under ``jax.vmap`` over the vector, and differentiated (``jax.grad``,
    ``jax.jvp``, one over the other as TRON's Hessian-vector product is),
    the row-wise products behave as the gathered ones do. ONE RESTRICTION:
    a staged loop (``lax.scan``, ``fori_loop``) with a row-wise product in
    its body can be differentiated THROUGH once, either way, but not twice:
    JAX keeps no derivative rule of a function's own through a loop's
    partial evaluation, so the second derivative meets the kernel itself
    and raises ``NotImplementedError``. Derivatives taken INSIDE a loop's
    body are not through the loop and are fine at any order: TRON's
    conjugate-gradient loop takes its Hessian-vector products so.
    """

    cols: Array  # i32[k * n], slot-major
    vals: Array  # f[k * n]
    n_rows: int
    n_features: int
    # what the chooser counted, where it built this matrix (static, aux data)
    counts: Optional["LayoutCounts"] = None
    # the coded slots' codes, ``[len(coded), _code_stride(n) / 128, 128]`` in
    # the order of ``coded``; their dictionaries end to end (ascending, each
    # padded with column 0 to its slot's class); which slots they are
    # (ascending) and each one's class (static, aux data)
    codes: Optional[Array] = None
    dicts: Optional[Array] = None
    coded: Tuple[int, ...] = ()
    classes: Tuple[int, ...] = ()

    @property
    def shape(self) -> Tuple[int, int]:
        return (self.n_rows, self.n_features)

    @property
    def num_features(self) -> int:
        return self.n_features

    @property
    def slots_per_row(self) -> int:
        return self.vals.shape[-1] // max(self.n_rows, 1)

    def _slot(self, s, square: bool, acc):
        n = self.n_rows
        c = lax.dynamic_slice(self.cols, (s * n,), (n,))
        x = lax.dynamic_slice(self.vals, (s * n,), (n,)).astype(acc)
        return c, (x * x if square else x)

    def _by_row(self, v: Array, square: bool) -> Array:
        acc = jnp.promote_types(v.dtype, jnp.float32)
        n, k = self.n_rows, self.slots_per_row

        def gathered(s, out):
            c, x = self._slot(s, square, acc)
            # in bounds by construction (checked where the matrix is built)
            return out + x * v.at[c].get(mode="promise_in_bounds")

        out = jnp.zeros((n,), acc)
        if not self.coded:
            return lax.fori_loop(0, k, gathered, out)
        # every coded slot's table entries: sum(classes) gathers
        tables = v.at[self.dicts].get(mode="promise_in_bounds").astype(acc)
        starts = (0, *itertools.accumulate(self.classes))
        # A loop a run of gathered slots, in slot order. (One loop over all
        # slots with a ``cond`` a slot compiles to a ninth of the text, but
        # a gather inside a conditional's branch reads its indices from
        # HBM, 108 ms a slot against 61.9: PERF.md section 6, PR 36.) The
        # coded slots one by one, each a kernel call of its own class.
        for first, stop, at in _runs(self.coded, k):
            if at is None:
                with jax.named_scope(scopes.FE_MATVEC_GATHERED):
                    out = lax.fori_loop(first, stop, gathered, out)
                continue
            with jax.named_scope(scopes.FE_MATVEC_CODED):
                for j in range(at, at + stop - first):
                    _, x = self._slot(self.coded[j], square, acc)
                    found = _lookup(self.codes,
                                    tables[starts[j]:starts[j + 1]],
                                    jnp.full((1,), j, jnp.int32))
                    out = _add_term(out, x, found, fenced=_off_tpu())
        return out

    def _by_column(self, u: Array, square: bool) -> Array:
        acc = jnp.promote_types(u.dtype, jnp.float32)

        def body(s, out):
            c, x = self._slot(s, square, acc)
            return out.at[c].add(x * u, mode="promise_in_bounds")

        return lax.fori_loop(0, self.slots_per_row, body,
                             jnp.zeros((self.n_features,), acc))

    def matvec(self, v: Array) -> Array:
        with jax.named_scope(scopes.FE_MATVEC):
            return self._by_row(v, square=False)

    def rmatvec(self, u: Array) -> Array:
        with jax.named_scope(scopes.FE_RMATVEC):
            return self._by_column(u, square=False)

    def row_sq_matvec(self, v: Array) -> Array:
        with jax.named_scope(scopes.FE_MATVEC):
            return self._by_row(v, square=True)

    def sq_rmatvec(self, u: Array) -> Array:
        with jax.named_scope(scopes.FE_RMATVEC):
            return self._by_column(u, square=True)

    def to_csr(self) -> CSRFeatures:
        """The same matrix as a flat triplet, in this layout's order (slot
        by slot, so not sorted by row; a padded slot stays a stored value 0
        at column 0): for what reads a matrix entry by entry, such as the
        sharding of a batch over a mesh."""
        rows = jnp.tile(jnp.arange(self.n_rows, dtype=jnp.int32),
                        self.slots_per_row)
        return CSRFeatures(self.vals, self.cols, rows, self.n_rows,
                           self.n_features, self.counts)

    def tree_flatten(self):
        return ((self.cols, self.vals, self.codes, self.dicts),
                (self.n_rows, self.n_features, self.counts, self.coded,
                 self.classes))

    @classmethod
    def tree_unflatten(cls, aux, children):
        cols, vals, codes, dicts = children
        n_rows, n_features, counts, coded, classes = aux
        return cls(cols, vals, n_rows, n_features, counts, codes, dicts,
                   coded, classes)


FeatureMatrix = Union[DenseFeatures, CSRFeatures, BlockedCSRFeatures,
                      BlockedEllFeatures, BucketedEllFeatures,
                      SortPermuteEllFeatures, SlotMajorEllFeatures,
                      KroneckerFeatures]


@dataclasses.dataclass(frozen=True)
class LayoutCounts:
    """What the chooser knows of a sparse matrix given as rows of ``k``
    stored slots, and what it chose. Counted on the device, for a matrix
    born there (``sparse_rows_to_device``) and for one uploaded as a
    triplet (``lay_out_triplet``). Rides on the matrix it describes as
    static pytree data (``counts``)."""

    n_rows: int
    slots_per_row: int  # k: the fullest row's stored entries
    n_features: int
    nnz: int  # stored values that are not 0
    max_col_degree: int  # non-zeros of the fullest column
    layout: str = ""  # the layout chosen
    slots: int = 0  # the slots that layout stores
    coded_slots: int = 0  # of the k slots a row, those read by code
    coded_entries: int = 0  # their tables' entries, each padded to its class


def choose_layout(counts: LayoutCounts) -> str:
    """``"slot_major_ell"`` or ``"csr"`` for a sparse matrix of these
    counts: the fewer index operations a product. An index operation, a
    gather or a scatter-add alike, costs the v5e ~6.7 ns an index whatever
    the table and the order (PERF.md section 5, the cell ``sparse-lr.fit``:
    the row-wise ELL's products read 6.65 / 6.75 ns a slot, the flat
    triplet's 20.2 / 14.2 a non-zero on the same data). The ELL pays one a
    stored slot, padding included; the flat triplet pays two a non-zero (a
    gather and a segment-sum) and stores no padding. The column degrees
    are counted for the gauges and decide nothing: that log's skew costs
    its scatter-add 0.8%."""
    slots = counts.n_rows * counts.slots_per_row
    return "slot_major_ell" if slots <= 2 * counts.nnz else "csr"


@functools.partial(jax.jit, static_argnames=("n_features",))
@jax.named_scope(scopes.FE_LAYOUT)
def _count_rows(cols, vals, n_features: int):
    """nnz, the fullest column's non-zeros and the columns' range: one
    scatter-add over every slot."""
    stored = vals != 0
    deg = jnp.zeros((n_features,), jnp.int32).at[cols].add(
        stored.astype(jnp.int32), mode="drop")
    return (jnp.sum(stored, dtype=jnp.int32), jnp.max(deg), jnp.min(cols),
            jnp.max(cols))


@jax.jit
@jax.named_scope(scopes.FE_LAYOUT)
def _slot_major(a):
    return a.T.reshape(-1)


def _prefix_sums(x):
    """Inclusive prefix sums of a long vector, as ``cumsum`` along rows of
    1,024 and over the rows' totals: the TPU's compiler takes 19 s over
    ``jnp.cumsum`` of ``i32[1000001]`` and 0.4 s over this."""
    side = 1024
    m = x.shape[0]
    if m <= side:
        return jnp.cumsum(x)
    rows = -(-m // side)
    within = jnp.cumsum(
        jnp.pad(x, (0, rows * side - m)).reshape(rows, side), axis=1)
    totals = within[:, -1]
    return (within + (_prefix_sums(totals) - totals)[:, None]).reshape(-1)[:m]


@functools.partial(jax.jit, static_argnames=("n_rows", "n_features"))
@jax.named_scope(scopes.FE_LAYOUT)
def _slot_dictionaries(cols, n_rows: int, n_features: int):
    """Of slot-major ``cols``, slot by slot: the distinct columns over all
    rows (exact; a padded slot's column 0 counts) and the first
    ``CODED_SLOT_TOP_CLASS`` of them ascending, padded with column 0:
    ``i32[k]`` and ``i32[k, CODED_SLOT_TOP_CLASS]``. One scatter of n a
    slot."""
    k = cols.shape[0] // n_rows
    want = jnp.arange(1, CODED_SLOT_TOP_CLASS + 1, dtype=jnp.int32)

    def one(s, carry):
        distinct, dicts = carry
        c = lax.dynamic_slice(cols, (s * n_rows,), (n_rows,))
        upto = _prefix_sums(
            jnp.zeros((n_features,), jnp.int32).at[c].set(1))
        first = jnp.where(want <= upto[-1], jnp.searchsorted(upto, want), 0)
        return (distinct.at[s].set(upto[-1]),
                dicts.at[s].set(first.astype(jnp.int32)))

    return lax.fori_loop(0, k, one, (
        jnp.zeros((k,), jnp.int32),
        jnp.zeros((k, CODED_SLOT_TOP_CLASS), jnp.int32)))


@functools.partial(jax.jit, static_argnames=("n_rows", "n_features"))
@jax.named_scope(scopes.FE_LAYOUT)
def _slot_codes(cols, dicts, distinct, coded, n_rows: int, n_features: int):
    """Row by row the place of the row's column in its slot's dictionary,
    for the slots ``coded`` (``i32[m]``), 128 to a row of the result, and
    those slots' dictionaries: one gather of n a coded slot. The program
    depends on how many slots are coded, not on which or on their counts."""
    stride = _code_stride(n_rows)
    place = jnp.arange(CODED_SLOT_TOP_CLASS, dtype=jnp.int32)

    def one(j, codes):
        s = coded[j]
        c = lax.dynamic_slice(cols, (s * n_rows,), (n_rows,))
        # the padding of a dictionary is no entry: dropped, past the table
        at = jnp.where(place < distinct[s], dicts[s], n_features)
        rank = jnp.zeros((n_features,), jnp.int32).at[at].set(
            place, mode="drop")
        return lax.dynamic_update_slice(
            codes, rank[c].astype(_CODE_DTYPE), (j * stride,))

    codes = lax.fori_loop(0, coded.shape[0], one, jnp.zeros(
        (coded.shape[0] * stride,), _CODE_DTYPE))
    return codes.reshape(coded.shape[0], -1, _LANES), dicts[coded]


@functools.partial(jax.jit, static_argnames=("classes",))
@jax.named_scope(scopes.FE_LAYOUT)
def _cut_to_classes(dicts, classes: Tuple[int, ...]):
    """Row j of ``dicts`` cut to ``classes[j]`` entries, the rows end to
    end."""
    return jnp.concatenate([dicts[j, :width]
                            for j, width in enumerate(classes)])


def _slot_major_ell(cols, vals, counts: "LayoutCounts"
                    ) -> SlotMajorEllFeatures:
    """The slot-major arrays as the matrix the program runs, its small
    slots coded, each in the class its distinct columns fill: which are
    small is counted here, on the device, of which k numbers come back."""
    n, k, d = counts.n_rows, counts.slots_per_row, counts.n_features
    counts = dataclasses.replace(counts, layout="slot_major_ell",
                                 slots=n * k)
    if n * k == 0:
        return SlotMajorEllFeatures(cols, vals, n, d, counts)
    distinct, dicts = _slot_dictionaries(cols, n_rows=n, n_features=d)
    named = np.asarray(jax.device_get(distinct))
    coded = tuple(int(s) for s in np.flatnonzero(
        named <= CODED_SLOT_TOP_CLASS))
    classes = tuple(_slot_class(named[s]) for s in coded)
    counts = dataclasses.replace(counts, coded_slots=len(coded),
                                 coded_entries=sum(classes))
    if not coded:
        return SlotMajorEllFeatures(cols, vals, n, d, counts)
    codes, dicts = _slot_codes(
        cols, dicts, distinct, jax.device_put(np.asarray(coded, np.int32)),
        n_rows=n, n_features=d)
    return SlotMajorEllFeatures(cols, vals, n, d, counts, codes,
                                _cut_to_classes(dicts, classes), coded, classes)


@functools.partial(jax.jit, static_argnames=("nnz",))
@jax.named_scope(scopes.FE_LAYOUT)
def _compact_rows(cols, vals, nnz: int):
    """The stored non-zeros as a row-sorted triplet of exactly ``nnz``."""
    k = cols.shape[1]
    flat = vals.reshape(-1)
    (at,) = jnp.nonzero(flat != 0, size=nnz, fill_value=0)
    return flat[at], cols.reshape(-1)[at], (at // k).astype(jnp.int32)


def sparse_rows_to_device(cols: Array, vals: Array,
                          n_features: int) -> "FeatureMatrix":
    """A sparse matrix that is already on the device, as rows of ``k``
    stored slots (``cols i32[n, k]``, ``vals f[n, k]``: a row's non-zeros
    side by side; a padded slot is value 0 at column 0), to the layout the
    program runs it in. Everything happens on the device: the counts the
    choice needs are one program there, of which four scalars come back,
    and the layout is built there from the two arrays (which are left as
    they are: the caller's).

    There is no layout argument. ``choose_layout`` decides from n, k and
    the non-zeros counted; the result's ``counts`` (``layout_counts``) say
    what was counted and chosen."""
    n, k = (int(v) for v in cols.shape)
    n_features = int(n_features)
    nnz, max_deg, lo, hi = (int(v) for v in jax.device_get(
        _count_rows(cols, vals, n_features)))
    if lo < 0 or hi >= n_features:
        raise ValueError(f"column ids span [{lo}, {hi}]; the matrix has "
                         f"{n_features} columns")
    counts = LayoutCounts(n_rows=n, slots_per_row=k, n_features=n_features,
                          nnz=nnz, max_col_degree=max_deg)
    layout = choose_layout(counts)
    if layout == "slot_major_ell":
        return _slot_major_ell(_slot_major(cols), _slot_major(vals), counts)
    return CSRFeatures(
        *_compact_rows(cols, vals, nnz), n, n_features,
        dataclasses.replace(counts, layout=layout, slots=nnz))


def layout_counts(feats) -> Optional[LayoutCounts]:
    """What the chooser counted and chose for ``feats``; None for a matrix
    it did not build (and for the layouts it never builds)."""
    return getattr(feats, "counts", None)


def csr_from_scipy(mat, n_features: int | None = None, pad_to: int | None = None,
                   dtype=jnp.float32) -> CSRFeatures:
    """Build CSRFeatures from a scipy.sparse matrix (host-side ingest)."""
    coo = mat.tocoo()
    order = np.argsort(coo.row, kind="stable")
    rows = coo.row[order].astype(np.int32)
    cols = coo.col[order].astype(np.int32)
    vals = coo.data[order]
    nnz = len(vals)
    target = pad_to if pad_to is not None else nnz
    if target < nnz:
        raise ValueError(f"pad_to={target} < nnz={nnz}")
    pad = target - nnz
    if pad:
        rows = np.concatenate([rows, np.zeros(pad, np.int32)])
        cols = np.concatenate([cols, np.zeros(pad, np.int32)])
        vals = np.concatenate([vals, np.zeros(pad, vals.dtype)])
    return CSRFeatures(
        values=jnp.asarray(vals, dtype=dtype),
        col_ids=jnp.asarray(cols),
        row_ids=jnp.asarray(rows),
        n_rows=int(mat.shape[0]),
        n_features=int(n_features if n_features is not None else mat.shape[1]),
    )


def padded_csr_arrays(mat, n_rows_pad: int, nnz_pad: int,
                      value_dtype=np.float32):
    """Host-side CSR -> padded expanded-CSR triplet
    ``(values[nnz_pad], col_ids[nnz_pad], row_ids[nnz_pad])`` (numpy).

    The serving engine's featureization step: a request's scipy CSR is
    flattened into the static bucket shape ``(n_rows_pad, nnz_pad)``
    BEFORE upload, so every H2D transfer and every compiled executable
    sees identical shapes. Pad entries carry value 0 at (row 0, col 0) —
    they contribute nothing to any product — and rows in
    [mat.shape[0], n_rows_pad) simply have no entries, so padded rows
    score exactly 0 (CSRFeatures' existing padding convention).
    """
    import scipy.sparse as sp

    csr = mat.tocsr() if sp.issparse(mat) else sp.csr_matrix(mat)
    if csr.shape[0] > n_rows_pad:
        raise ValueError(f"{csr.shape[0]} rows > n_rows_pad={n_rows_pad}")
    if csr.nnz > nnz_pad:
        raise ValueError(f"nnz={csr.nnz} > nnz_pad={nnz_pad}")
    values = np.zeros(nnz_pad, dtype=value_dtype)
    col_ids = np.zeros(nnz_pad, dtype=np.int32)
    row_ids = np.zeros(nnz_pad, dtype=np.int32)
    values[:csr.nnz] = csr.data
    col_ids[:csr.nnz] = csr.indices
    row_ids[:csr.nnz] = np.repeat(
        np.arange(csr.shape[0], dtype=np.int32), np.diff(csr.indptr))
    return values, col_ids, row_ids


@functools.partial(jax.jit, static_argnames=("n_rows", "n_features"))
@jax.named_scope(scopes.FE_LAYOUT)
def _count_triplet(values, col_ids, row_ids, n_rows: int, n_features: int):
    """nnz, the fullest column's non-zeros and the fullest row's stored
    entries, of a flat triplet."""
    stored = (values != 0).astype(jnp.int32)
    deg = jnp.zeros((n_features,), jnp.int32).at[col_ids].add(stored)
    per_row = jnp.zeros((n_rows,), jnp.int32).at[row_ids].add(1)
    return (jnp.sum(stored), jnp.max(deg, initial=0),
            jnp.max(per_row, initial=0))


@functools.partial(jax.jit, static_argnames=("n_rows", "k"))
@jax.named_scope(scopes.FE_LAYOUT)
def _slot_major_of_triplet(values, col_ids, row_ids, n_rows: int, k: int):
    """A row-sorted triplet laid slot-major: a row's ``j``-th entry becomes
    its slot ``j``; the slots a row does not fill hold value 0 at column 0."""
    per_row = jnp.zeros((n_rows,), jnp.int32).at[row_ids].add(1)
    first = jnp.cumsum(per_row) - per_row
    nth = jnp.arange(row_ids.shape[0], dtype=jnp.int32) - first[row_ids]
    at = nth * n_rows + row_ids
    return (jnp.zeros((k * n_rows,), col_ids.dtype).at[at].set(col_ids),
            jnp.zeros((k * n_rows,), values.dtype).at[at].set(values))


def lay_out_triplet(feats: CSRFeatures) -> "FeatureMatrix":
    """A row-sorted triplet that is on the device, nothing padded, to the
    layout ``choose_layout`` picks for it, counted and built there: what
    the host paths end in (``features_to_device`` after its upload, the
    streamed assembly of ``data/shard_cache.py`` after its last piece), so
    that the two hold the same arrays and a matrix born on the device
    (``sparse_rows_to_device``) meets the same chooser."""
    n, d = feats.shape
    nnz, max_deg, k = (int(v) for v in jax.device_get(_count_triplet(
        feats.values, feats.col_ids, feats.row_ids, n_rows=n, n_features=d)))
    counts = LayoutCounts(n_rows=n, slots_per_row=k, n_features=d, nnz=nnz,
                          max_col_degree=max_deg)
    layout = choose_layout(counts)
    if layout == "slot_major_ell":
        return _slot_major_ell(*_slot_major_of_triplet(
            feats.values, feats.col_ids, feats.row_ids, n_rows=n, k=k),
            counts)
    return dataclasses.replace(feats, counts=dataclasses.replace(
        counts, layout=layout, slots=int(feats.values.shape[-1])))


DENSE_DENSITY_THRESHOLD = 0.2


def features_to_device(mat, dtype=jnp.float32,
                       dense_threshold: float = DENSE_DENSITY_THRESHOLD,
                       storage_dtype=None,
                       sparse_layout: "str | None" = None) -> FeatureMatrix:
    """Host feature matrix -> device layout, choosing dense vs sparse by
    density. The single chooser shared by the GLM and GAME ingest paths.

    ``storage_dtype=jnp.bfloat16`` stores DENSE features at half width
    (products accumulate in the solver dtype; ~2x on the
    bandwidth-bound fixed-effect iteration — see DenseFeatures). Sparse
    layouts ignore it (their cost is lookup-count-, not byte-, bound).

    Below the density threshold the sparse layout is ``choose_layout``'s,
    from the uploaded triplet's own counts (``lay_out_triplet``): the
    chooser a matrix born on the device goes through
    (``sparse_rows_to_device``).
    ``sparse_layout`` overrides it by name: ``"csr"``, ``"bucketed_ell"``
    (degree-bucketed dual-ELL: gather-only products at ~2x the memory) or
    ``"sort_permute_ell"`` (cross-order movement as one key-sort per pass;
    see docs/SCALE.md). No chip measurement ranks the named ones against
    the chooser's yet (PERF.md section 7). Use ``blocked_ell_from_scipy``
    directly for the mesh-sharded (column-blocked) variant."""
    import scipy.sparse as sp

    if sparse_layout not in (None, "csr", "bucketed_ell",
                             "sort_permute_ell"):
        # validate up front: a typo'd name must fail loudly even when
        # the density branch would never consult it (dense input)
        raise ValueError(
            f"unknown sparse_layout {sparse_layout!r}: expected "
            "'csr', 'bucketed_ell', or 'sort_permute_ell'")
    from photon_ml_tpu.data.device_feed import chunked_device_put

    dense_dt = storage_dtype if storage_dtype is not None else dtype
    if sp.issparse(mat):
        density = mat.nnz / max(1, mat.shape[0] * mat.shape[1])
        if density >= dense_threshold:
            # Chunked upload: densify + cast per row chunk, double-buffered
            # H2D — never materializes the full dense host copy.
            return DenseFeatures(chunked_device_put(mat, dense_dt))
        if storage_dtype is not None:
            import warnings

            # warnings (not logging): default dedup — diagnostics re-ingest
            # per bootstrap/fitting subset and one line per JOB is enough.
            # The message must be CONSTANT (dedup keys on text), so the
            # varying density stays out of it.
            warnings.warn(
                f"storage_dtype={storage_dtype} ignored: data density is "
                f"below the dense threshold ({dense_threshold:.2f}), which "
                "selects a sparse layout (sparse layouts are "
                "lookup-count-bound, not byte-bound)", stacklevel=2)
        if sparse_layout == "bucketed_ell":
            return bucketed_ell_from_scipy(mat, dtype=dtype)
        if sparse_layout == "sort_permute_ell":
            return sort_permute_ell_from_scipy(mat, dtype=dtype)
        if sparse_layout is None:
            return lay_out_triplet(csr_from_scipy(mat, dtype=dtype))
        return csr_from_scipy(mat, dtype=dtype)
    return DenseFeatures(chunked_device_put(np.asarray(mat), dense_dt))
