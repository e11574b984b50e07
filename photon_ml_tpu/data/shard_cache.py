"""Device-resident shard cache for out-of-core streaming TRAINING.

PRs 1 and 4 built a C-block-decoding, prefetched, chunked-H2D streaming
pipeline that only SCORING used; training still one-shot-materialized the
whole dataset on host and device (`read_game_dataset` ->
`fixed_effect_batch`), capping trainable dataset size at host RAM. This
module is the training-side consumer of that pipeline
(Snap ML's pipelined chunk streaming with a device-resident working set,
PAPERS.md): a `BlockGameStream` is consumed ONCE, batch by batch, and its
rows land on device in one of two regimes —

- **exact assembly** (`assemble_fixed_effect_batch`): each batch's CSR
  slice uploads as it decodes (host residency stays O(batch_rows)) and
  the device pieces concatenate into arrays BITWISE-identical to what
  `GameDataset.fixed_effect_batch` builds from a one-shot read (CSR cuts
  are row-contiguous, so values/col_ids/row_ids are literal slices of the
  one-shot arrays; casts are elementwise). The untouched fused
  `lax.while_loop` solvers then run on the assembled batch, so
  `--stream-train` writes a byte-identical model to the one-shot driver
  while never holding more than a batch of rows on host.

- **shard cache** (`DeviceShardCache`): each batch becomes a PADDED
  static-shape `CSRFeatures` block (rows and nnz quantized by the
  serving `BucketLadder`, so per-bucket jitted accumulate executables in
  ops/sharded_objective.py stay enumerable) kept in HBM, with row-space
  columns (labels/offsets/weights) ALWAYS resident and an explicit
  `hbm_budget_bytes` that spills FEATURE blocks to host column buffers
  (replay-aware furthest-next-use eviction, not plain LRU — see
  `DeviceShardCache`). Solver iterations after the first replay cached
  device blocks instead of re-decoding Avro; spilled blocks re-upload
  through `HostPrefetcher` + `chunked_device_put` so H2D of shard k+1
  overlaps the accumulate of shard k (the same three-stage pipeline
  shape as streamed scoring).

The spill tier itself has two knobs (Snap ML's hierarchical memory
tiers, PAPERS.md — compressed / recomputed lower tiers are what make
trainable size disk-bounded):

- ``spill_dtype`` — what the host spill buffers hold. ``"f32"``
  (default) keeps the PR-5 raw padded f32/i32/i32 triplet: re-uploads
  are literally the evicted bytes, so every bitwise replay guarantee
  holds unchanged. ``"bf16"`` spills values as bfloat16 and indices
  DELTA-ENCODED to u8/u16 (`encode_spill`: column ids re-based per row,
  row ids as non-negative diffs; either stream falls back to raw i32
  when a delta overflows or is negative), cutting spill bytes AND
  per-epoch H2D re-upload traffic to ~1/3-1/2 of f32. Restore
  (`restore_spilled_features`) decodes ON DEVICE — upload is the
  compact encoding; a per-bucket jitted kernel widens bf16 -> f32 and
  un-deltas the indices — so the `CSRFeatures` handed to the sharded
  objective is f32/i32 exactly as before: the accumulate kernels'
  dtype contract is untouched (index bits are EXACTLY the evicted
  ones; values round-trip through bf16 with documented parity bounds,
  docs/SCALE.md §Training memory envelope). Values are quantized ONCE
  AT INGEST — never-evicted blocks take the same bf16 round trip — so
  a bf16 replay is deterministic and residency-independent just like
  f32; only the value PRECISION differs from the f32-spill model.
- ``spill_source`` — where evicted blocks come back from.
  ``"buffer"`` (default) re-uploads host spill buffers (host RAM stays
  O(dataset) — f32 or ~1/3 of that for bf16). ``"redecode"`` keeps NO
  host copy: evicted blocks are dropped and a cache miss re-decodes
  the Avro container blocks that produced the batch through a
  `BlockRandomAccess` (data/block_stream.py) row-range fetch — host
  memory falls to O(budget + one block) and trainable dataset size is
  bounded only by disk. The re-decoded batch is byte-identical to the
  ingest-time batch (the block cut is deterministic), so the padded
  triplet — and every partial — is bit-for-bit the resident replay.
  Misses run inside the `blocks()` prefetch thread, so the re-decode
  of shard k+1 overlaps the accumulate of shard k.

With ``devices`` (a 1-D mesh's device list, ``--mesh-devices``), blocks
place ROUND-ROBIN over the devices — block i is committed to
``devices[i % D]``, spill re-uploads return to the same device, and
``hbm_budget_bytes`` becomes PER DEVICE (each device's resident feature
bytes stay within the budget; total residency scales to D x budget).
The block -> device assignment is a pure function of the block index,
so the fixed shard order — and with it the fold's numeric contract —
is untouched by placement (ops/sharded_objective.py combines partials
in shard order regardless of which device computed them). A single
device (or ``devices=None``) is EXACTLY the PR-5 single-pool cache,
bit for bit.

With ``col_blocks=C`` (> 1) the cache keys feature blocks by
(row-shard, column-block) for a 2-D ``(data, model)`` mesh
(``--mesh-shape RxC``): each streamed batch's CSR matrix is cut into C
contiguous column blocks of ``ceil(d / C)`` columns
(`parallel.distributed.split_csr_columns` — scipy's canonical column
slice, so each block's nnz stream is an order-preserving subsequence
of the full stream), each block padded to its OWN nnz bucket with
LOCAL column ids, spilled/restored through its OWN SpillBlock, and
placed on device slot ``(i % R) * C + c`` of the flat row-major
``devices`` list (R = len(devices) / C). Row-space columns live once
per shard on the row's HOME device ``grid[i % R][C-1]`` — the last
column block's device, where the 2-D objective's margin chain ends.
``hbm_budget_bytes`` still binds PER device slot and the Belady rule
is per-(row, col)-slot: a slot's resident column slices are an
index-arithmetic subsequence of the shard order (slices in slot s all
have index = s // C mod R), so the global cyclic distance ranks them
exactly as the slot's own replay cycle does — same argument as the
1-D round-robin. ``col_blocks=1`` is EXACTLY the 1-D cache, bit for
bit.

The reference's analog is treeAggregate over cached RDD partitions
(`ValueAndGradientAggregator.scala:243-274`): no node ever holds the whole
dataset, partials combine in a fixed deterministic order.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Callable, Dict, Iterator, List, Optional

import numpy as np

from photon_ml_tpu import telemetry
from photon_ml_tpu.data.device_feed import HostPrefetcher, chunked_device_put
from photon_ml_tpu.telemetry import span
from photon_ml_tpu.ops.features import (
    CSRFeatures,
    DENSE_DENSITY_THRESHOLD,
    lay_out_triplet,
    padded_csr_arrays,
)
from photon_ml_tpu.serving.buckets import BucketLadder, next_pow2

# Registry mirrors of the per-instance ``_stats`` (no-ops while
# telemetry is off); names are part of the metrics.json snapshot schema
# (docs/OBSERVABILITY.md).
_M_HITS = telemetry.counter("data.shard_cache.hits")
_M_MISSES = telemetry.counter("data.shard_cache.misses")
_M_EVICTIONS = telemetry.counter("data.shard_cache.evictions")
_M_REUPLOAD_BYTES = telemetry.counter("data.shard_cache.bytes_reuploaded")
_M_SPILL_WRITTEN = telemetry.counter("data.shard_cache.spill_bytes_written")
_M_REDECODE_BYTES = telemetry.counter("data.shard_cache.bytes_redecoded")
_M_EPOCHS = telemetry.counter("data.shard_cache.epochs")
_G_DEVICE_BYTES = telemetry.gauge("data.shard_cache.device_bytes")
_G_PEAK_BYTES = telemetry.gauge("data.shard_cache.peak_device_bytes")
# Host-side spill residency: the O(dataset) cost that device_bytes/peak
# never showed (metrics.json twin: stream_train.cache.spill_bytes_host).
_G_SPILL_HOST = telemetry.gauge("data.shard_cache.spill_bytes_host")

SPILL_DTYPES = ("f32", "bf16")
SPILL_SOURCES = ("buffer", "redecode")


def _row_ids_i32(indptr: np.ndarray, offset: int = 0) -> np.ndarray:
    n = len(indptr) - 1
    return (np.repeat(np.arange(n, dtype=np.int64), np.diff(indptr))
            + offset).astype(np.int32)


# ---------------------------------------------------------------------------
# Spill codecs: compressed host buffers + on-device restore to f32/i32
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class SpillBlock:
    """Host spill record of one evicted feature block.

    All three arrays are PADDED to ``nnz_bucket`` (pad entries are
    zeros), so restore H2D transfers keep the static bucket shape the
    jitted decode kernel compiles for. Encodings per ``dtype_tag``:

    - ``"f32"``: the raw PR-5 triplet — ``enc_values`` f32,
      ``enc_cols``/``enc_rows`` i32. Restore re-uploads them verbatim
      (bitwise the evicted bytes).
    - ``"bf16"``: ``enc_values`` bfloat16 (round-to-nearest-even of the
      f32 values); ``enc_cols`` u8/u16 per-row delta codes (absolute
      column at each row start, positive within-row diffs after — CSR
      canonicalization guarantees sorted, duplicate-free columns);
      ``enc_rows`` u8/u16 non-negative diffs of the non-decreasing row
      ids. Either index stream independently falls back to raw i32
      when a delta overflows its widest unsigned code (or a
      non-canonical input produces a negative delta).

    The ``enc_*`` fields are ONLY consumed by
    :func:`restore_spilled_features` — anywhere else they would leak
    bf16/delta-encoded data into device kernels (enforced by the
    jaxlint ``spill-dtype-leak`` rule, docs/ANALYSIS.md).
    """

    nnz: int  # true entries; [nnz, nnz_bucket) is padding
    enc_values: np.ndarray
    enc_cols: np.ndarray
    enc_rows: np.ndarray
    dtype_tag: str  # "f32" | "bf16"

    @property
    def nbytes(self) -> int:
        return (self.enc_values.nbytes + self.enc_cols.nbytes
                + self.enc_rows.nbytes)


def _shrink_deltas(deltas: np.ndarray, raw: np.ndarray,
                   pad_to: int) -> np.ndarray:
    """Pick the narrowest unsigned code that holds every delta; when a
    delta is negative or exceeds u16, fall back to the RAW i32 ids
    (decode then skips the cumulative reconstruction entirely)."""
    lo = int(deltas.min()) if len(deltas) else 0
    hi = int(deltas.max()) if len(deltas) else 0
    if lo < 0 or hi > np.iinfo(np.uint16).max:
        out = np.zeros(pad_to, np.int32)
        out[:len(raw)] = raw
        return out
    code = np.uint8 if hi <= np.iinfo(np.uint8).max else np.uint16
    out = np.zeros(pad_to, code)
    out[:len(deltas)] = deltas
    return out


def encode_spill(values: np.ndarray, cols: np.ndarray, rows: np.ndarray,
                 nnz: int, spill_dtype: str) -> SpillBlock:
    """Padded f32/i32/i32 triplet -> host spill record (see SpillBlock).

    ``values/cols/rows`` are the padded ingest arrays
    (`padded_csr_arrays`); ``nnz`` is the true entry count. The f32 tag
    stores them as-is (zero-copy — today's spill, bit for bit)."""
    if spill_dtype not in SPILL_DTYPES:
        raise ValueError(
            f"spill_dtype must be one of {SPILL_DTYPES}, got "
            f"{spill_dtype!r}")
    if spill_dtype == "f32":
        return SpillBlock(nnz=nnz, enc_values=values, enc_cols=cols,
                          enc_rows=rows, dtype_tag="f32")
    import ml_dtypes

    pad_to = len(values)
    ev = np.zeros(pad_to, ml_dtypes.bfloat16)
    ev[:nnz] = values[:nnz].astype(ml_dtypes.bfloat16)
    c = cols[:nnz].astype(np.int64)
    r = rows[:nnz].astype(np.int64)
    cd = c.copy()
    cd[1:] -= c[:-1]
    if nnz:
        # Absolute column at each row start (the first entry is one).
        starts = np.empty(nnz, bool)
        starts[0] = True
        starts[1:] = r[1:] != r[:-1]
        cd[starts] = c[starts]
    rd = r.copy()
    rd[1:] -= r[:-1]
    return SpillBlock(
        nnz=nnz, enc_values=ev,
        enc_cols=_shrink_deltas(cd, cols[:nnz], pad_to),
        enc_rows=_shrink_deltas(rd, rows[:nnz], pad_to),
        dtype_tag="bf16")


def _decode_spill_impl(values, col_enc, row_enc, nnz):
    """Device-side spill decode: widen values to f32, un-delta the
    index streams, zero the pad tail. Traced per (nnz_bucket, encoding
    dtypes); ``nnz`` is a TRACED i32 scalar, so varying true nnz never
    recompiles. Raw-i32 fallback streams skip reconstruction (the
    dtype is part of the trace signature, so the branch is static)."""
    import jax.numpy as jnp
    from jax import lax

    n = values.shape[0]
    pos = lax.iota(jnp.int32, n)
    live = pos < nnz
    vals = jnp.where(live, values.astype(jnp.float32),
                     jnp.zeros((), jnp.float32))
    if row_enc.dtype == jnp.int32:
        rows = row_enc
    else:
        rows = jnp.cumsum(row_enc.astype(jnp.int32))
    if col_enc.dtype == jnp.int32:
        cols = col_enc
    else:
        d = col_enc.astype(jnp.int32)
        cum = jnp.cumsum(d)
        start = jnp.concatenate(
            [jnp.ones((1,), bool), rows[1:] != rows[:-1]])
        base = cum - d  # prefix sum before each element
        # Bases at row starts are non-decreasing (deltas >= 0), so a
        # running max propagates each segment's re-base forward.
        corr = lax.cummax(jnp.where(start, base, 0))
        cols = cum - corr
    zero = jnp.zeros((), jnp.int32)
    return (vals, jnp.where(live, cols, zero).astype(jnp.int32),
            jnp.where(live, rows, zero).astype(jnp.int32))


@functools.lru_cache(maxsize=1)
def _decode_spill_jit():
    """One process-wide jitted decode (built on first spill restore so
    importing this module never imports jax); the jit cache keys on
    (nnz_bucket, encoding dtypes) — true nnz is a traced argument."""
    import jax

    return jax.jit(_decode_spill_impl)


def restore_spilled_features(spill: SpillBlock, rows_bucket: int,
                             n_features: int, device) -> CSRFeatures:
    """The ONE blessed spill -> device path: re-upload (compact bytes on
    the wire) and restore to the f32/i32 `CSRFeatures` the sharded
    objective's kernels were compiled for. f32 spill re-uploads the
    evicted bytes verbatim; bf16 spill uploads the encodings and
    decodes on device (`_decode_spill_impl`)."""
    import jax
    import jax.numpy as jnp

    def idx(x):
        return (jnp.asarray(x) if device is None
                else jax.device_put(x, device))

    if spill.dtype_tag == "f32":
        return CSRFeatures(
            chunked_device_put(spill.enc_values, device=device),
            idx(spill.enc_cols), idx(spill.enc_rows),
            rows_bucket, n_features)
    vals, cols, rows = _decode_spill_jit()(
        idx(spill.enc_values), idx(spill.enc_cols), idx(spill.enc_rows),
        idx(np.int32(spill.nnz)))
    return CSRFeatures(vals, cols, rows, rows_bucket, n_features)


# ---------------------------------------------------------------------------
# Exact assembly: streamed ingest -> the one-shot device batch, bit for bit
# ---------------------------------------------------------------------------


class StreamedFixedEffectData:
    """Duck-typed stand-in for the GameDataset a FixedEffectCoordinate
    consumes: the feature batch is already device-assembled from a
    stream, so `fixed_effect_batch` hands it back instead of re-uploading
    host CSR. Exposes exactly the surface the fixed-effect training path
    touches (`num_rows`, `feature_shards[...].shape`,
    `responses`/`offsets`/`weights` for the coordinate-descent objective
    rows, `fixed_effect_batch`)."""

    class _ShapeOnly:
        def __init__(self, shape):
            self.shape = shape

    def __init__(self, shard_id: str, batch, n_rows: int, d: int,
                 ingest_stats: dict):
        self._shard_id = shard_id
        self._batch = batch
        self._n_rows = int(n_rows)
        self.feature_shards = {shard_id: self._ShapeOnly((n_rows, d))}
        # Device f32 columns: jnp.asarray(col, dtype) in the consumer is a
        # no-op cast, value-identical to the one-shot host-f64 -> f32 cast.
        self.responses = batch.labels
        self.offsets = batch.offsets
        self.weights = batch.weights
        self.ingest_stats = dict(ingest_stats)

    @property
    def num_rows(self) -> int:
        return self._n_rows

    def fixed_effect_batch(self, shard_id: str, dtype=None,
                           extra_offsets=None):
        from photon_ml_tpu.ops.glm_objective import GLMBatch

        if shard_id != self._shard_id:
            raise KeyError(
                f"streamed ingest assembled shard {self._shard_id!r}, "
                f"coordinate asked for {shard_id!r}")
        # the array's own dtype: no fetch of the vector to the host
        have = np.dtype(self._batch.labels.dtype)
        if dtype is not None and np.dtype(dtype) != have:
            raise ValueError(
                f"streamed batch was assembled as {have}, asked for {dtype}")
        if extra_offsets is None:
            return self._batch
        return GLMBatch(self._batch.features, self._batch.labels,
                        self._batch.offsets + extra_offsets,
                        self._batch.weights)


def assemble_fixed_effect_batch(
    stream, shard_id: str, dtype=np.float32,
    dense_threshold: float = DENSE_DENSITY_THRESHOLD,
) -> StreamedFixedEffectData:
    """Consume a BlockGameStream into ONE device GLMBatch, bitwise equal
    to `read_game_dataset(...)[0].fixed_effect_batch(shard_id, dtype)`.

    Host residency is O(batch_rows): each decoded batch's arrays upload
    (async) and are dropped before the next batch decodes. Device pieces
    are exact slices of the one-shot arrays (row-contiguous CSR cuts +
    the same elementwise f64->f32 / int->i32 casts), so the final
    device-side concatenation reconstructs the one-shot upload exactly —
    including the dense-vs-CSR layout decision, which is made from the
    GLOBAL density after the stream ends, exactly like
    `features_to_device` on the full matrix. Below the density threshold
    the streamed triplet ends where the one-shot upload ends, in the
    program's chooser (`ops.features.lay_out_triplet`)."""
    import jax.numpy as jnp

    from photon_ml_tpu.ops.glm_objective import GLMBatch

    vals_p, cols_p, rows_p = [], [], []
    lab_p, off_p, wgt_p = [], [], []
    n_rows = 0
    nnz = 0
    d = None
    for ds in stream:
        mat = ds.feature_shards[shard_id].tocsr()
        d = mat.shape[1]
        if ds.num_rows == 0:
            continue
        # Exact one-shot pieces: csr_from_scipy's COO row-stable sort is
        # the identity on a canonical CSR, so data/indices ARE the slices.
        vals_p.append(chunked_device_put(mat.data, dtype))
        cols_p.append(jnp.asarray(mat.indices.astype(np.int32)))
        rows_p.append(jnp.asarray(_row_ids_i32(mat.indptr, n_rows)))
        lab_p.append(chunked_device_put(ds.responses, dtype))
        off_p.append(chunked_device_put(ds.offsets, dtype))
        wgt_p.append(chunked_device_put(ds.weights, dtype))
        n_rows += ds.num_rows
        nnz += mat.nnz
    if n_rows == 0:
        raise ValueError("stream yielded no rows to assemble")

    def cat(parts):
        return parts[0] if len(parts) == 1 else jnp.concatenate(parts)

    values, col_ids, row_ids = cat(vals_p), cat(cols_p), cat(rows_p)
    feats = CSRFeatures(values, col_ids, row_ids, n_rows, int(d))
    density = nnz / max(1, n_rows * d)
    if density >= dense_threshold:
        # One-shot path densifies before upload; scattering the exact CSR
        # pieces into zeros reproduces the same array (no duplicates, and
        # the f64->f32 value cast already happened elementwise at upload).
        feats = feats.to_dense()
    else:
        feats = lay_out_triplet(feats)
    batch = GLMBatch(features=feats, labels=cat(lab_p), offsets=cat(off_p),
                     weights=cat(wgt_p))
    stats = dict(stream.stats())
    stats.update({"assembled_rows": n_rows, "assembled_nnz": nnz,
                  "density": density,
                  "layout": type(feats).__name__})
    return StreamedFixedEffectData(shard_id, batch, n_rows, int(d), stats)


# ---------------------------------------------------------------------------
# The shard cache: padded device blocks, replay-aware spill, prefetch
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class ColumnSlice:
    """One (row-shard, column-block) feature unit of a ``col_blocks > 1``
    cache: the shard's nnz entries whose columns fall in
    ``[c*block_size, (c+1)*block_size)``, padded to the slice's OWN nnz
    bucket, with LOCAL column ids (``CSRFeatures.n_features ==
    block_size``). The slice — not the shard — is the unit of placement
    (device ``grid[i % R][c]``), eviction, and spill."""

    c: int  # column-block index
    nnz: int  # true entries (<= nnz_bucket)
    nnz_bucket: int
    spill: Optional[SpillBlock]  # host spill record; None = no host copy
    feats: Optional[CSRFeatures] = None  # None = spilled
    device: object = None
    slot: int = 0  # (index % R) * C + c

    @property
    def feature_bytes(self) -> int:
        return 12 * self.nnz_bucket

    @property
    def spill_bytes(self) -> int:
        return 0 if self.spill is None else self.spill.nbytes


@dataclasses.dataclass
class CachedShard:
    """One streamed batch as a static-shape device block.

    Row-space columns (labels/offsets/weights, padded to ``rows_bucket``
    with weight-0 rows) are ALWAYS device-resident — they are the cheap
    4-bytes-per-row part, and keeping them resident is what makes the
    margin-cached line search feature-pass-free. The FEATURE triplet
    (``feats``) is the evictable part; ``spill`` is the host record it
    restores from (None in the ``redecode`` tier, where a miss re-decodes
    the source Avro rows instead).

    With ``col_blocks > 1`` the feature triplet is split into per-column
    ``ColumnSlice`` units (``cols``; ``feats``/``spill`` stay None and
    ``nnz_bucket`` is unused) and ``device``/``slot`` are the row's HOME
    placement — the LAST column block's device, where labels/offsets/
    weights and the 2-D objective's row-space state live."""

    index: int
    n_rows: int  # true rows (<= rows_bucket)
    nnz: int  # true nnz (<= nnz_bucket)
    rows_bucket: int
    nnz_bucket: int
    row_offset: int  # first global row id
    labels: object  # device f[rows_bucket]
    offsets: object
    weights: object
    spill: Optional[SpillBlock]  # host spill record; None = no host copy
    feats: Optional[CSRFeatures] = None  # None = spilled
    device: object = None  # mesh placement; None = default device
    slot: int = 0  # mesh slot (index % n_devices); 0 without a mesh
    cols: Optional[List[ColumnSlice]] = None  # col_blocks > 1 units

    @property
    def feature_bytes(self) -> int:
        # Device-resident cost: values f32 + col_ids i32 + row_ids i32,
        # at the padded shape (restore always widens back to f32/i32).
        if self.cols is not None:
            return sum(s.feature_bytes for s in self.cols)
        return 12 * self.nnz_bucket

    @property
    def spill_bytes(self) -> int:
        # Host-resident cost of the spill record (0 for redecode).
        if self.cols is not None:
            return sum(s.spill_bytes for s in self.cols)
        return 0 if self.spill is None else self.spill.nbytes


@dataclasses.dataclass(frozen=True)
class ResidentBlock:
    """A shard handed out by `DeviceShardCache.blocks()`: a SNAPSHOT
    holding its own strong reference to the device feature triplet, so a
    later eviction (which only drops the cache's reference) can never
    pull the arrays out from under an in-flight accumulate."""

    index: int
    n_rows: int
    feats: Optional[CSRFeatures]
    labels: object
    offsets: object
    weights: object
    slot: int = 0  # device slot the block (and its partials) live on
    # col_blocks > 1: per-column feature snapshots (feats is None); the
    # slot above is the HOME slot where row-space columns live.
    cols: tuple = ()


class DeviceShardCache:
    """Device cache of padded feature blocks over a streamed ingest.

    Built once from a `BlockGameStream` (`from_stream`); every solver
    iteration then replays `blocks()` in FIXED shard order — the
    accumulation order is part of the numeric contract, so resident,
    spilled, and re-uploaded replays produce bitwise-identical partials
    (re-uploaded bytes are the bytes that were evicted).

    ``hbm_budget_bytes`` bounds the feature bytes resident on device;
    `None` means unbounded (fully resident, spill buffers freed). The
    budget is enforced DURING ingest (evict-as-you-go, so ingest peak
    HBM is O(budget), not O(dataset)) and on every re-upload. Eviction
    is replay-aware rather than plain LRU: the replay order is the fixed
    shard order, so the victim is the resident block whose next use is
    FURTHEST in the cyclic order. Plain LRU degenerates to a 0% hit
    rate here — with n shards and budget n-1, the least-recently-used
    block is always exactly the next one needed (n misses/epoch). The
    distance rule pays ~(n - budget_blocks) misses per epoch plus a
    small wrap-around surcharge (the in-hand block must be cached, so
    the resident "hole" walks and costs one extra miss every n-1
    epochs: amortized 1 + 1/(n-1) misses/epoch at budget n-1 with
    equal blocks) —
    per-epoch re-uploads stay close to (dataset - budget) bytes instead
    of the whole dataset. The in-hand block is never evicted; one block
    can exceed a too-small budget (you cannot accumulate a block that
    is not there).
    """

    def __init__(self, entries: List[CachedShard], n_rows: int,
                 n_features: int, dtype,
                 hbm_budget_bytes: Optional[int] = None,
                 prefetch_depth: int = 2,
                 ingest_stats: Optional[dict] = None,
                 devices: Optional[List] = None,
                 spill_dtype: str = "f32",
                 spill_source: str = "buffer",
                 shard_id: Optional[str] = None,
                 redecode_fetch: Optional[Callable] = None,
                 col_blocks: int = 1):
        if spill_dtype not in SPILL_DTYPES:
            raise ValueError(
                f"spill_dtype must be one of {SPILL_DTYPES}, got "
                f"{spill_dtype!r}")
        if spill_source not in SPILL_SOURCES:
            raise ValueError(
                f"spill_source must be one of {SPILL_SOURCES}, got "
                f"{spill_source!r}")
        if spill_source == "redecode" and spill_dtype != "f32":
            raise ValueError(
                f"spill_dtype={spill_dtype!r} compresses host spill "
                "buffers, but spill_source='redecode' keeps none — the "
                "combination would silently train as f32 while "
                "reporting bf16; pick one")
        if spill_source == "redecode" and hbm_budget_bytes is not None \
                and redecode_fetch is None:
            raise ValueError(
                "spill_source='redecode' needs a redecode_fetch "
                "callable (BlockRandomAccess.fetch_rows) to re-decode "
                "evicted blocks from")
        self._entries = entries
        self.n_rows = int(n_rows)
        self.n_features = int(n_features)
        self.dtype = np.dtype(dtype)
        self.hbm_budget_bytes = hbm_budget_bytes
        self.prefetch_depth = max(0, int(prefetch_depth))
        self.ingest_stats = dict(ingest_stats or {})
        self.spill_dtype = spill_dtype
        self.spill_source = spill_source
        self._shard_id = shard_id
        self._redecode_fetch = redecode_fetch
        self._stats = {"hits": 0, "misses": 0, "evictions": 0,
                       "bytes_reuploaded": 0, "epochs": 0,
                       "spill_bytes_written": 0, "redecodes": 0,
                       "bytes_redecoded": 0}
        # A 1-device "mesh" is the single-pool cache: `devices` is only
        # recorded (and placement/budget split per device) for >= 2.
        self.devices = (list(devices)
                        if devices is not None and len(devices) > 1
                        else None)
        self.col_blocks = int(col_blocks)
        if self.col_blocks < 1:
            raise ValueError(f"col_blocks must be >= 1, got {col_blocks}")
        if self.col_blocks > 1:
            if self.devices is None:
                raise ValueError(
                    "col_blocks > 1 places column blocks on a (data, "
                    "model) device grid — pass devices="
                    "mesh_fold_devices(make_mesh_2d(R, C))")
            if len(self.devices) % self.col_blocks:
                raise ValueError(
                    f"{len(self.devices)} devices do not tile a grid "
                    f"with {self.col_blocks} column blocks — need a "
                    "multiple of col_blocks")
        self.n_slots = len(self.devices) if self.devices else 1
        # Uniform column-block width (the split_csr_columns rule); the
        # 2-D objective slices the coefficient vector by it.
        self.col_block_size = -(-self.n_features // self.col_blocks)
        self._slot_bytes = [0] * self.n_slots
        for _, unit in self._all_units():
            if unit.feats is not None:
                self._slot_bytes[unit.slot] += unit.feature_bytes
        self.peak_device_bytes = self.device_bytes
        if hbm_budget_bytes is None:
            for _, unit in self._all_units():
                unit.spill = None
        _G_SPILL_HOST.set(self.spill_bytes_host)

    def _all_units(self):
        """(entry, evictable feature unit) pairs in shard order — the
        CachedShard itself for col_blocks == 1, its ColumnSlices
        otherwise."""
        for e in self._entries:
            if e.cols is not None:
                for s in e.cols:
                    yield e, s
            else:
                yield e, e

    @property
    def spill_bytes_host(self) -> int:
        """Host bytes retained by spill records across all shards — the
        cost that is O(dataset) for ``buffer`` spill (f32, or ~1/3 for
        bf16) and 0 for ``redecode``. Constant after ingest: buffers
        are written once and retained regardless of residency."""
        return sum(e.spill_bytes for e in self._entries)

    @property
    def device_bytes(self) -> int:
        """Cache-accounted feature bytes resident across ALL devices
        (with a mesh the budget binds PER device — see stats())."""
        return sum(self._slot_bytes)

    # -- construction ------------------------------------------------------

    @classmethod
    def from_stream(cls, stream, shard_id: str, dtype=np.float32,
                    hbm_budget_bytes: Optional[int] = None,
                    min_rows_bucket: int = 16,
                    prefetch_depth: int = 2,
                    devices: Optional[List] = None,
                    spill_dtype: str = "f32",
                    spill_source: str = "buffer",
                    redecode_fetch: Optional[Callable] = None,
                    col_blocks: int = 1
                    ) -> "DeviceShardCache":
        """Ingest pass: decode (prefetched, via the stream) -> pad to the
        bucket ladder -> upload. Decode of batch k+1 overlaps the H2D of
        batch k (device_put is async; the stream's prefetch thread keeps
        decoding while uploads ride the wire). With an ``hbm_budget``
        the budget is enforced AS blocks upload — the most recently
        ingested block spills first (its next use, at the start of the
        first replay epoch, is the furthest away), so ingest-peak device
        bytes stay O(budget + one block) and the resident set ends as a
        stable PREFIX of the shard order. ``devices`` (>= 2) places
        block i on ``devices[i % D]`` and makes the budget (and the
        evict-as-you-go accounting) per device.

        ``spill_dtype``/``spill_source`` pick the spill tier (module
        docstring): compressed host buffers (``bf16``) and/or no host
        buffers at all (``redecode``, with ``redecode_fetch`` the
        row-range re-decode hook — `BlockRandomAccess.fetch_rows`)."""
        import jax
        import jax.numpy as jnp

        if spill_dtype not in SPILL_DTYPES:
            raise ValueError(
                f"spill_dtype must be one of {SPILL_DTYPES}, got "
                f"{spill_dtype!r}")
        if spill_source not in SPILL_SOURCES:
            raise ValueError(
                f"spill_source must be one of {SPILL_SOURCES}, got "
                f"{spill_source!r}")
        if spill_source == "redecode" and spill_dtype != "f32":
            # Fail BEFORE the ingest pass: compressed buffers and
            # no-buffers are mutually exclusive tiers (the combination
            # would silently train as f32 while reporting bf16).
            raise ValueError(
                f"spill_dtype={spill_dtype!r} compresses host spill "
                "buffers, but spill_source='redecode' keeps none — "
                "pick one")
        keep_buffers = (hbm_budget_bytes is not None
                        and spill_source == "buffer")
        devs = (list(devices)
                if devices is not None and len(devices) > 1 else None)
        n_slots = len(devs) if devs else 1
        col_blocks = int(col_blocks)
        if col_blocks > 1:
            if devs is None:
                raise ValueError(
                    "col_blocks > 1 places column blocks on a (data, "
                    "model) device grid — pass devices="
                    "mesh_fold_devices(make_mesh_2d(R, C))")
            if n_slots % col_blocks:
                raise ValueError(
                    f"{n_slots} devices do not tile a grid with "
                    f"{col_blocks} column blocks — need a multiple of "
                    "col_blocks")
        n_row_slots = n_slots // col_blocks
        entries: List[CachedShard] = []
        n_rows = 0
        d = None
        ladder = None
        slot_bytes = [0] * n_slots
        peak_bytes = 0
        evictions = 0
        spill_written = 0
        for ds in stream:
            if ds.num_rows == 0:
                continue
            mat = ds.feature_shards[shard_id].tocsr()
            d = mat.shape[1]
            if ladder is None:
                ladder = BucketLadder(
                    min_rows=min(min_rows_bucket, next_pow2(ds.num_rows)),
                    max_rows=next_pow2(ds.num_rows))
            rb = ladder.rows_bucket(ds.num_rows)
            nb = ladder.nnz_bucket(mat.nnz, rb)
            if col_blocks > 1:
                # Row-space columns live on the row's HOME device — the
                # LAST column block's slot, where the 2-D objective's
                # margin chain ends (ops/sharded_objective.py).
                slot = (len(entries) % n_row_slots) * col_blocks \
                    + (col_blocks - 1)
            else:
                slot = len(entries) % n_slots
            dev = devs[slot] if devs else None
            with span("shard_upload"):

                def col(x):
                    out = np.zeros(rb, dtype)
                    out[:ds.num_rows] = x
                    return (jnp.asarray(out) if dev is None
                            else jax.device_put(out, dev))

                def idx(x, d_=None):
                    d_ = dev if d_ is None else d_
                    return (jnp.asarray(x) if d_ is None
                            else jax.device_put(x, d_))

                def build_unit(sub, sub_nnz, nb_u, width, u_dev):
                    """Pad + spill-encode + upload one feature unit
                    (the whole shard, or one column slice)."""
                    nonlocal spill_written
                    values, cols_a, rows_a = padded_csr_arrays(
                        sub, rb, nb_u, value_dtype=dtype)
                    sp = None
                    if keep_buffers:
                        sp = encode_spill(values, cols_a, rows_a,
                                          sub_nnz, spill_dtype)
                        spill_written += sp.nbytes
                        _M_SPILL_WRITTEN.inc(sp.nbytes)
                    if sp is not None and sp.dtype_tag != "f32":
                        # Lossy spill encodings quantize AT INGEST:
                        # every block's device values take the same
                        # encode->restore round trip whether or not it
                        # ever spills, so bf16 replays stay
                        # deterministic AND residency-independent (a
                        # path-dependent precision profile — resident
                        # blocks f32, once-evicted blocks bf16 — would
                        # make model bits depend on eviction history).
                        f = restore_spilled_features(sp, rb, width,
                                                     u_dev)
                    else:
                        f = CSRFeatures(
                            chunked_device_put(values, device=u_dev),
                            idx(cols_a, u_dev), idx(rows_a, u_dev),
                            rb, width)
                    return sp, f

                if col_blocks > 1:
                    from photon_ml_tpu.parallel.distributed import (
                        split_csr_columns,
                    )

                    bs_cols, subs = split_csr_columns(mat, col_blocks)
                    r_slot = len(entries) % n_row_slots
                    slices = []
                    for c, sub in enumerate(subs):
                        c_slot = r_slot * col_blocks + c
                        c_dev = devs[c_slot]
                        nb_c = ladder.nnz_bucket(int(sub.nnz), rb)
                        sp, f = build_unit(sub, int(sub.nnz), nb_c,
                                           bs_cols, c_dev)
                        slices.append(ColumnSlice(
                            c=c, nnz=int(sub.nnz), nnz_bucket=nb_c,
                            spill=sp, feats=f, device=c_dev,
                            slot=c_slot))
                    spill, feats, cols_list = None, None, slices
                else:
                    spill, feats = build_unit(mat, int(mat.nnz), nb,
                                              int(d), dev)
                    cols_list = None
                e = CachedShard(
                    index=len(entries), n_rows=ds.num_rows,
                    nnz=int(mat.nnz), rows_bucket=rb, nnz_bucket=nb,
                    row_offset=n_rows,
                    labels=col(ds.responses), offsets=col(ds.offsets),
                    weights=col(ds.weights),
                    spill=spill,
                    feats=feats,
                    device=dev, slot=slot, cols=cols_list,
                )
            entries.append(e)
            n_rows += ds.num_rows
            new_units = e.cols if e.cols is not None else [e]
            for nu in new_units:
                slot_bytes[nu.slot] += nu.feature_bytes
            peak_bytes = max(peak_bytes, sum(slot_bytes))
            if hbm_budget_bytes is not None:
                # Evict-as-you-go on each new unit's OWN device slot:
                # the budget is per device, and eviction stays
                # most-recent-first (keep the prefix), never the block
                # just uploaded.
                for nu in new_units:
                    sl = nu.slot
                    for victim in reversed(entries[:-1]):
                        if slot_bytes[sl] <= hbm_budget_bytes:
                            break
                        vu = (victim.cols[sl % col_blocks]
                              if victim.cols is not None else victim)
                        if vu.slot == sl and vu.feats is not None:
                            vu.feats = None
                            slot_bytes[sl] -= vu.feature_bytes
                            evictions += 1
                            _M_EVICTIONS.inc()
        if not entries:
            raise ValueError("stream yielded no rows to cache")
        cache = cls(entries, n_rows, int(d), dtype,
                    hbm_budget_bytes=hbm_budget_bytes,
                    prefetch_depth=prefetch_depth,
                    ingest_stats=stream.stats(), devices=devs,
                    spill_dtype=spill_dtype, spill_source=spill_source,
                    shard_id=shard_id, redecode_fetch=redecode_fetch,
                    col_blocks=col_blocks)
        cache._stats["evictions"] += evictions
        cache._stats["spill_bytes_written"] += spill_written
        cache.peak_device_bytes = max(cache.peak_device_bytes, peak_bytes)
        if hbm_budget_bytes is not None:
            # The final block stayed pinned during ingest; settle to the
            # budget with the replay-aware policy (next use = shard 0).
            cache._enforce_budget(pinned=-1)
        # Mirror residency gauges even when nothing evicts (a fully
        # resident cache must not report 0 bytes in the registry).
        _G_DEVICE_BYTES.set(cache.device_bytes)
        _G_PEAK_BYTES.set(cache.peak_device_bytes)
        return cache

    # -- residency management ----------------------------------------------

    @property
    def n_shards(self) -> int:
        return len(self._entries)

    @property
    def entries(self) -> List[CachedShard]:
        return list(self._entries)

    def bucket_shapes(self) -> set:
        if self.col_blocks > 1:
            return {(e.rows_bucket, s.nnz_bucket)
                    for e in self._entries for s in e.cols}
        return {(e.rows_bucket, e.nnz_bucket) for e in self._entries}

    def _entry_resident(self, e: CachedShard) -> bool:
        if e.cols is not None:
            return all(s.feats is not None for s in e.cols)
        return e.feats is not None

    def _enforce_budget(self, pinned: int) -> None:
        """Evict until within budget — PER DEVICE slot under a mesh (the
        budget bounds each device's residency; a single-pool cache is
        the one-slot case). Victim = that slot's resident block whose
        next use is FURTHEST in the fixed cyclic replay order from the
        block in hand (`pinned`; -1 = before an epoch, i.e. next use
        starts at shard 0). Belady's rule for a known cyclic scan — see
        the class docstring for why plain LRU is pathological here.
        Round-robin slots are index-arithmetic subsequences of the shard
        order, so the GLOBAL cyclic distance ranks a slot's blocks
        exactly as the slot's own replay cycle does."""
        budget = self.hbm_budget_bytes
        if budget is None:
            return
        n = len(self._entries)
        cur = pinned if pinned >= 0 else 0
        for slot in range(self.n_slots):
            if self._slot_bytes[slot] <= budget:
                continue
            resident = [(e, u) for e, u in self._all_units()
                        if u.feats is not None and e.index != pinned
                        and u.slot == slot]
            # descending cyclic distance (j - cur) mod n: furthest-next-
            # use first; ties impossible (a slot holds at most one unit
            # per shard index).
            resident.sort(key=lambda p: -((p[0].index - cur) % n))
            while self._slot_bytes[slot] > budget and resident:
                _, victim = resident.pop(0)
                victim.feats = None
                self._slot_bytes[slot] -= victim.feature_bytes
                self._stats["evictions"] += 1
                _M_EVICTIONS.inc()
        _G_DEVICE_BYTES.set(self.device_bytes)

    def _redecode(self, e: CachedShard) -> CSRFeatures:
        """redecode-tier miss: re-decode the block's source rows through
        the random-access block fetch, re-pad, re-upload. The fetched
        batch is byte-identical to the ingest-time batch (deterministic
        block cut), so the padded triplet — hence every partial — is
        bit-for-bit the resident replay."""
        fetch = self._redecode_fetch
        before = getattr(fetch, "payload_bytes_read", None)
        with span("shard_redecode"):
            ds = fetch(e.row_offset, e.n_rows)
            mat = ds.feature_shards[self._shard_id].tocsr()
            if mat.shape[0] != e.n_rows or int(mat.nnz) != e.nnz:
                raise RuntimeError(
                    f"re-decoded shard {e.index} does not match the "
                    f"ingested block: got {mat.shape[0]} rows/{mat.nnz} "
                    f"nnz, cached {e.n_rows}/{e.nnz} — the input "
                    "changed under the cache")
            values, cols, rows = padded_csr_arrays(
                mat, e.rows_bucket, e.nnz_bucket, value_dtype=self.dtype)
        self._stats["redecodes"] += 1
        after = getattr(fetch, "payload_bytes_read", None)
        redecoded = (after - before if before is not None
                     and after is not None else e.feature_bytes)
        self._stats["bytes_redecoded"] += redecoded
        _M_REDECODE_BYTES.inc(redecoded)
        return restore_spilled_features(
            SpillBlock(nnz=e.nnz, enc_values=values, enc_cols=cols,
                       enc_rows=rows, dtype_tag="f32"),
            e.rows_bucket, self.n_features, e.device)

    def _redecode_2d(self, e: CachedShard, missing: List[ColumnSlice]
                     ) -> None:
        """redecode-tier miss for a col_blocks > 1 entry: ONE row-range
        fetch re-decodes the batch, the column cut re-slices it (the
        same deterministic `split_csr_columns` cut as ingest), and only
        the MISSING slices re-pad and re-upload — each to its own
        (row, col) device."""
        from photon_ml_tpu.parallel.distributed import split_csr_columns

        fetch = self._redecode_fetch
        before = getattr(fetch, "payload_bytes_read", None)
        with span("shard_redecode"):
            ds = fetch(e.row_offset, e.n_rows)
            mat = ds.feature_shards[self._shard_id].tocsr()
            if mat.shape[0] != e.n_rows or int(mat.nnz) != e.nnz:
                raise RuntimeError(
                    f"re-decoded shard {e.index} does not match the "
                    f"ingested block: got {mat.shape[0]} rows/{mat.nnz} "
                    f"nnz, cached {e.n_rows}/{e.nnz} — the input "
                    "changed under the cache")
            _, subs = split_csr_columns(mat, self.col_blocks)
            payloads = {}
            for s in missing:
                sub = subs[s.c]
                values, cols, rows = padded_csr_arrays(
                    sub, e.rows_bucket, s.nnz_bucket,
                    value_dtype=self.dtype)
                payloads[s.c] = (values, cols, rows, int(sub.nnz))
        self._stats["redecodes"] += 1
        after = getattr(fetch, "payload_bytes_read", None)
        redecoded = (after - before if before is not None
                     and after is not None
                     else sum(s.feature_bytes for s in missing))
        self._stats["bytes_redecoded"] += redecoded
        _M_REDECODE_BYTES.inc(redecoded)
        for s in missing:
            values, cols, rows, sub_nnz = payloads[s.c]
            s.feats = restore_spilled_features(
                SpillBlock(nnz=sub_nnz, enc_values=values, enc_cols=cols,
                           enc_rows=rows, dtype_tag="f32"),
                e.rows_bucket, self.col_block_size, s.device)

    def _ensure_2d(self, e: CachedShard) -> ResidentBlock:
        """col_blocks > 1 residency: a miss restores each evicted
        column slice to ITS OWN (row, col) device; the snapshot carries
        the per-column feature triplets in column order."""
        missing = [s for s in e.cols if s.feats is None]
        if missing:
            self._stats["misses"] += 1
            _M_MISSES.inc()
            reupload = 0
            for s in missing:
                if s.spill is not None:
                    reupload += (s.spill.nbytes
                                 if s.spill.dtype_tag != "f32"
                                 else s.feature_bytes)
                elif self._redecode_fetch is not None:
                    reupload += s.feature_bytes
                else:
                    raise RuntimeError(
                        f"shard {e.index} column block {s.c} was "
                        "evicted but has no spill buffers (cache built "
                        "without an hbm budget)")
            self._stats["bytes_reuploaded"] += reupload
            _M_REUPLOAD_BYTES.inc(reupload)
            for s in missing:
                self._slot_bytes[s.slot] += s.feature_bytes
            self.peak_device_bytes = max(self.peak_device_bytes,
                                         self.device_bytes)
            _G_PEAK_BYTES.set(self.peak_device_bytes)
            if missing[0].spill is not None:
                with span("shard_reupload"):
                    for s in missing:
                        s.feats = restore_spilled_features(
                            s.spill, e.rows_bucket, self.col_block_size,
                            s.device)
            else:
                self._redecode_2d(e, missing)
            self._enforce_budget(pinned=e.index)
        else:
            self._stats["hits"] += 1
            _M_HITS.inc()
        return ResidentBlock(index=e.index, n_rows=e.n_rows, feats=None,
                             labels=e.labels, offsets=e.offsets,
                             weights=e.weights, slot=e.slot,
                             cols=tuple(s.feats for s in e.cols))

    def ensure(self, index: int) -> ResidentBlock:
        """Return a resident snapshot of the block, restoring it on a
        miss (async put — the caller overlaps it with whatever it is
        accumulating): buffer spill re-uploads + decodes the host spill
        record (`restore_spilled_features`), the redecode tier
        re-decodes the source Avro rows (`_redecode`)."""
        e = self._entries[index]
        if e.cols is not None:
            return self._ensure_2d(e)
        if e.feats is None:
            self._stats["misses"] += 1
            _M_MISSES.inc()
            if e.spill is not None:
                reupload = (e.spill.nbytes if e.spill.dtype_tag != "f32"
                            else e.feature_bytes)
            elif self._redecode_fetch is not None:
                reupload = e.feature_bytes
            else:
                raise RuntimeError(
                    f"shard {index} was evicted but has no spill "
                    "buffers (cache built without an hbm budget)")
            self._stats["bytes_reuploaded"] += reupload
            _M_REUPLOAD_BYTES.inc(reupload)
            self._slot_bytes[e.slot] += e.feature_bytes
            self.peak_device_bytes = max(self.peak_device_bytes,
                                         self.device_bytes)
            _G_PEAK_BYTES.set(self.peak_device_bytes)
            if e.spill is not None:
                with span("shard_reupload"):
                    # Spilled blocks return to their ASSIGNED device —
                    # the round-robin placement is part of the replay
                    # contract.
                    e.feats = restore_spilled_features(
                        e.spill, e.rows_bucket, self.n_features,
                        e.device)
            else:
                e.feats = self._redecode(e)
            self._enforce_budget(pinned=index)
        else:
            self._stats["hits"] += 1
            _M_HITS.inc()
        return ResidentBlock(index=e.index, n_rows=e.n_rows, feats=e.feats,
                             labels=e.labels, offsets=e.offsets,
                             weights=e.weights, slot=e.slot)

    def blocks(self, prefetch_depth: Optional[int] = None
               ) -> Iterator[ResidentBlock]:
        """One replay epoch in fixed shard order. With a prefetch depth
        > 0 the spill re-uploads run on a background thread
        (`HostPrefetcher`), so H2D of shard k+1 overlaps the consumer's
        accumulate of shard k; resident epochs yield straight from HBM."""
        self._stats["epochs"] += 1
        _M_EPOCHS.inc()
        depth = (self.prefetch_depth if prefetch_depth is None
                 else max(0, int(prefetch_depth)))

        def gen():
            for i in range(len(self._entries)):
                yield self.ensure(i)

        if depth < 1 or self.hbm_budget_bytes is None:
            yield from gen()
            return
        yield from HostPrefetcher(gen(), depth)

    def stats(self) -> Dict:
        s = dict(self._stats)
        s.update({
            "shards": self.n_shards,
            "rows": self.n_rows,
            "bucket_shapes": sorted(self.bucket_shapes()),
            "hbm_budget_bytes": self.hbm_budget_bytes,
            "device_bytes": self.device_bytes,
            "peak_device_bytes": self.peak_device_bytes,
            # Host-side spill residency (the O(dataset) cost device
            # gauges never showed) + the tier that produced it.
            "spill_dtype": self.spill_dtype,
            "spill_source": self.spill_source,
            "spill_bytes_host": self.spill_bytes_host,
            "resident_shards": sum(1 for e in self._entries
                                   if self._entry_resident(e)),
            # Mesh placement: hbm_budget_bytes binds PER device, so the
            # per-device breakdown is the budget-compliance view. With
            # col_blocks > 1 the per-slot unit is a COLUMN SLICE, slots
            # are row-major over the (R, C) grid.
            "mesh_devices": len(self.devices) if self.devices else None,
            "col_blocks": self.col_blocks,
            "col_block_size": (self.col_block_size
                               if self.col_blocks > 1 else None),
            "per_device_bytes": list(self._slot_bytes),
            "per_device_resident_shards": [
                sum(1 for _, u in self._all_units()
                    if u.feats is not None and u.slot == slot)
                for slot in range(self.n_slots)],
        })
        return s
