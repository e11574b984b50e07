"""Parallel sharded ingest (data/shard_planner.py, data/parallel_ingest.py,
data/device_feed.py): worker-count invariance (byte-identical datasets,
values AND row order), graceful fallback without the C decoder, and clean
shard-naming errors on corrupt input instead of a hung pool."""

import numpy as np
import pytest

from photon_ml_tpu.data.avro_reader import (
    read_game_dataset,
    read_labeled_points,
)
from photon_ml_tpu.data.parallel_ingest import (
    IngestShardError,
    parallel_fast_ingest,
    resolve_ingest_workers,
)
from photon_ml_tpu.data.shard_planner import (
    plan_shards,
    scan_container_blocks,
)
from photon_ml_tpu.io import schemas
from photon_ml_tpu.io.avro_codec import write_container


def _write_training_file(path, n, rng, n_features=60, per_row=6,
                         sync_interval=2048):
    """Many-block TrainingExampleAvro file with every optional field
    exercised (null/absent uids, weights, offsets)."""
    recs = []
    for i in range(n):
        cols = rng.choice(n_features, size=per_row, replace=False)
        recs.append({
            "uid": f"u{i}" if i % 3 else None,
            "label": float(i % 2),
            "features": [
                {"name": f"f{c}", "term": "t" if c % 2 else None,
                 "value": float(rng.normal())} for c in cols],
            "weight": 2.0 if i % 5 == 0 else None,
            "offset": 0.25 if i % 7 == 0 else None,
            "metadataMap": {"userId": f"user{i % 13}",
                            "itemId": f"item{i % 31}"},
        })
    write_container(path, schemas.TRAINING_EXAMPLE, recs,
                    sync_interval=sync_interval)
    return recs


@pytest.fixture
def training_file(tmp_path, rng):
    p = tmp_path / "train.avro"
    _write_training_file(p, 3000, rng)
    return p


def _assert_datasets_identical(a, b):
    assert np.array_equal(a.responses, b.responses)
    assert np.array_equal(a.offsets, b.offsets)
    assert np.array_equal(a.weights, b.weights)
    assert a.responses.dtype == b.responses.dtype
    assert (a.uids == b.uids).all()
    assert set(a.feature_shards) == set(b.feature_shards)
    for name in a.feature_shards:
        ma, mb = a.feature_shards[name], b.feature_shards[name]
        assert np.array_equal(ma.data, mb.data)
        assert np.array_equal(ma.indices, mb.indices)
        assert np.array_equal(ma.indptr, mb.indptr)
    assert set(a.id_columns) == set(b.id_columns)
    for t in a.id_columns:
        assert np.array_equal(a.id_columns[t].codes, b.id_columns[t].codes)
        assert np.array_equal(a.id_columns[t].vocabulary,
                              b.id_columns[t].vocabulary)


def test_worker_count_invariance_game_dataset(training_file):
    """Datasets from workers in {1, 2, 4} are byte-identical, row order
    included — the core contract of the parallel path."""
    datasets = {
        w: read_game_dataset(training_file, id_types=["userId", "itemId"],
                             ingest_workers=w)[0]
        for w in (1, 2, 4)}
    _assert_datasets_identical(datasets[1], datasets[2])
    _assert_datasets_identical(datasets[1], datasets[4])


def test_worker_count_invariance_labeled_points(training_file):
    mats, ys, uidss = {}, {}, {}
    imap = None
    for w in (1, 2, 4):
        mat, y, off, weights, uids, imap = read_labeled_points(
            training_file, index_map=imap, ingest_workers=w)
        mats[w], ys[w], uidss[w] = mat, y, uids
    for w in (2, 4):
        assert np.array_equal(ys[1], ys[w])
        assert uidss[1] == uidss[w]
        assert np.array_equal(mats[1].data, mats[w].data)
        assert np.array_equal(mats[1].indices, mats[w].indices)
        assert np.array_equal(mats[1].indptr, mats[w].indptr)


def test_multi_file_order_preserved(tmp_path, rng):
    """Shards never cross files and assemble in file order: two files read
    in parallel equal their single-process concatenation."""
    p1, p2 = tmp_path / "a.avro", tmp_path / "b.avro"
    _write_training_file(p1, 1200, rng)
    _write_training_file(p2, 800, rng)
    d1, maps = read_game_dataset([p1, p2], id_types=["userId"],
                                 ingest_workers=1)
    d2, _ = read_game_dataset([p1, p2], id_types=["userId"],
                              feature_shard_maps=maps, ingest_workers=3)
    _assert_datasets_identical(d1, d2)


def test_fallback_without_native_decoder(training_file, monkeypatch):
    """With the C decoder unavailable, a parallel worker request degrades
    gracefully to the pure-python path — same values, no error."""
    native = read_game_dataset(training_file, id_types=["userId"],
                               ingest_workers=2)[0]

    import photon_ml_tpu.native as nat

    monkeypatch.setattr(nat, "_loaded", True)
    monkeypatch.setattr(nat, "_module", None)
    fallback = read_game_dataset(training_file, id_types=["userId"],
                                 ingest_workers=4)[0]
    _assert_datasets_identical(native, fallback)


def test_corrupt_payload_names_shard(tmp_path, rng):
    """Garbage INSIDE a block payload (structurally valid container, so the
    planner scan passes) fails in the worker and surfaces as a clean
    IngestShardError naming the shard — never a hung pool."""
    p = tmp_path / "bad.avro"
    _write_training_file(p, 3000, rng)
    index = scan_container_blocks(p)
    assert len(index.blocks) >= 4

    from photon_ml_tpu.data.avro_reader import build_index_map

    imap = build_index_map(p, ingest_workers=1)  # before corruption
    raw = bytearray(p.read_bytes())
    block = index.blocks[len(index.blocks) // 2]

    def varint_len(off):
        k = 0
        while raw[off + k] & 0x80:
            k += 1
        return k + 1

    payload_start = block.offset + varint_len(block.offset)
    payload_start += varint_len(payload_start)
    # Clobber deflate bytes mid-payload; sizes and sync stay intact.
    for i in range(8):
        raw[payload_start + 4 + i] ^= 0xFF
    p.write_bytes(bytes(raw))

    with pytest.raises(IngestShardError, match="bad.avro"):
        parallel_fast_ingest(
            [str(p)], {"global": imap},
            {"global": imap.intercept_index}, id_types=["userId"],
            workers=2)


def test_truncated_file_clean_error(tmp_path, rng):
    """A truncated container fails the planner scan with an error naming
    the file and offset (before any worker starts)."""
    p = tmp_path / "trunc.avro"
    _write_training_file(p, 2000, rng)
    raw = p.read_bytes()
    p.write_bytes(raw[:len(raw) // 2])
    with pytest.raises(ValueError, match="trunc.avro"):
        read_game_dataset(p, id_types=["userId"], ingest_workers=2)


def test_shard_planner_covers_all_blocks(training_file):
    index = scan_container_blocks(training_file)
    assert index.num_rows == 3000
    for num_shards in (1, 3, 7, 100):
        shards = plan_shards([index], num_shards)
        assert [s.seq for s in shards] == list(range(len(shards)))
        assert sum(s.num_rows for s in shards) == 3000
        assert sum(s.num_blocks for s in shards) == len(index.blocks)
        assert shards[0].offset == index.blocks[0].offset
        # Consecutive coverage: each shard starts at the block after the
        # previous shard's last block.
        starts = [b.offset for b in index.blocks]
        i = 0
        for s in shards:
            assert s.offset == starts[i]
            i += s.num_blocks
        assert i == len(index.blocks)


def test_auto_mode_declines_tiny_inputs(training_file):
    """In auto mode the pool is skipped below MIN_PARALLEL_BYTES (startup
    would dominate); explicit worker counts still parallelize."""
    from photon_ml_tpu.data.avro_reader import build_index_map

    imap = build_index_map(training_file, ingest_workers=1)
    assert parallel_fast_ingest(
        [str(training_file)], {"global": imap},
        {"global": imap.intercept_index}, workers=4, auto=True) is None
    assert parallel_fast_ingest(
        [str(training_file)], {"global": imap},
        {"global": imap.intercept_index}, workers=2, auto=False) is not None


def test_resolve_ingest_workers():
    assert resolve_ingest_workers(1) == 1
    assert resolve_ingest_workers("4") == 4
    assert resolve_ingest_workers("auto") >= 1
    assert resolve_ingest_workers(None) >= 1
    with pytest.raises(ValueError):
        resolve_ingest_workers(0.5)
    with pytest.raises(ValueError):
        resolve_ingest_workers("-2")


def test_chunked_device_put_matches_monolithic(rng):
    import jax.numpy as jnp
    import scipy.sparse as sp

    from photon_ml_tpu.data.device_feed import chunked_device_put

    x = rng.normal(0, 1, (257, 5)).astype(np.float64)
    whole = jnp.asarray(x, jnp.float32)
    chunked = chunked_device_put(x, jnp.float32, chunk_bytes=4096)
    assert chunked.dtype == whole.dtype
    np.testing.assert_array_equal(np.asarray(chunked), np.asarray(whole))

    m = sp.csr_matrix(x)
    from_sparse = chunked_device_put(m, jnp.float32, chunk_bytes=4096)
    np.testing.assert_array_equal(np.asarray(from_sparse),
                                  np.asarray(whole))
    # Single-put path (below the chunk threshold) is equivalent too.
    small = chunked_device_put(x, jnp.float32)
    np.testing.assert_array_equal(np.asarray(small), np.asarray(whole))


def test_overlapped_uploader_concatenates_in_order(rng):
    import jax.numpy as jnp

    from photon_ml_tpu.data.device_feed import OverlappedUploader

    chunks = [rng.normal(0, 1, (n,)).astype(np.float32)
              for n in (100, 37, 256, 1)]
    up = OverlappedUploader(dtype=jnp.float32)
    for c in chunks:
        up.submit(c)
    out = up.collect()
    np.testing.assert_array_equal(np.asarray(out), np.concatenate(chunks))
    assert up.collect() is None


def test_column_consumer_sees_rows_in_order(training_file):
    from photon_ml_tpu.data.avro_reader import build_index_map

    imap = build_index_map(training_file, ingest_workers=1)
    seen = []
    res = parallel_fast_ingest(
        [str(training_file)], {"global": imap},
        {"global": imap.intercept_index}, workers=2,
        column_consumer=lambda seq, lb, ob, wb: seen.append(
            (seq, np.array(lb))))
    assert res is not None
    assert [s for s, _ in seen] == sorted(s for s, _ in seen)
    np.testing.assert_array_equal(
        np.concatenate([a for _, a in seen]), res.labels)


def test_workers_are_held_to_the_cpu(training_file, monkeypatch):
    """Decoder workers never need a device and the parent may hold the
    one chip: whatever the parent's environment says, they start with
    JAX_PLATFORMS=cpu."""
    import subprocess

    from photon_ml_tpu.data.avro_reader import build_index_map

    imap = build_index_map(training_file, ingest_workers=1)
    monkeypatch.setenv("JAX_PLATFORMS", "tpu")
    real_popen, seen = subprocess.Popen, []

    def recording_popen(*args, **kwargs):
        seen.append(kwargs["env"]["JAX_PLATFORMS"])
        return real_popen(*args, **kwargs)

    monkeypatch.setattr(subprocess, "Popen", recording_popen)
    res = parallel_fast_ingest(
        [str(training_file)], {"global": imap},
        {"global": imap.intercept_index}, workers=2)
    assert res is not None and seen == ["cpu", "cpu"]
