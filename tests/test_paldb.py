"""PalDB 1.1 read-only store interop.

The reference's feature-index stores are PalDB (ml/util/PalDBIndexMap.scala:
43-220, built by ml/FeatureIndexingJob.scala:145-174); its GAME integ
fixtures ship pre-built stores. These tests hold the parser to the
reference's own artifacts: full decode of every fixture store, forward /
reverse consistency, partitioned-offset semantics, and the training
driver's --feature-index-dir plumbing.
"""

from pathlib import Path

import numpy as np
import pytest

from photon_ml_tpu.data.index_map import INTERCEPT_KEY, IndexMap, feature_key
from photon_ml_tpu.data.paldb import (
    discover_namespaces,
    java_hash_partition,
    load_feature_index_maps,
    load_paldb_index_map,
    load_paldb_index_maps,
    read_paldb_store,
)

GAME_INPUT = Path(
    "/root/reference/photon-ml/src/integTest/resources/GameIntegTest/input")

pytestmark = pytest.mark.skipif(
    not GAME_INPUT.exists(), reason="reference fixtures not available")


def test_java_hash_partition_matches_java_semantics():
    # Java String.hashCode golden values.
    assert java_hash_partition("", 4) == 0
    # "polygenelubricants".hashCode() == Integer.MIN_VALUE (classic case);
    # Spark nonNegativeMod keeps the partition non-negative.
    for p in (1, 2, 3, 7):
        part = java_hash_partition("polygenelubricants", p)
        assert 0 <= part < p


def test_discover_namespaces():
    assert discover_namespaces(GAME_INPUT / "feature-indexes") == {
        "shard1": 1, "shard2": 1, "shard3": 1}
    assert discover_namespaces(
        GAME_INPUT / "test-with-uid-feature-indexes") == {
        "globalShard": 1, "songShard": 1, "userShard": 1}


def test_store_decodes_fully_and_bidirectionally():
    """Every entry decodes; name->idx and idx->name directions agree
    (PalDBIndexMapBuilder stores both, PalDBIndexMapBuilder.scala:45-49)."""
    store = GAME_INPUT / "feature-indexes" / "paldb-partition-shard1-0.dat"
    fwd, rev = {}, {}
    for k, v in read_paldb_store(store):
        (fwd if isinstance(k, str) else rev)[k] = v
    assert len(fwd) == len(rev) == 15045
    for name, idx in fwd.items():
        assert rev[idx] == name
    assert sorted(fwd.values()) == list(range(15045))


@pytest.mark.parametrize("dirname,expected", [
    ("feature-indexes", {"shard1": 15045, "shard2": 15015, "shard3": 31}),
    ("test-with-uid-feature-indexes",
     {"globalShard": 7234, "songShard": 7204, "userShard": 7204}),
])
def test_fixture_stores_load_as_index_maps(dirname, expected):
    maps = load_paldb_index_maps(GAME_INPUT / dirname)
    assert {ns: len(m) for ns, m in maps.items()} == expected
    for ns, m in maps.items():
        # The reference's key convention (name + \x01 + term) means the
        # intercept key resolves directly.
        assert m.intercept_index >= 0
        assert m.get_index(INTERCEPT_KEY) == m.intercept_index
        # Round-trip: every key looks up to its index and back.
        for key, idx in m.key_items():
            assert m.get_index(key) == idx
            assert m.get_feature_name(idx) == key
        # Indices are a clean 0..n-1 range (offset semantics validated
        # inside the loader as well).
        assert m.get_index("no-such-feature\x01") == -1


def test_partition_offsets_match_reference_semantics(monkeypatch, tmp_path):
    """Multi-partition layout: global idx = internal idx + cumulative
    feature count of earlier partitions, in partition order
    (PalDBIndexMap.load, :71-100). The fixtures are single-partition, so
    synthesize a 2-partition store: split fixture keys with the
    reference's hash partitioner, re-number each partition's internal
    indices from 0 (exactly what FeatureIndexingJob produces), and serve
    the two synthetic stores through read_paldb_store."""
    import photon_ml_tpu.data.paldb as paldb_mod

    src = load_paldb_index_map(GAME_INPUT / "feature-indexes", "shard3", 1)
    keys = sorted(k for k, _ in src.key_items())
    parts = {0: [], 1: []}
    for k in keys:
        parts[java_hash_partition(k, 2)].append(k)
    assert parts[0] and parts[1]  # both partitions populated

    def fake_store(path):
        name = Path(path).name
        part = int(name.rsplit("-", 1)[1].split(".")[0])
        assert name.startswith("paldb-partition-shard3-")
        for internal, k in enumerate(parts[part]):
            yield k, internal          # name -> internal idx
            yield internal, k          # idx -> name (reverse direction)

    monkeypatch.setattr(paldb_mod, "read_paldb_store", fake_store)
    m = paldb_mod.load_paldb_index_map(tmp_path, "shard3", 2)
    # Partition 0 keys keep their internal indices; partition 1 keys are
    # offset by len(partition 0) — the reference's cumulative-offset rule.
    for internal, k in enumerate(parts[0]):
        assert m.get_index(k) == internal
    for internal, k in enumerate(parts[1]):
        assert m.get_index(k) == internal + len(parts[0])
    assert len(m) == len(keys)

    # A key planted in the WRONG partition must fail the hash validation,
    # never silently mis-index.
    swapped = {0: parts[1], 1: parts[0]}

    def wrong_store(path):
        part = int(Path(path).name.rsplit("-", 1)[1].split(".")[0])
        for internal, k in enumerate(swapped[part]):
            yield k, internal

    monkeypatch.setattr(paldb_mod, "read_paldb_store", wrong_store)
    with pytest.raises(ValueError, match="hashes to partition"):
        paldb_mod.load_paldb_index_map(tmp_path, "shard3", 2)


def test_load_feature_index_maps_both_formats(tmp_path):
    # PalDB format
    maps = load_feature_index_maps(GAME_INPUT / "feature-indexes")
    assert set(maps) == {"shard1", "shard2", "shard3"}
    # JSON format (this package's own stores)
    m = IndexMap({feature_key("a"): 0, feature_key("b"): 1})
    m.save(tmp_path / "myShard.json")
    maps2 = load_feature_index_maps(tmp_path)
    assert set(maps2) == {"myShard"}
    assert maps2["myShard"].get_index(feature_key("b")) == 1


def test_training_driver_accepts_feature_index_dir(tmp_path):
    """--feature-index-dir pointing at reference PalDB stores drives a real
    (tiny) GAME training run with the preloaded index space."""

    from photon_ml_tpu.cli.game_training_driver import run as train_run
    from photon_ml_tpu.data.paldb import load_paldb_index_map
    from photon_ml_tpu.io import schemas
    from photon_ml_tpu.io.avro_codec import write_container
    from photon_ml_tpu.data.index_map import split_key

    imap = load_paldb_index_map(GAME_INPUT / "feature-indexes", "shard3", 1)
    keys = [k for k, _ in imap.key_items() if k != INTERCEPT_KEY][:6]
    rng = np.random.default_rng(0)
    records = []
    for i in range(40):
        feats = []
        for k in rng.choice(len(keys), size=3, replace=False):
            name, term = split_key(keys[int(k)])
            feats.append({"name": name, "term": term,
                          "value": float(rng.normal())})
        records.append({
            "uid": f"u{i}", "label": float(rng.integers(0, 2)),
            "features": feats, "weight": 1.0, "offset": 0.0,
            "metadataMap": {"userId": f"user{i % 5}"}})
    data_dir = tmp_path / "train"
    data_dir.mkdir()
    write_container(data_dir / "part-0.avro",
                    schemas.TRAINING_EXAMPLE, records)

    out = train_run([
        "--train-input-dirs", str(data_dir),
        "--output-dir", str(tmp_path / "out"),
        "--task-type", "LOGISTIC_REGRESSION",
        "--feature-index-dir", str(GAME_INPUT / "feature-indexes"),
        "--fixed-effect-data-configurations", "fixed:shard3",
        "--fixed-effect-optimization-configurations",
        "fixed:10,1e-4,1.0,1,LBFGS,L2",
        "--updating-sequence", "fixed",
        "--num-iterations", "1",
    ])
    assert out["numRows"] == 40
    # The model was trained in the PalDB store's 31-feature index space.
    model_txt = list((tmp_path / "out" / "best").rglob("*.avro"))
    assert model_txt, "saved model artifacts missing"


def test_glm_driver_accepts_offheap_indexmap_dir(tmp_path):
    """--offheap-indexmap-dir (the reference's OFFHEAP_INDEXMAP_DIR flag)
    trains a GLM in a reference PalDB store's index space."""
    from photon_ml_tpu.cli.glm_driver import run as glm_run
    from photon_ml_tpu.data.index_map import split_key
    from photon_ml_tpu.data.paldb import load_paldb_index_map
    from photon_ml_tpu.io import schemas
    from photon_ml_tpu.io.avro_codec import write_container

    imap = load_paldb_index_map(GAME_INPUT / "feature-indexes", "shard3", 1)
    keys = [k for k, _ in imap.key_items() if k != INTERCEPT_KEY][:6]
    rng = np.random.default_rng(1)
    records = []
    for i in range(60):
        feats = []
        for k in rng.choice(len(keys), size=3, replace=False):
            name, term = split_key(keys[int(k)])
            feats.append({"name": name, "term": term,
                          "value": float(rng.normal())})
        records.append({"uid": f"u{i}", "label": float(rng.integers(0, 2)),
                        "features": feats, "weight": None, "offset": None,
                        "metadataMap": None})
    data_dir = tmp_path / "train"
    data_dir.mkdir()
    write_container(data_dir / "part-0.avro", schemas.TRAINING_EXAMPLE,
                    records)

    out = glm_run([
        "--training-data-directory", str(data_dir),
        "--output-directory", str(tmp_path / "out"),
        "--task", "LOGISTIC_REGRESSION",
        "--offheap-indexmap-dir", str(GAME_INPUT / "feature-indexes"),
        "--offheap-indexmap-namespace", "shard3",
        "--regularization-weights", "1.0",
        "--max-num-iterations", "15",
    ])
    assert out["numRows"] == 60
    # The model text lists coefficients in the PalDB store's 31-feature
    # index space (intercept included).
    model_txt = (tmp_path / "out" / "best-model" / "model.txt").read_text()
    assert "(INTERCEPT)" in model_txt


# ---------------------------------------------------------------------------
# Writer: write -> read round trip + layout parity
# with the reference's own fixture structure.
# ---------------------------------------------------------------------------


def test_write_store_round_trips(tmp_path):
    from photon_ml_tpu.data.paldb import write_paldb_store

    pairs = [("a\x01t", 0), (0, "a\x01t"), ("b\x01", 1), (1, "b\x01"),
             ("long-feature-name\x01with-term", 300),
             (300, "long-feature-name\x01with-term"),
             ("i9", 9), (9, "i9"), ("i255", 255), (255, "i255"),
             ("unicode-é中", 70000), (70000, "unicode-é中")]
    path = tmp_path / "paldb-partition-t-0.dat"
    write_paldb_store(path, pairs)
    got = dict(read_paldb_store(path))
    assert got == dict(pairs)


def test_write_store_multibyte_offsets(tmp_path):
    """Enough entries in one key-length class that data offsets need
    multi-byte varints (the slot size grows accordingly)."""
    from photon_ml_tpu.data.paldb import write_paldb_store

    pairs = [(f"f{i:04d}\x01term-{i:04d}", i) for i in range(2000)]
    path = tmp_path / "big.dat"
    write_paldb_store(path, pairs)
    got = dict(read_paldb_store(path))
    assert len(got) == 2000
    assert got["f1999\x01term-1999"] == 1999


def test_write_store_rejects_duplicates_allows_empty(tmp_path):
    from photon_ml_tpu.data.paldb import write_paldb_store

    with pytest.raises(ValueError, match="duplicate"):
        write_paldb_store(tmp_path / "d.dat", [("a", 1), ("a", 2)])
    # An empty store is legal — hash partitions can be empty and the
    # 0..N-1 filename scan still needs the file to exist.
    write_paldb_store(tmp_path / "e.dat", [])
    assert list(read_paldb_store(tmp_path / "e.dat")) == []


@pytest.mark.parametrize("num_partitions", [1, 3])
def test_build_index_stores_round_trip(tmp_path, num_partitions):
    from photon_ml_tpu.data.paldb import build_paldb_index_stores

    names = [feature_key(f"name{i}", f"t{i % 4}") for i in range(50)]
    names.append(INTERCEPT_KEY)
    written = build_paldb_index_stores(tmp_path, "myShard", names,
                                       num_partitions=num_partitions)
    loaded = load_paldb_index_map(tmp_path, "myShard", num_partitions)
    assert dict(written.key_items()) == dict(loaded.key_items())
    assert sorted(i for _, i in loaded.key_items()) == list(range(len(names)))


def test_written_store_layout_matches_fixture_structure(tmp_path):
    """Re-write the reference fixture's CONTENT with our writer and
    compare the container structure field by field: same sections (key
    lengths, counts), same slot counts (Math.round(count/0.75)), same
    slot sizes, same empty-slot/data-sentinel conventions. Byte identity
    is not expected (insertion order differs), but every structural
    header field the PalDB 1.1 reader navigates by must match."""
    import struct as st

    from photon_ml_tpu.data.paldb import write_paldb_store

    fixture = (Path("/root/reference/photon-ml/src/test/resources/"
                    "PalDBIndexMapTest/paldb_offheapmap_for_heart") /
               "paldb-partition-global-0.dat")

    def header_fields(path):
        raw = Path(path).read_bytes()
        n_magic = st.unpack_from(">H", raw, 0)[0]
        o = 2 + n_magic + 8
        key_count, klc, mkl = st.unpack_from(">iii", raw, o)
        o += 12
        secs = []
        for _ in range(klc):
            klen, kcnt, slots, ssize, _io = st.unpack_from(">iiiii", raw, o)
            o += 28
            secs.append((klen, kcnt, slots, ssize))
        return key_count, mkl, secs

    pairs = list(read_paldb_store(fixture))
    ours = tmp_path / "rewrite.dat"
    write_paldb_store(ours, pairs)

    ref_kc, ref_mkl, ref_secs = header_fields(fixture)
    our_kc, our_mkl, our_secs = header_fields(ours)
    assert our_kc == ref_kc
    assert our_mkl == ref_mkl
    assert our_secs == ref_secs
    # And the rewrite round-trips to identical content.
    assert dict(read_paldb_store(ours)) == dict(pairs)


def test_slot_hash_matches_fixture_placement():
    """The writer's murmur3(seed 42) slot hash reproduces the placement
    observed in the reference's own stores: every key sits at its hash
    slot or within linear-probe distance of it."""
    import struct as st

    from photon_ml_tpu.data.paldb import (
        _MAGIC,
        _murmur3_32,
        _unpack_varint,
    )

    fixture = GAME_INPUT / "feature-indexes" / "paldb-partition-shard1-0.dat"
    raw = fixture.read_bytes()
    n_magic = st.unpack_from(">H", raw, 0)[0]
    assert raw[2:2 + n_magic].decode() == _MAGIC
    o = 2 + n_magic + 8
    key_count, klc, _ = st.unpack_from(">iii", raw, o)
    o += 12
    secs = []
    for _ in range(klc):
        klen, kcnt, slots, ssize, ioff = st.unpack_from(">iiiii", raw, o)
        o += 28
        secs.append((klen, kcnt, slots, ssize, ioff))
    o += 4
    index_start = st.unpack_from(">i", raw, o)[0]

    exact = probed = 0
    for klen, kcnt, slots, ssize, ioff in secs:
        base = index_start + ioff
        occupancy = kcnt / slots
        for s in range(slots):
            slot = raw[base + s * ssize: base + (s + 1) * ssize]
            if _unpack_varint(slot, klen)[0] == 0:
                continue
            h = _murmur3_32(bytes(slot[:klen])) % slots
            dist = (s - h) % slots
            if dist == 0:
                exact += 1
            else:
                probed += 1
                assert dist <= kcnt, "key unreachable by linear probing"
    assert exact + probed == key_count
    # The hash must explain the bulk of placements directly (collisions
    # at 0.75 load factor account for the rest).
    assert exact / key_count > 0.5
