"""Native C Avro decoder (photon_ml_tpu/native/_avro_native.c): bit-exact
equivalence with the pure-python read_datum across schema shapes, plus
graceful fallback."""

import numpy as np
import pytest

from photon_ml_tpu.io import schemas
from photon_ml_tpu.io.avro_codec import (
    Schema,
    compile_schema_program,
    read_container,
    write_container,
)
from photon_ml_tpu.native import load_avro_native

native = load_avro_native()
pytestmark = pytest.mark.skipif(
    native is None, reason="no C compiler available for the native decoder")


def _roundtrip_both(tmp_path, schema, records):
    """Write once; read with the native path and the forced-python path."""
    p = tmp_path / "data.avro"
    write_container(p, schema, records)
    got_native = list(read_container(p))

    import photon_ml_tpu.native as nat

    saved = (nat._loaded, nat._module)
    nat._loaded, nat._module = True, None
    try:
        got_python = list(read_container(p))
    finally:
        nat._loaded, nat._module = saved
    return got_native, got_python


def test_training_examples_equal(tmp_path, rng):
    records = []
    for i in range(500):
        records.append({
            "uid": f"u{i}" if i % 3 else None,
            "label": float(rng.normal()),
            "features": [
                {"name": f"f{j}", "term": "t" if j % 2 else None,
                 "value": float(rng.normal())}
                for j in range(int(rng.integers(0, 8)))],
            "weight": float(rng.random()) if i % 2 else None,
            "offset": None,
            "metadataMap": {"userId": f"user{i % 7}", "k": "v"} if i % 4
            else None,
        })
    a, b = _roundtrip_both(tmp_path, schemas.TRAINING_EXAMPLE, records)
    assert a == b == records


def test_exotic_schema_equal(tmp_path):
    schema = {
        "type": "record", "name": "Exotic", "fields": [
            {"name": "e", "type": {"type": "enum", "name": "Color",
                                   "symbols": ["RED", "GREEN", "BLUE"]}},
            {"name": "fx", "type": {"type": "fixed", "name": "F8",
                                    "size": 8}},
            {"name": "b", "type": "bytes"},
            {"name": "flag", "type": "boolean"},
            {"name": "i", "type": "int"},
            {"name": "l", "type": "long"},
            {"name": "f", "type": "float"},
            {"name": "nested", "type": {"type": "array", "items": {
                "type": "map", "values": ["null", "double", "string"]}}},
        ]}
    records = [
        {"e": "GREEN", "fx": b"12345678", "b": b"\x00\xff", "flag": True,
         "i": -2**31, "l": 2**62 - 1, "f": 1.5,
         "nested": [{"a": None, "b": 3.25}, {}, {"s": "ünicøde"}]},
        {"e": "RED", "fx": b"\x00" * 8, "b": b"", "flag": False,
         "i": 0, "l": -2**62, "f": -0.0, "nested": []},
    ]
    a, b = _roundtrip_both(tmp_path, schema, records)
    assert a == b == records


def test_all_bundled_schemas_compile():
    for name in ("NAME_TERM_VALUE", "TRAINING_EXAMPLE",
                 "BAYESIAN_LINEAR_MODEL", "LATENT_FACTOR", "SCORING_RESULT",
                 "FEATURE_SUMMARIZATION_RESULT"):
        schema = getattr(schemas, name)
        prog = compile_schema_program(Schema(schema).root)
        assert prog is not None, name


def test_truncated_block_raises():
    prog = compile_schema_program(Schema(schemas.NAME_TERM_VALUE).root)
    with pytest.raises(ValueError):
        native.decode_block(b"\x02", 1, prog.prog, prog.root, prog.strings)


def test_trailing_bytes_raise():
    prog = compile_schema_program(Schema("long").root)
    with pytest.raises(ValueError, match="trailing"):
        native.decode_block(b"\x02\x02", 1, prog.prog, prog.root,
                            prog.strings)
    assert native.decode_block(b"\x02\x04", 2, prog.prog, prog.root,
                               prog.strings) == [1, 2]


# The reference's own wire layout: term is a PLAIN string, not a
# [null, string] union, and metadataMap/weight/offset come after features
# (photon-avro-schemas/src/main/avro/TrainingExampleAvro.avsc,
# FeatureAvro.avsc).
REFERENCE_TRAINING_EXAMPLE = {
    "type": "record",
    "name": "TrainingExampleAvro",
    "namespace": "com.linkedin.photon.avro.generated",
    "fields": [
        {"name": "uid", "type": ["null", "string"], "default": None},
        {"name": "label", "type": "double"},
        {"name": "features", "type": {"type": "array", "items": {
            "type": "record", "name": "FeatureAvro", "fields": [
                {"name": "name", "type": "string"},
                {"name": "term", "type": "string"},
                {"name": "value", "type": "double"},
            ]}}},
        {"name": "metadataMap",
         "type": ["null", {"type": "map", "values": "string"}],
         "default": None},
        {"name": "weight", "type": ["null", "double"], "default": None},
        {"name": "offset", "type": ["null", "double"], "default": None},
    ],
}


def _reference_records(n=50):
    return [
        {"uid": f"u{i}", "label": float(i % 2),
         "features": [{"name": "age", "term": "", "value": 1.0 + i},
                      {"name": "f", "term": "t2", "value": -0.5 * i}],
         "metadataMap": {"userId": f"user{i % 3}"},
         "weight": 1.0 + 0.5 * (i % 2), "offset": 0.25 * i}
        for i in range(n)]


def test_reference_layout_plain_string_term(tmp_path):
    """Plain-string terms (the reference layout) must be consumed by the
    native fast path and produce the same matrix as the python path."""
    from photon_ml_tpu.data.avro_reader import (
        build_index_map, read_labeled_points)
    from photon_ml_tpu.data.fast_ingest import fast_ingest

    p = tmp_path / "ref.avro"
    write_container(p, REFERENCE_TRAINING_EXAMPLE, _reference_records())

    imap = build_index_map(p)
    fast = fast_ingest([p], {"m": imap}, {"m": imap.intercept_index},
                       id_types=["userId"])
    assert fast is not None, "native fast path rejected the reference layout"

    mat_n, y_n, off_n, w_n, uids_n, imap_n = read_labeled_points(p)

    import photon_ml_tpu.native as nat

    saved = (nat._loaded, nat._module)
    nat._loaded, nat._module = True, None
    try:
        mat_p, y_p, off_p, w_p, uids_p, imap_p = read_labeled_points(p)
    finally:
        nat._loaded, nat._module = saved

    assert uids_n == uids_p
    np.testing.assert_array_equal(y_n, y_p)
    np.testing.assert_array_equal(off_n, off_p)
    np.testing.assert_array_equal(w_n, w_p)
    np.testing.assert_array_equal(mat_n.toarray(), mat_p.toarray())
    assert fast.ids["userId"].tolist() == [
        r["metadataMap"]["userId"] for r in _reference_records()]


def test_mixed_optional_layouts_across_files(tmp_path):
    """One file with weight/offset fields, one without: rows must stay
    aligned (absent fields default to weight=1, offset=0 per file)."""
    from photon_ml_tpu.data.avro_reader import build_index_map
    from photon_ml_tpu.data.fast_ingest import fast_ingest

    bare_schema = {
        "type": "record", "name": "TrainingExampleAvro", "fields": [
            {"name": "label", "type": "double"},
            {"name": "features", "type": {"type": "array", "items": {
                "type": "record", "name": "FeatureAvro", "fields": [
                    {"name": "name", "type": "string"},
                    {"name": "value", "type": "double"},
                ]}}},
        ]}
    rich = tmp_path / "rich.avro"
    bare = tmp_path / "bare.avro"
    write_container(rich, REFERENCE_TRAINING_EXAMPLE, _reference_records(8))
    write_container(bare, bare_schema, [
        {"label": 10.0 + i,
         "features": [{"name": "age", "value": 2.0}]}
        for i in range(3)])

    imap = build_index_map(rich)
    fast = fast_ingest([rich, bare], {"m": imap},
                       {"m": imap.intercept_index})
    assert fast is not None
    assert len(fast.labels) == 11
    np.testing.assert_array_equal(fast.labels[8:], [10.0, 11.0, 12.0])
    # File-local defaults — no cross-file misalignment.
    np.testing.assert_array_equal(
        fast.offsets[:8], [0.25 * i for i in range(8)])
    np.testing.assert_array_equal(fast.offsets[8:], 0.0)
    np.testing.assert_array_equal(
        fast.weights[:8], [1.0 + 0.5 * (i % 2) for i in range(8)])
    np.testing.assert_array_equal(fast.weights[8:], 1.0)


def test_duplicate_metadata_key_keeps_last(tmp_path):
    """A doubly-present map key (legal on the wire) must not shift id
    alignment; last occurrence wins, matching python dict semantics."""
    import io

    from photon_ml_tpu.data.fast_ingest import build_training_layout
    from photon_ml_tpu.io.avro_codec import Schema, _write_long

    schema = {
        "type": "record", "name": "T", "fields": [
            {"name": "label", "type": "double"},
            {"name": "features", "type": {"type": "array", "items": {
                "type": "record", "name": "F", "fields": [
                    {"name": "name", "type": "string"},
                    {"name": "value", "type": "double"},
                ]}}},
            {"name": "metadataMap",
             "type": ["null", {"type": "map", "values": "string"}]},
        ]}
    layout = build_training_layout(Schema(schema).root)
    assert layout is not None

    def wstr(buf, s):
        b = s.encode()
        _write_long(buf, len(b))
        buf.write(b)

    buf = io.BytesIO()
    buf.write(np.float64(1.0).tobytes())      # label
    _write_long(buf, 0)                        # features: empty array
    _write_long(buf, 1)                        # metadataMap: map branch
    _write_long(buf, 2)                        # one block, two entries
    wstr(buf, "userId"); wstr(buf, "first")
    wstr(buf, "userId"); wstr(buf, "second")
    _write_long(buf, 0)                        # end of map blocks

    (lb, ob, wb, us, shard_out, ids_out) = native.decode_training_block(
        buf.getvalue(), 1, layout.prog, layout.layout,
        ({},), (-1,), ("userId",), "\x01", None)
    assert list(ids_out[0]) == ["second"]
    assert np.frombuffer(lb, np.float64).tolist() == [1.0]


def test_varint_extremes():
    import io

    from photon_ml_tpu.io.avro_codec import _write_long

    vals = [0, 1, -1, 63, -64, 2**63 - 1, -2**63]
    buf = io.BytesIO()
    for v in vals:
        _write_long(buf, v)
    prog = compile_schema_program(Schema("long").root)
    out = native.decode_block(buf.getvalue(), len(vals), prog.prog,
                              prog.root, prog.strings)
    assert out == vals


def test_load_failure_warns_once_per_process(monkeypatch, caplog):
    """A decoder that cannot be built or loaded is not silent: one
    warning, then the pure-python fallback without repeating it."""
    import logging

    import photon_ml_tpu.native as nat

    monkeypatch.setattr(nat, "_loaded", False)
    monkeypatch.setattr(nat, "_module", None)

    def broken_loader(*_a, **_k):
        raise OSError("no such shared object")

    monkeypatch.setattr(nat.importlib.util, "spec_from_file_location",
                        broken_loader)
    with caplog.at_level(logging.WARNING, logger=nat.__name__):
        assert nat.load_avro_native() is None
        assert nat.load_avro_native() is None
    warnings = [r for r in caplog.records if r.levelno == logging.WARNING]
    assert len(warnings) == 1
    assert "pure python" in warnings[0].getMessage()
