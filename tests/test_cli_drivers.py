"""End-to-end CLI driver tests — the analog of the reference's DriverTest
(1034 LoC) and cli/game/*/DriverTest integration suites, on generated Avro
fixtures instead of checked-in ones.
"""

import json

import numpy as np
import pytest

from photon_ml_tpu.cli import (  # noqa: F401  (import check)
    feature_indexing,
    game_scoring_driver,
    game_training_driver,
    glm_driver,
)
from photon_ml_tpu.io import schemas
from photon_ml_tpu.io.avro_codec import read_container, write_container
from photon_ml_tpu.utils.events import (
    EventListener,
    PhotonOptimizationLogEvent,
    TrainingFinishEvent,
    TrainingStartEvent,
)


def _write_glm_avro(path, rng, n=200, d=5, poisson=False, w=None):
    if w is None:
        w = rng.normal(0, 1, d + 1)
    records = []
    for i in range(n):
        idx = rng.choice(d, size=rng.integers(1, d + 1), replace=False)
        vals = rng.normal(0, 1, len(idx))
        z = float(vals @ w[idx] + w[-1])
        if poisson:
            label = float(rng.poisson(np.exp(np.clip(z, -5, 3))))
        else:
            label = float(rng.random() < 1 / (1 + np.exp(-z)))
        records.append({
            "uid": f"u{i}", "label": label,
            "features": [{"name": f"f{j}", "term": None, "value": float(v)}
                         for j, v in zip(idx, vals)],
            "weight": None, "offset": None, "metadataMap": None})
    path.mkdir(parents=True, exist_ok=True)
    write_container(path / "part-00000.avro", schemas.TRAINING_EXAMPLE,
                    records)


def _write_game_avro(path, rng, n=300, n_users=10, params=None):
    if params is None:
        user_bias = rng.normal(0, 1.5, n_users)
        w = rng.normal(0, 1, 3)
    else:
        user_bias, w = params
    records = []
    for i in range(n):
        u = int(rng.integers(0, n_users))
        x = rng.normal(0, 1, 3)
        z = float(x @ w + user_bias[u])
        records.append({
            "uid": f"r{i}", "label": float(rng.random() < 1 / (1 + np.exp(-z))),
            "features": [{"name": f"x{j}", "term": None, "value": float(v)}
                         for j, v in enumerate(x)],
            "weight": None, "offset": None,
            "metadataMap": {"userId": f"user{u}"}})
    path.mkdir(parents=True, exist_ok=True)
    write_container(path / "part-00000.avro", schemas.TRAINING_EXAMPLE,
                    records)


def test_glm_driver_avro_end_to_end(tmp_path, rng):
    train = tmp_path / "train"
    valid = tmp_path / "valid"
    w_true = rng.normal(0, 1, 6)
    _write_glm_avro(train, rng, n=300, w=w_true)
    _write_glm_avro(valid, rng, n=100, w=w_true)
    out = tmp_path / "out"
    summary = glm_driver.run([
        "--training-data-directory", str(train),
        "--validating-data-directory", str(valid),
        "--output-directory", str(out),
        "--task", "LOGISTIC_REGRESSION",
        "--regularization-weights", "10,1,0.1",
        "--max-num-iterations", "60",
        "--dtype", "float64",
    ])
    assert summary["stages"] == ["INIT", "PREPROCESSED", "TRAINED",
                                 "VALIDATED"]
    assert summary["bestLambda"] in (10.0, 1.0, 0.1)
    assert summary["device"]["platform"] == "cpu"
    assert (out / "best-model" / "model.txt").exists()
    assert (out / "best-model" / "model.avro").exists()
    assert (out / "log-message.txt").exists()
    # validation-metrics.json shape: {"metrics": {λ: {...}},
    # "metricMetadata": {name: {...}}}
    vm = json.loads((out / "validation-metrics.json").read_text())
    assert set(vm) == {"metrics", "metricMetadata"}
    assert set(vm["metrics"]) == {"10.0", "1.0", "0.1"}
    assert vm["metrics"][str(summary["bestLambda"])]["AUC"] > 0.6
    assert vm["metricMetadata"]["AUC"]["higherIsBetter"] is True
    assert vm["metricMetadata"]["AUC"]["range"] == [0.0, 1.0]
    # text model format: 4 tab-separated columns
    line = (out / "best-model" / "model.txt").read_text().splitlines()[0]
    assert len(line.split("\t")) == 4
    # AUC should beat random on in-distribution validation data
    metrics = summary["validationMetrics"][str(summary["bestLambda"])]
    assert metrics["AUC"] > 0.6
    # all three lambdas produced models
    assert len(list((out / "all-models").iterdir())) == 3


def test_glm_driver_libsvm_tron_poisson(tmp_path, rng):
    # LIBSVM ingest + TRON + linear regression path
    f = tmp_path / "train" / "data.libsvm"
    f.parent.mkdir()
    lines = []
    w = rng.normal(0, 1, 4)
    for _ in range(150):
        x = rng.normal(0, 1, 4)
        y = x @ w + rng.normal(0, 0.1)
        feats = " ".join(f"{j+1}:{x[j]:.5f}" for j in range(4))
        lines.append(f"{y:.5f} {feats}")
    f.write_text("\n".join(lines) + "\n")
    out = tmp_path / "out"
    summary = glm_driver.run([
        "--training-data-directory", str(f.parent),
        "--output-directory", str(out),
        "--task", "LINEAR_REGRESSION",
        "--format", "LIBSVM",
        "--optimizer", "TRON",
        "--regularization-weights", "0.01",
        "--dtype", "float64",
    ])
    conv = summary["convergence"]["0.01"]
    assert conv["finalObjective"] < 10.0  # near-noise-floor fit


def test_glm_driver_normalization_and_constraints(tmp_path, rng):
    train = tmp_path / "train"
    _write_glm_avro(train, rng, n=200)
    out = tmp_path / "out"
    constraints = json.dumps([
        {"name": "*", "term": "*", "lowerBound": -0.5, "upperBound": 0.5}])
    summary = glm_driver.run([
        "--training-data-directory", str(train),
        "--output-directory", str(out),
        "--task", "LOGISTIC_REGRESSION",
        "--normalization-type", "STANDARDIZATION",
        "--coefficient-box-constraints", constraints,
        "--regularization-weights", "1",
        "--dtype", "float64",
    ])
    assert "TRAINED" in summary["stages"]


def test_compile_cache_goes_where_the_environment_says(monkeypatch,
                                                        tmp_path):
    """JAX_COMPILATION_CACHE_DIR decides when set — no directory is set
    in code — and otherwise the cache is <checkout>/.jax_cache."""
    import pathlib

    import jax

    from photon_ml_tpu.utils.compile_cache import enable_compile_cache

    updates = {}
    monkeypatch.setattr(jax.config, "update", updates.__setitem__)
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert enable_compile_cache() == str(tmp_path)
    assert "jax_compilation_cache_dir" not in updates
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    checkout = pathlib.Path(__file__).resolve().parents[1]
    assert enable_compile_cache() == str(checkout / ".jax_cache")
    assert updates["jax_compilation_cache_dir"] == str(checkout / ".jax_cache")


def test_game_pipeline_train_then_score(tmp_path, rng):
    train = tmp_path / "train"
    valid = tmp_path / "valid"
    params = (rng.normal(0, 1.5, 10), rng.normal(0, 1, 3))
    _write_game_avro(train, rng, n=400, params=params)
    _write_game_avro(valid, rng, n=150, params=params)
    out = tmp_path / "game-out"

    summary = game_training_driver.run([
        "--train-input-dirs", str(train),
        "--validate-input-dirs", str(valid),
        "--output-dir", str(out),
        "--task-type", "LOGISTIC_REGRESSION",
        "--fixed-effect-data-configurations", "fixed:global",
        "--fixed-effect-optimization-configurations",
        "fixed:30,1e-7,1.0,1.0,LBFGS,L2",
        "--random-effect-data-configurations",
        "perUser:userId,global,4,-1,-1,-1",
        "--random-effect-optimization-configurations",
        "perUser:20,1e-7,1.0,1.0,LBFGS,L2",
        "--updating-sequence", "fixed,perUser",
        "--num-iterations", "2",
        "--evaluators", "AUC,LOGISTIC_LOSS",
    ])
    assert summary["numCombos"] == 1
    assert len(summary["validationHistory"]) == 2
    assert summary["validationHistory"][-1]["AUC"] > 0.6
    # No number is read without its device (8 virtual CPU devices here).
    cpu8 = {"platform": "cpu", "kind": "cpu", "count": 8}
    assert json.loads((out / "metrics.json").read_text())["device"] == cpu8
    assert (out / "best" / "model-metadata.json").exists()
    assert (out / "best" / "feature-indexes" / "global.json").exists()

    score_out = tmp_path / "score-out"
    score_summary = game_scoring_driver.run([
        "--input-dirs", str(valid),
        "--game-model-input-dir", str(out / "best"),
        "--output-dir", str(score_out),
        "--evaluators", "AUC",
    ])
    assert score_summary["numRows"] == 150
    assert json.loads(
        (score_out / "metrics.json").read_text())["device"] == cpu8
    # Scoring the same validation data reproduces the training-time AUC.
    np.testing.assert_allclose(
        score_summary["metrics"]["AUC"],
        summary["validationHistory"][-1]["AUC"], atol=1e-9)
    scored = list(read_container(score_out / "scores" / "part-00000.avro"))
    assert len(scored) == 150
    assert {"uid", "predictionScore", "label"} <= set(scored[0])


def _train_small_game(tmp_path, rng, n_train=300, n_valid=140):
    train = tmp_path / "train"
    valid = tmp_path / "valid"
    params = (rng.normal(0, 1.5, 10), rng.normal(0, 1, 3))
    _write_game_avro(train, rng, n=n_train, params=params)
    _write_game_avro(valid, rng, n=n_valid, params=params)
    out = tmp_path / "game-out"
    game_training_driver.run([
        "--train-input-dirs", str(train),
        "--output-dir", str(out),
        "--task-type", "LOGISTIC_REGRESSION",
        "--fixed-effect-data-configurations", "fixed:global",
        "--fixed-effect-optimization-configurations",
        "fixed:20,1e-7,1.0,1.0,LBFGS,L2",
        "--random-effect-data-configurations",
        "perUser:userId,global,4,-1,-1,-1",
        "--random-effect-optimization-configurations",
        "perUser:15,1e-7,1.0,1.0,LBFGS,L2",
        "--updating-sequence", "fixed,perUser",
        "--num-iterations", "1",
    ])
    return out / "best", valid


def test_game_scoring_stream_matches_batch(tmp_path, rng):
    """--stream --batch-rows N (bounded-memory serving-engine path) must
    reproduce the one-shot scoring run: same Avro score records, same
    metrics — padded batch boundaries never leak into output."""
    model_dir, valid = _train_small_game(tmp_path, rng)

    batch_out = tmp_path / "score-batch"
    batch = game_scoring_driver.run([
        "--input-dirs", str(valid),
        "--game-model-input-dir", str(model_dir),
        "--output-dir", str(batch_out),
        "--evaluators", "AUC,LOGISTIC_LOSS",
    ])
    stream_out = tmp_path / "score-stream"
    stream = game_scoring_driver.run([
        "--input-dirs", str(valid),
        "--game-model-input-dir", str(model_dir),
        "--output-dir", str(stream_out),
        "--evaluators", "AUC,LOGISTIC_LOSS",
        "--stream", "--batch-rows", "33",  # uneven: forces partial batch
    ])
    assert stream["numRows"] == batch["numRows"] == 140
    assert batch["scoringPath"] == "device"  # snapshot models device-score
    assert stream["scoringPath"] == "streaming-engine"
    assert stream["numBatches"] == 5  # ceil(140 / 33)
    for name, v in batch["metrics"].items():
        np.testing.assert_allclose(stream["metrics"][name], v, atol=1e-9)
    recs_b = list(read_container(batch_out / "scores" / "part-00000.avro"))
    recs_s = list(read_container(stream_out / "scores" / "part-00000.avro"))
    assert [r["uid"] for r in recs_s] == [r["uid"] for r in recs_b]
    np.testing.assert_allclose(
        [r["predictionScore"] for r in recs_s],
        [r["predictionScore"] for r in recs_b], rtol=1e-9, atol=1e-12)
    # engine telemetry rode along: compile cache stayed small
    assert stream["engine"]["compilations"] <= \
        stream["engine"]["dispatches"]
    # feeder telemetry: decode path + bounded residency (prefetch default 2)
    feeder = stream["feeder"]
    assert feeder["decode_path"] in ("native", "python")
    assert feeder["batches"] == 5
    assert feeder["rows"] == 140
    assert feeder["peak_resident_batches"] <= feeder["prefetch_depth"] + 2

    # The forced-python feeder (no prefetch) writes the SAME bytes — the
    # decode path can never change a score.
    py_out = tmp_path / "score-stream-py"
    py = game_scoring_driver.run([
        "--input-dirs", str(valid),
        "--game-model-input-dir", str(model_dir),
        "--output-dir", str(py_out),
        "--stream", "--batch-rows", "33",
        "--feeder", "python", "--prefetch-batches", "0",
    ])
    assert py["feeder"]["decode_path"] == "python"
    recs_p = list(read_container(py_out / "scores" / "part-00000.avro"))
    assert [(r["uid"], r["predictionScore"]) for r in recs_p] == \
        [(r["uid"], r["predictionScore"]) for r in recs_s]


def test_game_scoring_host_fallback_on_unsupported_model(
        tmp_path, rng, monkeypatch):
    """A model family the device scorer rejects — the TYPED
    UnsupportedSubModelError contract — must fall back to host numpy
    scoring, not crash the driver."""
    model_dir, valid = _train_small_game(tmp_path, rng, n_train=200,
                                         n_valid=60)
    from photon_ml_tpu.models import device_scoring
    from photon_ml_tpu.serving.kernels import UnsupportedSubModelError

    def boom(*a, **kw):
        raise UnsupportedSubModelError("synthetic: unsupported sub-model")

    monkeypatch.setattr(device_scoring, "DeviceGameScorer", boom)
    out = tmp_path / "score-fallback"
    summary = game_scoring_driver.run([
        "--input-dirs", str(valid),
        "--game-model-input-dir", str(model_dir),
        "--output-dir", str(out),
        "--evaluators", "AUC",
    ])
    assert summary["numRows"] == 60
    assert summary["scoringPath"] == "host"
    assert (out / "scores" / "part-00000.avro").exists()


def test_game_scoring_engine_bug_surfaces(tmp_path, rng, monkeypatch):
    """Satellite regression: the host fallback is RESTRICTED to the
    documented unsupported-sub-model case — an injected bare TypeError
    out of the engine (a real bug) must surface, never silently degrade
    to host scoring."""
    model_dir, valid = _train_small_game(tmp_path, rng, n_train=200,
                                         n_valid=60)
    from photon_ml_tpu.models import device_scoring

    def boom(*a, **kw):
        raise TypeError("synthetic: engine bug, not the documented "
                        "unsupported-sub-model contract")

    monkeypatch.setattr(device_scoring, "DeviceGameScorer", boom)
    with pytest.raises(TypeError, match="engine bug"):
        game_scoring_driver.run([
            "--input-dirs", str(valid),
            "--game-model-input-dir", str(model_dir),
            "--output-dir", str(tmp_path / "score-bug"),
        ])


def test_game_scoring_serve_matches_batch(tmp_path, rng):
    """Tier-1 smoke for the async front-end CLI mode: --serve replays
    the input as concurrent coalesced requests (python feeder, so it
    runs everywhere) and must reproduce the one-shot scores exactly, in
    order, with the frontend telemetry block in metrics.json."""
    model_dir, valid = _train_small_game(tmp_path, rng)

    batch_out = tmp_path / "score-batch"
    batch = game_scoring_driver.run([
        "--input-dirs", str(valid),
        "--game-model-input-dir", str(model_dir),
        "--output-dir", str(batch_out),
        "--evaluators", "AUC",
    ])
    serve_out = tmp_path / "score-serve"
    serve = game_scoring_driver.run([
        "--input-dirs", str(valid),
        "--game-model-input-dir", str(model_dir),
        "--output-dir", str(serve_out),
        "--evaluators", "AUC",
        "--serve", "--request-rows", "7", "--serve-concurrency", "8",
        "--coalesce-ms", "1", "--feeder", "python",
    ])
    assert serve["num_rows"] == batch["numRows"] == 140
    assert serve["scoring_path"] == "async-frontend"
    assert serve["num_requests"] == 20  # ceil(140 / 7)
    np.testing.assert_allclose(serve["metrics"]["AUC"],
                               batch["metrics"]["AUC"], atol=1e-9)
    recs_b = list(read_container(batch_out / "scores" / "part-00000.avro"))
    recs_s = list(read_container(serve_out / "scores" / "part-00000.avro"))
    assert [r["uid"] for r in recs_s] == [r["uid"] for r in recs_b]
    np.testing.assert_allclose(
        [r["predictionScore"] for r in recs_s],
        [r["predictionScore"] for r in recs_b], rtol=1e-9, atol=1e-12)
    fe = serve["frontend"]
    assert fe["admitted"] == fe["completed"] == 20
    assert fe["rejected"] == 0
    assert fe["engines"]["default"]["requests"] == 20
    # coalescing happened: fewer device dispatches than requests
    assert fe["engines"]["default"]["dispatches"] <= 20
    # per-request latency telemetry populated (driver enables telemetry)
    assert fe["request_latency_seconds"]["count"] == 20
    assert fe["queue_wait_seconds"]["count"] == 20

    with pytest.raises(SystemExit, match="mutually exclusive"):
        game_scoring_driver.run([
            "--input-dirs", str(valid),
            "--game-model-input-dir", str(model_dir),
            "--output-dir", str(tmp_path / "score-both"),
            "--serve", "--stream",
        ])


def test_game_scoring_listen_network_front_door(tmp_path, rng):
    """--listen opens the framed network front door over the serving
    front-end: requests over BOTH framings (length-prefixed binary and
    HTTP/1.1 JSON) score byte-identically to each other, reproduce the
    one-shot batch run, and the summary carries the netserver report.
    The driver runs in a thread (it owns its own event loop); the test
    is the network client."""
    import asyncio
    import threading
    import time

    from photon_ml_tpu.data.avro_reader import iter_game_dataset_batches
    from photon_ml_tpu.data.paldb import load_feature_index_maps
    from photon_ml_tpu.serving.netserver import NetClient

    model_dir, valid = _train_small_game(tmp_path, rng, n_train=200,
                                         n_valid=40)
    batch_out = tmp_path / "score-batch"
    batch = game_scoring_driver.run([
        "--input-dirs", str(valid),
        "--game-model-input-dir", str(model_dir),
        "--output-dir", str(batch_out),
    ])
    assert batch["numRows"] == 40
    want = [r["predictionScore"] for r in
            read_container(batch_out / "scores" / "part-00000.avro")]

    # Build the wire requests the way the driver's serve replay does:
    # featureized batches split into fixed-row requests.
    shard_maps = load_feature_index_maps(model_dir / "feature-indexes")
    requests = []
    for ds in iter_game_dataset_batches(
            [valid], id_types=["userId"], feature_shard_maps=shard_maps,
            batch_rows=64, feeder="python"):
        for a in range(0, ds.num_rows, 8):
            requests.append(ds.subset(
                np.arange(a, min(a + 8, ds.num_rows))))
    assert len(requests) == 5

    listen_out = tmp_path / "score-listen"
    result = {}

    def drive():
        result["summary"] = game_scoring_driver.run([
            "--input-dirs", str(valid),
            "--game-model-input-dir", str(model_dir),
            "--output-dir", str(listen_out),
            "--listen", "127.0.0.1:0", "--serve-seconds", "8",
            "--coalesce-ms", "1",
        ])

    t = threading.Thread(target=drive)
    t.start()
    try:
        port_file = listen_out / "net_port"
        deadline = time.time() + 60
        while not port_file.exists() and time.time() < deadline:
            time.sleep(0.05)
        assert port_file.exists(), "--listen never published net_port"
        port = int(port_file.read_text())

        async def client():
            async with NetClient("127.0.0.1", port) as c:
                got_b = [await c.score(r) for r in requests]
            async with NetClient("127.0.0.1", port,
                                 framing="http") as c:
                got_h = [await c.score(r) for r in requests]
            return got_b, got_h

        got_b, got_h = asyncio.run(client())
    finally:
        t.join(timeout=60)
    assert not t.is_alive()

    bin_scores = np.concatenate(got_b)
    # The two framings return the SAME BYTES (JSON float repr
    # round-trips doubles exactly).
    assert bin_scores.tobytes() == np.concatenate(got_h).tobytes()
    offsets = np.concatenate([np.asarray(r.offsets) for r in requests])
    np.testing.assert_allclose(bin_scores + offsets, want,
                               rtol=1e-9, atol=1e-9)

    summary = result["summary"]
    assert summary["scoring_path"] == "netserver"
    assert summary["listen"] == "127.0.0.1:0"
    net = summary["net"]
    assert net["requests_binary"] == 5 and net["requests_http"] == 5
    assert net["responses"] == 10 and net["wire_errors"] == {}
    fe = summary["frontend"]
    assert fe["admitted"] == fe["completed"] == 10
    assert fe["rejected"] == 0


def test_game_scoring_listen_flag_validation(tmp_path):
    with pytest.raises(SystemExit, match="pass --listen"):
        game_scoring_driver.run([
            "--input-dirs", str(tmp_path),
            "--game-model-input-dir", str(tmp_path),
            "--output-dir", str(tmp_path / "out"),
            "--adaptive-admission",
        ])
    with pytest.raises(SystemExit, match="at least one --slo"):
        game_scoring_driver.run([
            "--input-dirs", str(tmp_path),
            "--game-model-input-dir", str(tmp_path),
            "--output-dir", str(tmp_path / "out"),
            "--listen", ":0", "--adaptive-admission",
        ])


def test_game_training_grid_selects_best(tmp_path, rng):
    train = tmp_path / "train"
    valid = tmp_path / "valid"
    params = (rng.normal(0, 1.5, 10), rng.normal(0, 1, 3))
    _write_game_avro(train, rng, n=250, params=params)
    _write_game_avro(valid, rng, n=100, params=params)
    out = tmp_path / "out"
    summary = game_training_driver.run([
        "--train-input-dirs", str(train),
        "--validate-input-dirs", str(valid),
        "--output-dir", str(out),
        "--task-type", "LOGISTIC_REGRESSION",
        "--fixed-effect-data-configurations", "fixed:global",
        "--fixed-effect-optimization-configurations",
        "fixed:20,1e-6,10.0,1.0,LBFGS,L2|20,1e-6,0.1,1.0,LBFGS,L2",
        "--updating-sequence", "fixed",
        "--evaluators", "AUC",
    ])
    assert summary["numCombos"] == 2
    assert "fixed" in summary["bestConfigs"]


def test_feature_indexing_job(tmp_path, rng):
    train = tmp_path / "train"
    _write_glm_avro(train, rng, n=50)
    out = feature_indexing.run([
        "--data-path", str(train),
        "--output-dir", str(tmp_path / "index"),
    ])
    from photon_ml_tpu.data.index_map import IndexMap

    imap = IndexMap.load(out)
    assert imap.intercept_index >= 0
    assert len(imap) == 6  # f0..f4 + intercept


def test_feature_indexing_job_paldb_format(tmp_path, rng):
    """--format paldb writes reference-layout partitioned stores that the
    PalDB parser (and therefore any --feature-index-dir consumer) loads
    back identically (FeatureIndexingJob.scala:145-174)."""
    train = tmp_path / "train"
    _write_glm_avro(train, rng, n=50)
    out_dir = tmp_path / "paldb-index"
    feature_indexing.run([
        "--data-path", str(train),
        "--output-dir", str(out_dir),
        "--format", "paldb",
        "--partition-num", "2",
        "--shard-name", "global",
    ])
    from photon_ml_tpu.data.paldb import load_paldb_index_map

    assert (out_dir / "paldb-partition-global-0.dat").exists()
    assert (out_dir / "paldb-partition-global-1.dat").exists()
    imap = load_paldb_index_map(out_dir, "global", 2)
    assert len(imap) == 6
    assert imap.intercept_index >= 0


def test_game_driver_rejects_unknown_sequence_entry(tmp_path, rng):
    train = tmp_path / "train"
    _write_game_avro(train, rng, n=20)
    with pytest.raises(ValueError, match="no data configuration"):
        game_training_driver.run([
            "--train-input-dirs", str(train),
            "--output-dir", str(tmp_path / "o"),
            "--task-type", "LOGISTIC_REGRESSION",
            "--fixed-effect-data-configurations", "fixed:global",
            "--fixed-effect-optimization-configurations",
            "fixed:10,1e-6,1.0,1.0,LBFGS,L2",
            "--updating-sequence", "fixed,ghost",
        ])


def test_game_training_with_factored_random_effect(tmp_path, rng):
    train = tmp_path / "train"
    valid = tmp_path / "valid"
    params = (rng.normal(0, 1.5, 10), rng.normal(0, 1, 3))
    _write_game_avro(train, rng, n=300, params=params)
    _write_game_avro(valid, rng, n=120, params=params)
    out = tmp_path / "out"
    summary = game_training_driver.run([
        "--train-input-dirs", str(train),
        "--validate-input-dirs", str(valid),
        "--output-dir", str(out),
        "--task-type", "LOGISTIC_REGRESSION",
        "--fixed-effect-data-configurations", "fixed:global",
        "--fixed-effect-optimization-configurations",
        "fixed:20,1e-7,1.0,1.0,LBFGS,L2",
        "--factored-random-effect-data-configurations",
        "perUserMF:userId,global,4,-1,-1,-1",
        "--factored-random-effect-optimization-configurations",
        "perUserMF:15,1e-7,1.0,1.0,LBFGS,L2;15,1e-7,1.0,1.0,LBFGS,L2;2,2",
        "--updating-sequence", "fixed,perUserMF",
        "--num-iterations", "2",
        "--evaluators", "AUC",
    ])
    assert summary["validationHistory"][-1]["AUC"] > 0.6
    meta = json.loads((out / "best" / "model-metadata.json").read_text())
    kinds = {c["name"]: c["kind"] for c in meta["coordinates"]}
    # Factored models persist as original-space random-effect coordinates.
    assert kinds == {"fixed": "fixed", "perUserMF": "random"}

    score_out = tmp_path / "score-out"
    score_summary = game_scoring_driver.run([
        "--input-dirs", str(valid),
        "--game-model-input-dir", str(out / "best"),
        "--output-dir", str(score_out),
        "--evaluators", "AUC",
    ])
    np.testing.assert_allclose(
        score_summary["metrics"]["AUC"],
        summary["validationHistory"][-1]["AUC"], atol=1e-6)


def test_glm_driver_selected_features_and_summarization(tmp_path, rng):
    """--selected-features-file restricts the index map to the whitelist
    (GLMSuite.scala:76-150); --summarization-output-dir writes per-feature
    FeatureSummarizationResultAvro (IOUtils.scala:270-330)."""
    train = tmp_path / "train"
    _write_glm_avro(train, rng, n=150)
    # Whitelist only f0, f1 (FeatureNameTermAvro-shaped records).
    sel = tmp_path / "selected.avro"
    write_container(sel, schemas.NAME_TERM_VALUE,
                    [{"name": "f0", "term": None, "value": 0.0},
                     {"name": "f1", "term": None, "value": 0.0}])
    out = tmp_path / "out"
    summ = tmp_path / "feature-summary"
    summary = glm_driver.run([
        "--training-data-directory", str(train),
        "--output-directory", str(out),
        "--task", "LOGISTIC_REGRESSION",
        "--regularization-weights", "1",
        "--max-num-iterations", "10",
        "--selected-features-file", str(sel),
        "--summarization-output-dir", str(summ),
        "--dtype", "float64",
    ])
    # 2 selected features + intercept.
    index = json.loads((out / "feature-index.json").read_text())
    assert len(index) == 3
    recs = list(read_container(summ / "part-00000.avro"))
    assert len(recs) == 3
    by_name = {r["featureName"]: r["metrics"] for r in recs}
    assert {"f0", "f1"} <= set(by_name)
    m = by_name["f0"]
    assert {"max", "min", "mean", "normL1", "normL2", "numNonzeros",
            "variance"} == set(m)
    assert m["numNonzeros"] > 0


def test_glm_driver_profile_trace(tmp_path, rng):
    """--profile-output-dir writes a jax.profiler trace of the train phase."""
    train = tmp_path / "train"
    _write_glm_avro(train, rng, n=60)
    out = tmp_path / "out"
    prof = tmp_path / "profile"
    glm_driver.run([
        "--training-data-directory", str(train),
        "--output-directory", str(out),
        "--task", "LOGISTIC_REGRESSION",
        "--regularization-weights", "1",
        "--max-num-iterations", "5",
        "--profile-output-dir", str(prof),
        "--dtype", "float64",
    ])
    assert prof.exists(), "profiler did not create the trace directory"
    assert any(prof.rglob("*.xplane.pb")), list(prof.rglob("*"))


def _write_sparse_fe_avro(path, rng, n=240, d=40, per_row=4, offset=0):
    """Fixed-effect-only TrainingExampleAvro with density below the dense
    threshold, so ingest takes the CSR layout (the --stream-train sparse
    assembly path)."""
    w = rng.normal(0, 1, d + 1)
    records = []
    for i in range(n):
        idx = rng.choice(d, size=per_row, replace=False)
        vals = rng.normal(0, 1, per_row)
        z = float(vals @ w[idx] + w[-1])
        records.append({
            "uid": f"u{offset + i}",
            "label": float(rng.random() < 1 / (1 + np.exp(-z))),
            "features": [{"name": f"f{j}", "term": None, "value": float(v)}
                         for j, v in zip(idx, vals)],
            "weight": None, "offset": None, "metadataMap": None})
    path.mkdir(parents=True, exist_ok=True)
    write_container(path / "part-00000.avro", schemas.TRAINING_EXAMPLE,
                    records)


_STREAM_BASE = [
    "--task-type", "LOGISTIC_REGRESSION",
    "--fixed-effect-data-configurations", "fixed:global",
    "--fixed-effect-optimization-configurations",
    "fixed:25,1e-7,1.0,1.0,LBFGS,L2",
    "--updating-sequence", "fixed",
]


def _coeff_records(out_dir):
    """Decoded coefficient records — the byte-identity comparison unit
    (the Avro container header embeds a random sync marker, so FILE bytes
    can never match; the records carry the exact f32 coefficient bits)."""
    return list(read_container(
        out_dir / "best" / "fixed-effect" / "fixed" / "coefficients"
        / "part-00000.avro"))


def test_stream_train_resident_model_identical_to_one_shot(tmp_path, rng):
    """--stream-train without --hbm-budget assembles the exact one-shot
    device batch from the streamed ingest: the saved fixed-effect model
    is identical to the one-shot driver's, bit for bit, for BOTH feature
    layouts and for non-block-aligned --batch-rows."""
    for tag, writer in (("sparse", _write_sparse_fe_avro),
                        ("dense", _write_glm_avro)):
        train = tmp_path / tag / "train"
        writer(train, rng, n=220)
        base = ["--train-input-dirs", str(train)] + _STREAM_BASE
        one = tmp_path / tag / "one"
        st = tmp_path / tag / "stream"
        game_training_driver.run(base + ["--output-dir", str(one)])
        summary = game_training_driver.run(
            base + ["--output-dir", str(st), "--stream-train",
                    "--batch-rows", "33"])
        assert _coeff_records(one) == _coeff_records(st), tag
        info = summary["stream_train"]
        assert info["mode"] == "resident-assembled"
        assert info["feeder"]["rows"] == 220
        assert info["feeder"]["batches"] == 7  # ceil(220/33)


def test_stream_train_spill_identical_across_residency(tmp_path, rng):
    """--hbm-budget mode: eviction-forced, python-feeder, zero-prefetch
    runs all write the SAME model bytes as a fully-resident streamed run
    (fixed shard order defines the accumulation); and the result matches
    the one-shot model to f32 accumulation tolerance."""
    train = tmp_path / "train"
    _write_sparse_fe_avro(train, rng, n=300)
    base = ["--train-input-dirs", str(train)] + _STREAM_BASE
    one = game_training_driver.run(
        base + ["--output-dir", str(tmp_path / "one")])
    big = game_training_driver.run(
        base + ["--output-dir", str(tmp_path / "big"), "--stream-train",
                "--batch-rows", "64", "--hbm-budget", "64M"])
    small = game_training_driver.run(
        base + ["--output-dir", str(tmp_path / "small"), "--stream-train",
                "--batch-rows", "64", "--hbm-budget", "8K",
                "--feeder", "python", "--prefetch-batches", "0"])
    assert big["stream_train"]["cache"]["evictions"] == 0
    assert small["stream_train"]["cache"]["evictions"] > 0
    assert _coeff_records(tmp_path / "big") == \
        _coeff_records(tmp_path / "small")
    ref = {r["name"]: r["value"]
           for r in _coeff_records(tmp_path / "one")[0]["means"]}
    got = {r["name"]: r["value"]
           for r in _coeff_records(tmp_path / "big")[0]["means"]}
    assert set(ref) == set(got)
    np.testing.assert_allclose([got[k] for k in sorted(ref)],
                               [ref[k] for k in sorted(ref)],
                               rtol=1e-3, atol=2e-5)
    assert one["numRows"] == big["numRows"] == 300


def test_stream_train_spill_source_redecode_model_identity(tmp_path, rng):
    """Fully out-of-core epochs: --spill-source redecode (evicted blocks
    dropped, misses re-decode Avro) writes model bytes IDENTICAL to the
    buffer-spill run — for the native and the python feeder — because a
    re-decoded block reconstructs the evicted padded triplet exactly.
    The explicit --spill-dtype f32 spelling equals the default."""
    train = tmp_path / "train"
    _write_sparse_fe_avro(train, rng, n=300)
    base = ["--train-input-dirs", str(train)] + _STREAM_BASE + [
        "--stream-train", "--batch-rows", "64", "--hbm-budget", "8K"]
    buffer_run = game_training_driver.run(
        base + ["--output-dir", str(tmp_path / "buf"),
                "--spill-dtype", "f32"])
    assert buffer_run["stream_train"]["cache"]["evictions"] > 0
    assert buffer_run["stream_train"]["cache"]["spill_bytes_host"] > 0
    ref = _coeff_records(tmp_path / "buf")
    for tag, extra in (("rd", []), ("rd_py", ["--feeder", "python"])):
        out = tmp_path / tag
        summary = game_training_driver.run(
            base + ["--output-dir", str(out),
                    "--spill-source", "redecode"] + extra)
        assert _coeff_records(out) == ref, tag
        info = summary["stream_train"]
        assert info["spill_source"] == "redecode"
        cache = info["cache"]
        assert cache["spill_bytes_host"] == 0  # no host copy at all
        assert cache["redecodes"] == cache["misses"] > 0
        assert cache["bytes_redecoded"] > 0
        assert info["redecode"]["payload_bytes_read"] > 0
        assert info["redecode"]["rows_fetched"] > 0


def test_stream_train_bf16_spill_parity_and_residency_independence(
        tmp_path, rng):
    """Compressed spill: --spill-dtype bf16 (1) is residency-independent
    — two budgets with very different eviction pressure write IDENTICAL
    model bytes (values quantize once at ingest) — (2) matches the
    f32-spill model per-coefficient within the bf16 parity bound, (3)
    retains 1/3 of the f32 host spill bytes and ~1/3 of its per-epoch
    re-upload traffic."""
    train = tmp_path / "train"
    _write_sparse_fe_avro(train, rng, n=300)
    base = ["--train-input-dirs", str(train)] + _STREAM_BASE + [
        "--stream-train", "--batch-rows", "64"]
    f32 = game_training_driver.run(
        base + ["--output-dir", str(tmp_path / "f32"),
                "--hbm-budget", "8K"])
    small = game_training_driver.run(
        base + ["--output-dir", str(tmp_path / "bf_small"),
                "--hbm-budget", "8K", "--spill-dtype", "bf16"])
    big = game_training_driver.run(
        base + ["--output-dir", str(tmp_path / "bf_big"),
                "--hbm-budget", "64K", "--spill-dtype", "bf16"])
    assert small["stream_train"]["cache"]["evictions"] \
        > big["stream_train"]["cache"]["evictions"]
    assert _coeff_records(tmp_path / "bf_small") == \
        _coeff_records(tmp_path / "bf_big")
    # parity bound vs the f32-spill model: per-coefficient rel error
    ref = {r["name"]: r["value"]
           for r in _coeff_records(tmp_path / "f32")[0]["means"]}
    got = {r["name"]: r["value"]
           for r in _coeff_records(tmp_path / "bf_small")[0]["means"]}
    assert set(ref) == set(got)
    np.testing.assert_allclose([got[k] for k in sorted(ref)],
                               [ref[k] for k in sorted(ref)],
                               rtol=0.1, atol=5e-3)
    c_f32 = f32["stream_train"]["cache"]
    c_bf = small["stream_train"]["cache"]
    assert c_bf["spill_bytes_host"] * 3 == c_f32["spill_bytes_host"]
    assert c_bf["spill_dtype"] == "bf16"
    # same eviction pressure, compact re-uploads: ~1/3 the f32 traffic
    # (not exactly — iteration counts may differ at bf16 precision)
    assert c_bf["bytes_reuploaded"] < 0.5 * c_f32["bytes_reuploaded"]


def test_spill_flags_require_hbm_budget(tmp_path, rng):
    train = tmp_path / "train"
    _write_sparse_fe_avro(train, rng, n=60)
    base = ["--train-input-dirs", str(train)] + _STREAM_BASE + [
        "--stream-train", "--batch-rows", "32"]
    with pytest.raises(ValueError, match="--spill-dtype"):
        game_training_driver.run(
            base + ["--output-dir", str(tmp_path / "a"),
                    "--spill-dtype", "bf16"])
    with pytest.raises(ValueError, match="--spill-source"):
        game_training_driver.run(
            base + ["--output-dir", str(tmp_path / "b"),
                    "--spill-source", "redecode"])
    # bf16 compresses buffers; redecode keeps none — reject the combo
    with pytest.raises(ValueError, match="pick one"):
        game_training_driver.run(
            base + ["--output-dir", str(tmp_path / "c"),
                    "--hbm-budget", "8K", "--spill-dtype", "bf16",
                    "--spill-source", "redecode"])


_GRID_STREAM_BASE = [
    "--task-type", "LOGISTIC_REGRESSION",
    "--fixed-effect-data-configurations", "fixed:global",
    "--fixed-effect-optimization-configurations",
    "fixed:25,1e-7,0.5,1.0,LBFGS,L2|25,1e-7,5.0,1.0,LBFGS,L2"
    "|25,1e-7,50.0,1.0,LBFGS,L2",
    "--updating-sequence", "fixed",
]


def test_grid_batched_sweep_selects_same_model(tmp_path, rng):
    """--grid-batched: 'auto' batches a 3-point λ-grid into one streamed
    sweep that selects the SAME λ as the sequential sweep with
    per-coefficient agreement on the saved model; 'on' with G=1 writes
    model bytes IDENTICAL to the sequential solve (the bitwise gate,
    end to end through the CLI)."""
    train = tmp_path / "train"
    _write_sparse_fe_avro(train, rng, n=300)
    base = ["--train-input-dirs", str(train)] + _GRID_STREAM_BASE + [
        "--stream-train", "--batch-rows", "64", "--hbm-budget", "8K"]
    seq = game_training_driver.run(
        base + ["--output-dir", str(tmp_path / "seq"),
                "--grid-batched", "off"])
    bat = game_training_driver.run(
        base + ["--output-dir", str(tmp_path / "bat")])
    assert seq["stream_train"]["grid_batched"] is False
    assert bat["stream_train"]["grid_batched"] is True
    assert seq["stream_train"]["grid_points"] == \
        bat["stream_train"]["grid_points"] == 3
    assert seq["bestConfigs"] == bat["bestConfigs"]  # selection parity
    ref = {r["name"]: r["value"]
           for r in _coeff_records(tmp_path / "seq")[0]["means"]}
    got = {r["name"]: r["value"]
           for r in _coeff_records(tmp_path / "bat")[0]["means"]}
    assert set(ref) == set(got)
    np.testing.assert_allclose([got[k] for k in sorted(ref)],
                               [ref[k] for k in sorted(ref)],
                               rtol=2e-3, atol=1e-4)
    # the sweep's grid kernels stayed within their compile budgets
    assert any(k.startswith("sharded:grid_") and v > 0
               for k, v in bat["stream_train"]["trace_counts"].items())
    # G=1 forced batched: bitwise model identity with sequential
    g1 = ["--train-input-dirs", str(train)] + _STREAM_BASE + [
        "--stream-train", "--batch-rows", "64", "--hbm-budget", "8K"]
    game_training_driver.run(
        g1 + ["--output-dir", str(tmp_path / "g1seq"),
              "--grid-batched", "off"])
    on = game_training_driver.run(
        g1 + ["--output-dir", str(tmp_path / "g1on"),
              "--grid-batched", "on"])
    assert on["stream_train"]["grid_batched"] is True
    assert _coeff_records(tmp_path / "g1seq") == \
        _coeff_records(tmp_path / "g1on")


def test_grid_batched_flag_validation(tmp_path, rng):
    train = tmp_path / "train"
    _write_sparse_fe_avro(train, rng, n=60)
    base = ["--train-input-dirs", str(train)] + _STREAM_BASE
    with pytest.raises(ValueError, match="--grid-batched applies"):
        game_training_driver.run(
            base + ["--output-dir", str(tmp_path / "a"),
                    "--grid-batched", "on"])
    with pytest.raises(ValueError, match="--grid-batched on requires"):
        game_training_driver.run(
            base + ["--output-dir", str(tmp_path / "b"),
                    "--stream-train", "--grid-batched", "on"])


def _write_mf_avro(path, rng, n=240, n_users=9, d=6, k_true=2):
    """Linear labels with per-entity rank-k_true coefficient structure —
    the streamed-MF coordinate's training shape (userId in
    metadataMap)."""
    b_true = rng.normal(0, 1, (k_true, d))
    g_true = rng.normal(0, 1, (n_users, k_true))
    coefs = g_true @ b_true
    records = []
    for i in range(n):
        u = int(rng.integers(0, n_users))
        x = rng.normal(0, 1, d)
        yv = float(x @ coefs[u] + rng.normal(0, 0.05))
        records.append({
            "uid": f"r{i}", "label": yv,
            "features": [{"name": f"x{j}", "term": None, "value": float(v)}
                         for j, v in enumerate(x)],
            "weight": None, "offset": None,
            "metadataMap": {"userId": f"user{u}"}})
    path.mkdir(parents=True, exist_ok=True)
    write_container(path / "part-00000.avro", schemas.TRAINING_EXAMPLE,
                    records)


_MF_STREAM_BASE = [
    "--task-type", "LINEAR_REGRESSION",
    "--factored-random-effect-data-configurations",
    "perUser:userId,global,1,-1,-1,-1,identity",
    "--factored-random-effect-optimization-configurations",
    "perUser:20,1e-8,0.001,1.0,LBFGS,L2;20,1e-8,0.001,1.0,LBFGS,L2;2,3",
    "--updating-sequence", "perUser",
]


def _latent_records(out_dir):
    """Decoded latent artifacts — the byte-identity comparison unit for
    MF runs (per-entity gamma + the shared projection B)."""
    base = out_dir / "best" / "random-effect" / "perUser" / "latent"
    return (list(read_container(base / "gamma-latent-factors.avro")),
            list(read_container(base / "projection-latent-factors.avro")))


@pytest.mark.slow
def test_stream_train_mf_identity_across_residency_and_feeder(tmp_path,
                                                              rng):
    """Tentpole acceptance at the CLI: a factor table larger than
    --hbm-budget trains to completion out-of-core, and the saved latent
    artifacts (gamma + B) are IDENTICAL across residency, feeder and
    prefetch configs; the streamed model parity-matches the in-core
    driver's factored coordinate at identical iteration counts."""
    train = tmp_path / "train"
    _write_mf_avro(train, rng)
    base = ["--train-input-dirs", str(train)] + _MF_STREAM_BASE

    resident = game_training_driver.run(
        base + ["--output-dir", str(tmp_path / "resident"),
                "--stream-train", "--batch-rows", "64"])
    info = resident["stream_train"]
    assert info["mode"] == "mf-stream"
    assert info["cache"]["evictions"] == 0
    g_res, p_res = _latent_records(tmp_path / "resident")

    spill = game_training_driver.run(
        base + ["--output-dir", str(tmp_path / "spill"),
                "--stream-train", "--batch-rows", "64",
                "--hbm-budget", "64"])
    cache = spill["stream_train"]["cache"]
    assert cache["evictions"] > 0 and cache["misses"] > 0
    # the factor table exceeds the budget: out-of-core by construction
    assert cache["peak_device_bytes"] + cache["spill_bytes_host"] > 64
    assert _latent_records(tmp_path / "spill") == (g_res, p_res)

    forced = game_training_driver.run(
        base + ["--output-dir", str(tmp_path / "python"),
                "--stream-train", "--batch-rows", "64",
                "--feeder", "python", "--prefetch-batches", "0"])
    assert forced["stream_train"]["feeder"]["decode_path"] == "python"
    assert _latent_records(tmp_path / "python") == (g_res, p_res)

    # in-core parity at identical iteration counts: the one-shot driver
    # trains the same factored coordinate through the estimator
    game_training_driver.run(
        base + ["--output-dir", str(tmp_path / "incore")])
    g_ic, p_ic = _latent_records(tmp_path / "incore")
    b_stream = np.asarray([r["latentFactor"] for r in p_res])
    b_core = np.asarray([r["latentFactor"] for r in p_ic])
    assert b_stream.shape == b_core.shape
    scale = np.max(np.abs(b_core))
    assert np.max(np.abs(b_stream - b_core)) <= 1e-3 * scale
    assert [r["effectId"] for r in g_res] == [r["effectId"] for r in g_ic]


@pytest.mark.slow
def test_stream_train_mf_bf16_and_redecode_tiers(tmp_path, rng):
    """Spill tiers for factors at the CLI: bf16 models are bitwise
    residency-independent and parity-bounded vs f32; redecode keeps
    ZERO host spill bytes, re-derives misses from observations, and
    writes bytes identical to the buffer tier."""
    train = tmp_path / "train"
    _write_mf_avro(train, rng)
    base = ["--train-input-dirs", str(train)] + _MF_STREAM_BASE + [
        "--stream-train", "--batch-rows", "64"]

    f32 = game_training_driver.run(
        base + ["--output-dir", str(tmp_path / "f32"),
                "--hbm-budget", "64"])
    lat_f32 = _latent_records(tmp_path / "f32")

    bf_small = game_training_driver.run(
        base + ["--output-dir", str(tmp_path / "bf-small"),
                "--hbm-budget", "64", "--spill-dtype", "bf16"])
    bf_big = game_training_driver.run(
        base + ["--output-dir", str(tmp_path / "bf-big"),
                "--hbm-budget", "1G", "--spill-dtype", "bf16"])
    assert bf_small["stream_train"]["cache"]["evictions"] > 0
    assert bf_big["stream_train"]["cache"]["evictions"] == 0
    lat_small = _latent_records(tmp_path / "bf-small")
    assert lat_small == _latent_records(tmp_path / "bf-big")
    assert lat_small != lat_f32  # quantized — but parity-bounded:
    b_bf = np.asarray([r["latentFactor"] for r in lat_small[1]])
    b_f = np.asarray([r["latentFactor"] for r in lat_f32[1]])
    assert np.max(np.abs(b_bf - b_f)) <= 0.05 * np.max(np.abs(b_f))

    rd = game_training_driver.run(
        base + ["--output-dir", str(tmp_path / "redecode"),
                "--hbm-budget", "64", "--spill-source", "redecode"])
    info = rd["stream_train"]
    assert info["cache"]["spill_bytes_host"] == 0
    assert info["cache"]["redecodes"] > 0
    assert info["redecode"]["payload_bytes_read"] > 0
    assert info["redecode"]["rows_fetched"] > 0
    assert _latent_records(tmp_path / "redecode") == lat_f32


def test_stream_train_mf_schema_grid_and_compile_bounds(tmp_path, rng):
    """MF-mode metrics.json schema (snake_case, plan block, ALX density
    histogram), λ-grid kernel sharing (grid points with one num_factors
    share every compiled kernel — trace counts within the per-bucket
    budgets), and factor-cache registry counters."""
    train = tmp_path / "train"
    _write_mf_avro(train, rng)
    # grid: two λ points at k=3 (share one objective/cache) + one at
    # k=2 (its own cache -> the cache_by_num_factors block)
    grid = ("perUser:20,1e-8,0.001,1.0,LBFGS,L2;20,1e-8,0.001,1.0,"
            "LBFGS,L2;2,3|15,1e-8,0.1,1.0,LBFGS,L2;15,1e-8,0.1,1.0,"
            "LBFGS,L2;2,3|10,1e-8,0.001,1.0,LBFGS,L2;10,1e-8,0.001,"
            "1.0,LBFGS,L2;1,2")
    summary = game_training_driver.run([
        "--train-input-dirs", str(train),
        "--task-type", "LINEAR_REGRESSION",
        "--factored-random-effect-data-configurations",
        "perUser:userId,global,1,-1,-1,-1,identity",
        "--factored-random-effect-optimization-configurations", grid,
        "--updating-sequence", "perUser",
        "--output-dir", str(tmp_path / "out"),
        "--stream-train", "--batch-rows", "64", "--hbm-budget", "64"])
    assert summary["numCombos"] == 3
    info = summary["stream_train"]
    assert set(info) == {"mode", "batch_rows", "hbm_budget_bytes",
                         "mesh_devices", "mesh_shape", "spill_dtype",
                         "spill_source", "feeder", "cache", "plan",
                         "trace_budgets", "trace_counts",
                         "cache_by_num_factors"}
    # every factor cache in a multi-k grid stays observable post-run
    assert set(info["cache_by_num_factors"]) == {"2", "3"}
    assert info["cache_by_num_factors"]["3"] == info["cache"]
    assert info["mode"] == "mf-stream"
    assert info["mesh_devices"] is None
    assert info["plan"]["entities"] == 9
    assert info["plan"]["shards"] >= 1
    assert sum(info["plan"]["obs_bucket_histogram"].values()) == 9
    # compile bound: every mf kernel within its observed-bucket budget,
    # TWO grid points deep (shared objective -> shared executables)
    for name, count in info["trace_counts"].items():
        if name in info["trace_budgets"]:
            assert count <= info["trace_budgets"][name], (name, count)
    m = summary["telemetry"]["metrics"]
    assert m["counters"]["data.factor_cache.evictions"] > 0
    assert m["gauges"]["data.factor_cache.peak_device_bytes"] > 0
    # mf sweeps rode the solver-iteration telemetry (B refits)
    assert m["counters"]["training.solver_iterations"] >= 1


def test_stream_train_mf_flag_validation(tmp_path, rng):
    train = tmp_path / "train"
    _write_mf_avro(train, rng, n=60)
    base = ["--train-input-dirs", str(train)] + _MF_STREAM_BASE
    with pytest.raises(ValueError, match="mesh"):
        game_training_driver.run(
            base + ["--output-dir", str(tmp_path / "a"), "--stream-train",
                    "--batch-rows", "32", "--hbm-budget", "8K",
                    "--mesh-devices", "1"])
    # a plain random effect still cannot stream-train
    with pytest.raises(ValueError, match="fixed-effect or factored"):
        game_training_driver.run([
            "--train-input-dirs", str(train),
            "--task-type", "LINEAR_REGRESSION",
            "--random-effect-data-configurations",
            "re:userId,global,1,-1,-1,-1",
            "--random-effect-optimization-configurations",
            "re:10,1e-7,1.0,1.0,LBFGS,L2",
            "--updating-sequence", "re",
            "--output-dir", str(tmp_path / "b"), "--stream-train"])


def test_stream_train_mesh_model_identical_across_mesh_sizes(tmp_path,
                                                             rng):
    """Tentpole acceptance: --mesh-devices 1 writes the PR-5
    single-device fold's model bit for bit, and mesh sizes {2, 4} write
    byte-identical model artifacts to each other (and, by the ordered
    shard-order combine, to the 1-device fold), with compile counts
    bounded per bucket through the TracingGuard."""
    train = tmp_path / "train"
    _write_sparse_fe_avro(train, rng, n=300)
    base = ["--train-input-dirs", str(train)] + _STREAM_BASE + [
        "--stream-train", "--batch-rows", "64", "--hbm-budget", "8K"]
    no_mesh = game_training_driver.run(
        base + ["--output-dir", str(tmp_path / "nomesh")])
    ref = _coeff_records(tmp_path / "nomesh")
    for n_dev in (1, 2, 4):
        out = tmp_path / f"mesh{n_dev}"
        summary = game_training_driver.run(
            base + ["--output-dir", str(out),
                    "--mesh-devices", str(n_dev)])
        assert _coeff_records(out) == ref, n_dev
        info = summary["stream_train"]
        assert info["mesh_devices"] == n_dev
        assert info["cache"]["mesh_devices"] == (n_dev if n_dev > 1
                                                 else None)
        assert info["cache"]["evictions"] > 0, n_dev
        for name, count in info["trace_counts"].items():
            assert count <= info["trace_budgets"][name], (n_dev, name)
        if n_dev > 1:
            # per-device kernels registered; budget binds PER device
            assert any(k.startswith("sharded:init@d")
                       for k in info["trace_counts"])
            assert len(info["cache"]["per_device_bytes"]) == n_dev


def test_mesh_devices_flag_validation(tmp_path, rng):
    """--mesh-devices composes only with the sharded streaming solve:
    it needs --stream-train, > 1 needs --hbm-budget, and more devices
    than the host exposes fails with the mesh builder's error."""
    train = tmp_path / "train"
    _write_sparse_fe_avro(train, rng, n=60)
    base = ["--train-input-dirs", str(train)] + _STREAM_BASE
    with pytest.raises(ValueError, match="--stream-train"):
        game_training_driver.run(
            base + ["--output-dir", str(tmp_path / "a"),
                    "--mesh-devices", "2"])
    with pytest.raises(ValueError, match="--hbm-budget"):
        game_training_driver.run(
            base + ["--output-dir", str(tmp_path / "b"), "--stream-train",
                    "--mesh-devices", "2"])
    with pytest.raises(ValueError, match="devices"):
        game_training_driver.run(
            base + ["--output-dir", str(tmp_path / "c"), "--stream-train",
                    "--hbm-budget", "8K", "--mesh-devices", "64"])
    # N=1 composes with BOTH modes (it is the single-device fold)
    summary = game_training_driver.run(
        base + ["--output-dir", str(tmp_path / "d"), "--stream-train",
                "--mesh-devices", "1", "--batch-rows", "32"])
    assert summary["stream_train"]["mesh_devices"] == 1


def _assert_stream_train_telemetry(out_dir, summary, feeder):
    info = summary["stream_train"]
    assert info["feeder"]["decode_path"] == feeder
    for key in ("mode", "batch_rows", "hbm_budget_bytes", "mesh_devices",
                "spill_dtype", "spill_source", "feeder", "cache"):
        assert key in info, key
    if info["cache"] is not None:
        for key in ("hits", "misses", "evictions", "bytes_reuploaded",
                    "peak_device_bytes", "bucket_shapes", "mesh_devices",
                    "per_device_bytes", "spill_dtype", "spill_source",
                    "spill_bytes_host", "spill_bytes_written",
                    "redecodes", "bytes_redecoded"):
            assert key in info["cache"], key
        assert "trace_budgets" in info and "trace_counts" in info
        for name, count in info["trace_counts"].items():
            assert count <= info["trace_budgets"][name], name
    # the deprecated camelCase alias is gone (rode one release behind)
    assert "streamTrain" not in summary
    # the telemetry must round-trip through the metrics.json artifact
    on_disk = json.loads((out_dir / "metrics.json").read_text())
    assert on_disk["stream_train"] == json.loads(json.dumps(info))
    assert "streamTrain" not in on_disk


def test_stream_train_smoke_python_feeder(tmp_path, rng):
    """Tier-1 smoke: end-to-end --stream-train on a tiny generated Avro
    file with the forced-python feeder, asserting metrics.json telemetry
    keys, in both resident and spill modes."""
    train = tmp_path / "train"
    _write_sparse_fe_avro(train, rng, n=90)
    base = ["--train-input-dirs", str(train)] + _STREAM_BASE
    s_res = game_training_driver.run(
        base + ["--output-dir", str(tmp_path / "res"), "--stream-train",
                "--batch-rows", "32", "--feeder", "python"])
    _assert_stream_train_telemetry(tmp_path / "res", s_res, "python")
    s_spill = game_training_driver.run(
        base + ["--output-dir", str(tmp_path / "spill"), "--stream-train",
                "--batch-rows", "32", "--feeder", "python",
                "--hbm-budget", "4K"])
    _assert_stream_train_telemetry(tmp_path / "spill", s_spill, "python")
    assert s_spill["stream_train"]["mode"] == "spill"


@pytest.mark.native_decoder
def test_stream_train_smoke_native_feeder(tmp_path, rng):
    train = tmp_path / "train"
    _write_sparse_fe_avro(train, rng, n=90)
    base = ["--train-input-dirs", str(train)] + _STREAM_BASE
    summary = game_training_driver.run(
        base + ["--output-dir", str(tmp_path / "out"), "--stream-train",
                "--batch-rows", "32", "--feeder", "native",
                "--hbm-budget", "1M"])
    _assert_stream_train_telemetry(tmp_path / "out", summary, "native")


def test_stream_train_streamed_validation_matches_one_shot(tmp_path, rng):
    """Validation goes through StreamingGameScorer.score_container_stream
    (bounded by --batch-rows) and reproduces the one-shot driver's
    validation metrics; grid selection uses the streamed metric."""
    train = tmp_path / "train"
    valid = tmp_path / "valid"
    _write_sparse_fe_avro(train, rng, n=300)
    _write_sparse_fe_avro(valid, rng, n=130, offset=300)
    grid = [
        "--task-type", "LOGISTIC_REGRESSION",
        "--fixed-effect-data-configurations", "fixed:global",
        "--fixed-effect-optimization-configurations",
        "fixed:25,1e-7,10.0,1.0,LBFGS,L2|25,1e-7,0.1,1.0,LBFGS,L2",
        "--updating-sequence", "fixed",
        "--evaluators", "AUC,LOGISTIC_LOSS",
        "--train-input-dirs", str(train),
        "--validate-input-dirs", str(valid),
    ]
    one = game_training_driver.run(grid + ["--output-dir",
                                           str(tmp_path / "one")])
    st = game_training_driver.run(
        grid + ["--output-dir", str(tmp_path / "stream"), "--stream-train",
                "--batch-rows", "48"])
    assert st["numCombos"] == one["numCombos"] == 2
    assert st["bestConfigs"] == one["bestConfigs"]
    for name, v in one["validationHistory"][-1].items():
        np.testing.assert_allclose(st["validationHistory"][-1][name], v,
                                   rtol=1e-6, atol=1e-7)
    # the winning streamed model is the winning one-shot model, exactly
    assert _coeff_records(tmp_path / "one") == \
        _coeff_records(tmp_path / "stream")


def test_stream_train_rejects_random_effects(tmp_path, rng):
    from photon_ml_tpu import telemetry

    train = tmp_path / "train"
    _write_game_avro(train, rng, n=40)
    with pytest.raises(ValueError, match="one fixed-effect"):
        game_training_driver.run([
            "--train-input-dirs", str(train),
            "--output-dir", str(tmp_path / "o"),
            "--task-type", "LOGISTIC_REGRESSION",
            "--fixed-effect-data-configurations", "fixed:global",
            "--fixed-effect-optimization-configurations",
            "fixed:10,1e-6,1.0,1.0,LBFGS,L2",
            "--random-effect-data-configurations",
            "perUser:userId,global,4,-1,-1,-1",
            "--random-effect-optimization-configurations",
            "perUser:10,1e-6,1.0,1.0,LBFGS,L2",
            "--updating-sequence", "fixed,perUser",
            "--stream-train"])
    # A failed run must not leave the process-wide recorder armed.
    assert not telemetry.enabled()


class RecordingListener(EventListener):
    """Registered BY NAME from the driver (utils/events.py reflective
    registration). State goes through a file named by an env var —
    importlib re-imports this module under its dotted name, so a
    class-level list would live on a DIFFERENT class object than the
    one pytest asserts on."""

    def on_event(self, event):
        import dataclasses
        import os

        with open(os.environ["PHOTON_TEST_EVENT_LOG"], "a") as f:
            f.write(json.dumps({"type": type(event).__name__,
                                **dataclasses.asdict(event)}) + "\n")


def test_stream_train_emits_training_events(tmp_path, rng, monkeypatch):
    """Satellite: --stream-train emits TrainingStart / per-λ
    PhotonOptimizationLog / TrainingFinish through the EventEmitter
    (listener registration existed; the streamed path never emitted)."""
    log = tmp_path / "events.jsonl"
    monkeypatch.setenv("PHOTON_TEST_EVENT_LOG", str(log))
    train = tmp_path / "train"
    _write_sparse_fe_avro(train, rng, n=90)
    game_training_driver.run([
        "--train-input-dirs", str(train),
        "--output-dir", str(tmp_path / "out"),
        "--task-type", "LOGISTIC_REGRESSION",
        "--fixed-effect-data-configurations", "fixed:global",
        "--fixed-effect-optimization-configurations",
        "fixed:25,1e-7,1.0,1.0,LBFGS,L2|25,1e-7,0.1,1.0,LBFGS,L2",
        "--updating-sequence", "fixed",
        "--stream-train", "--batch-rows", "32",
        "--job-name", "stream-events-job",
        "--event-listeners", "tests.test_cli_drivers.RecordingListener",
    ])
    evs = [json.loads(line) for line in log.read_text().splitlines()]
    assert evs[0]["type"] == TrainingStartEvent.__name__
    assert evs[0]["job_name"] == "stream-events-job"
    opt = [e for e in evs
           if e["type"] == PhotonOptimizationLogEvent.__name__]
    assert sorted(e["reg_weight"] for e in opt) == [0.1, 1.0]  # per λ
    for e in opt:
        assert e["iterations"] >= 1
        assert np.isfinite(e["final_value"])
        assert e["converged_reason"]
    assert evs[-1]["type"] == TrainingFinishEvent.__name__
    assert evs[-1]["job_name"] == "stream-events-job"
    assert evs[-1]["duration_seconds"] > 0


def test_stream_train_snake_schema_and_trace(tmp_path, rng):
    """Satellite + tentpole acceptance: the metrics.json stream block is
    snake_case (``stream_train``); the deprecated camelCase
    ``streamTrain`` alias — kept one release behind by PR 6 — is now
    REMOVED. The run writes a Perfetto-loadable trace and a telemetry
    block whose stage attribution explains >= 90% of the end-to-end
    wall time, with solver-iteration timing from the histogram."""
    train = tmp_path / "train"
    _write_sparse_fe_avro(train, rng, n=120)
    trace_path = tmp_path / "trace.json"
    summary = game_training_driver.run(
        ["--train-input-dirs", str(train)] + _STREAM_BASE + [
            "--output-dir", str(tmp_path / "out"), "--stream-train",
            "--batch-rows", "32", "--hbm-budget", "8K",
            "--trace-out", str(trace_path)])

    info = summary["stream_train"]
    assert set(info) == {"mode", "batch_rows", "hbm_budget_bytes",
                         "mesh_devices", "mesh_shape", "spill_dtype",
                         "spill_source", "feeder", "cache", "grid_batched",
                         "grid_points", "trace_budgets", "trace_counts"}
    assert info["batch_rows"] == 32
    assert info["mode"] == "spill"
    assert info["mesh_devices"] is None
    assert info["spill_dtype"] == "f32"
    assert info["spill_source"] == "buffer"
    assert info["grid_batched"] is False  # single-λ grid stays sequential
    assert info["grid_points"] == 1
    assert "streamTrain" not in summary  # deprecated alias removed

    tele = summary["telemetry"]
    assert tele["attributed_wall_frac"] >= 0.9
    assert tele["attributed_wall_seconds"] <= tele["wall_seconds"] * 1.01
    att = tele["stage_attribution"]
    for stage in ("driver", "build_index", "ingest", "solve", "finalize",
                  "solver_step", "accumulate", "decode"):
        assert stage in att, stage
    m = tele["metrics"]
    assert m["counters"]["training.solver_iterations"] >= 1
    it_hist = m["histograms"]["training.iteration_seconds"]
    assert it_hist["count"] >= 1 and it_hist["p50"] is not None
    assert m["counters"]["data.shard_cache.evictions"] > 0
    # the satellite gauge: host spill bytes visible in the registry,
    # equal to the cache's own accounting
    assert m["gauges"]["data.shard_cache.spill_bytes_host"] == \
        info["cache"]["spill_bytes_host"] > 0
    assert m["counters"]["data.shard_cache.spill_bytes_written"] > 0

    doc = json.loads(trace_path.read_text())
    xs = [e for e in doc["traceEvents"] if e["ph"] == "X"]
    names = {e["name"] for e in xs}
    assert {"ingest", "solve", "solver_step", "accumulate"} <= names
    assert all(e["dur"] >= 0 for e in xs)
    # The on-disk metrics.json carries the same telemetry block.
    on_disk = json.loads((tmp_path / "out" / "metrics.json").read_text())
    assert on_disk["stream_train"] == json.loads(json.dumps(info))
    assert on_disk["telemetry"]["attributed_wall_frac"] >= 0.9


def test_scoring_stream_trace_latency_and_schema(tmp_path, rng):
    """Tentpole acceptance, serving side: --stream writes a
    Perfetto-loadable trace, reports request-latency P50/P99 from the
    histogram, carries snake_case key aliases, and its stage attribution
    explains >= 90% of wall time."""
    model_dir, valid = _train_small_game(tmp_path, rng)
    trace_path = tmp_path / "trace.json"
    out = tmp_path / "score-out"
    summary = game_scoring_driver.run([
        "--input-dirs", str(valid),
        "--game-model-input-dir", str(model_dir),
        "--output-dir", str(out),
        "--stream", "--batch-rows", "33",
        "--trace-out", str(trace_path),
    ])
    # snake_case aliases ride beside the deprecated camelCase keys.
    assert summary["num_rows"] == summary["numRows"] == 140
    assert summary["num_batches"] == summary["numBatches"]
    assert summary["batch_rows"] == summary["batchRows"] == 33
    assert summary["scoring_path"] == summary["scoringPath"]
    assert summary["total_seconds"] == summary["totalSeconds"]

    lat = summary["engine"]["request_latency_seconds"]
    assert lat["count"] >= summary["numBatches"]
    assert lat["p50"] is not None and lat["p99"] is not None
    assert 0 < lat["p50"] <= lat["p99"]

    tele = summary["telemetry"]
    assert tele["attributed_wall_frac"] >= 0.9
    m = tele["metrics"]
    assert m["counters"]["serving.rows_scored"] == 140
    assert m["counters"]["serving.dispatches"] >= summary["numBatches"]

    doc = json.loads(trace_path.read_text())
    names = {e["name"] for e in doc["traceEvents"] if e["ph"] == "X"}
    assert {"score", "decode", "featureize", "dispatch"} <= names
    # decode ran on the prefetch thread: more than one trace track.
    tids = {e["tid"] for e in doc["traceEvents"] if e["ph"] == "X"}
    assert len(tids) >= 2
    on_disk = json.loads((out / "metrics.json").read_text())
    assert on_disk["telemetry"]["metrics"]["counters"][
        "serving.rows_scored"] == 140


def test_multihost_initialize_noop_single_host():
    from photon_ml_tpu.parallel import initialize_multihost, is_primary_host

    assert initialize_multihost() is False  # no coordinator env -> no-op
    assert is_primary_host() is True


def test_glm_driver_bf16_feature_storage(tmp_path, rng):
    """--feature-storage-dtype bfloat16 trains end-to-end and reaches the
    same validation quality as full-width storage (predictions carry
    bf16's ~3 digits; AUC is insensitive at this scale)."""
    train = tmp_path / "train"
    valid = tmp_path / "valid"
    w_true = rng.normal(0, 1, 6)
    _write_glm_avro(train, rng, n=300, w=w_true)
    _write_glm_avro(valid, rng, n=100, w=w_true)

    def run(extra):
        out = tmp_path / ("out-" + ("bf16" if extra else "f32"))
        summary = glm_driver.run([
            "--training-data-directory", str(train),
            "--validating-data-directory", str(valid),
            "--output-directory", str(out),
            "--task", "LOGISTIC_REGRESSION",
            "--regularization-weights", "1",
            "--max-num-iterations", "60",
        ] + extra)
        return summary["validationMetrics"]["1.0"]["AUC"]

    # The flag must actually reach the ingest chooser THROUGH the driver:
    # capture what train_glm_models hands to device_batch.
    import jax.numpy as jnp

    from photon_ml_tpu.estimators import model_training

    seen = []
    orig = model_training.device_batch

    def spy(*a, **kw):
        seen.append(kw.get("storage_dtype"))
        return orig(*a, **kw)

    model_training.device_batch, saved = spy, orig
    try:
        auc32 = run([])
        auc16 = run(["--feature-storage-dtype", "bfloat16"])
    finally:
        model_training.device_batch = saved
    assert auc32 > 0.6  # both models genuinely learned
    assert abs(auc16 - auc32) < 0.02
    assert seen == [None, jnp.bfloat16]
