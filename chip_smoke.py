#!/usr/bin/env python3
"""The quickest proof that the system still starts on the chip.

One process drives the users' path once at the headline GLMix widths
(fixed effect d=200, 5,000 users x 25 per-user features, 200,000 rows):

    python chip_smoke.py               # one chip: train, score, serve
    python chip_smoke.py --four-chips  # four chips: the mesh paths only

Data is made from ``--seed`` and written as TrainingExampleAvro; training,
scoring and serving go through ``game_training_driver.run`` and
``game_scoring_driver.run`` in THIS process (the chip belongs to one
process; the only children are Avro writers and the drivers' own decoder
workers, which never touch a device). Every check raises; nothing is
caught, so a failed phase is a non-zero exit. Each phase prints one JSON
line; the last line of stdout is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``.
Seconds in the phase lines are the wall time of a smoke run, not a metric.
"""

from __future__ import annotations

import argparse
import concurrent.futures
import contextlib
import json
import multiprocessing
import os
import shutil
import sys
import time
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parent
WORK = ROOT / ".chip_smoke"  # gitignored; removed again on success

ROWS = 200_000
SERVE_ROWS = 400
D_FIXED = 200  # intercept included; 200 and 25: the repo's headline widths, set by hand
N_USERS = 5_000
D_USER = 25  # intercept included
TRAIN_PARTS = 8

# The CPU rehearsal at seed 0 and full size (f32, vmapped solver) gave a
# validation AUC of 0.95870; the chip must land within 0.01 of it.
AUC_FLOOR = 0.9487

FIXED_OPT = "fixed:50,1e-7,1.0,1.0,LBFGS,L2"
USER_OPT = "perUser:20,1e-6,1.0,1.0,LBFGS,L2"
# The streamed mesh solve pays one pass over the cache per evaluation:
# a short solve bounds the four-chip call, and every mesh shape runs it.
STREAM_OPT = "fixed:15,1e-7,1.0,1.0,LBFGS,L2"
# The padded CSR cache of 200k x 200 is ~480 MB; a quarter of it.
STREAM_HBM_BUDGET = "120M"

KERNEL_MARKER = "tpu_custom_call"


def _emit(**line) -> None:
    print(json.dumps(line), flush=True)


# -- data ------------------------------------------------------------------


def _truth(seed: int):
    """The known coefficient set labels are drawn from, widths and scales
    set by hand: fixed w ~ N(0, 0.5), per-user w ~ N(0, 0.3);
    the last fixed column and the last per-user column are intercepts."""
    rng = np.random.default_rng([seed, 0])
    return (rng.normal(0, 0.5, D_FIXED),
            rng.normal(0, 0.3, (N_USERS, D_USER)))


def _write_part(seed: int, part: int, n_rows: int, uid0: int, path: str,
                head_path: str | None = None, head_rows: int = 0) -> int:
    """One Avro part file of ``n_rows`` examples from (seed, part); with
    ``head_path`` its first ``head_rows`` records are written there too.
    Runs in a writer child: imports nothing that imports jax."""
    from photon_ml_tpu.io import schemas
    from photon_ml_tpu.io.avro_codec import write_container

    w, wu = _truth(seed)
    rng = np.random.default_rng([seed, 1 + part])
    x = rng.normal(0, 1, (n_rows, D_FIXED - 1))
    xu = rng.normal(0, 1, (n_rows, D_USER - 1))
    users = rng.integers(0, N_USERS, n_rows)
    z = (x @ w[:-1] + w[-1]
         + np.einsum("nd,nd->n", xu, wu[users, :-1]) + wu[users, -1])
    y = (rng.random(n_rows) < 1.0 / (1.0 + np.exp(-z))).astype(float)
    names = ([f"g{j}" for j in range(D_FIXED - 1)]
             + [f"u{j}" for j in range(D_USER - 1)])
    values = np.hstack([x, xu])

    def records(n):
        for i in range(n):
            yield {"uid": f"r{uid0 + i}", "label": float(y[i]),
                   "features": [{"name": nm, "term": None, "value": v}
                                for nm, v in zip(names, values[i].tolist())],
                   "weight": None, "offset": None,
                   "metadataMap": {"userId": f"user{users[i]}"}}

    write_container(path, schemas.TRAINING_EXAMPLE, records(n_rows))
    if head_path is not None:
        write_container(head_path, schemas.TRAINING_EXAMPLE,
                        records(head_rows))
    return n_rows


def _writer_init() -> None:
    # Writers never need a device, and the parent may hold the chip.
    os.environ["JAX_PLATFORMS"] = "cpu"


def make_data(seed: int, work: Path, rows: int) -> dict:
    """train/ (TRAIN_PARTS files), validate/ (rows/10), serve/ (the first
    SERVE_ROWS validation records again) and the two feature-index
    stores, written by a pool of spawn-started children."""
    from photon_ml_tpu.data.index_map import IndexMap

    t0 = time.perf_counter()
    dirs = {k: work / k for k in ("train", "validate", "serve", "index")}
    for d in dirs.values():
        d.mkdir(parents=True)
    for shard, prefix, d in (("global", "g", D_FIXED), ("user", "u", D_USER)):
        IndexMap.from_name_terms(
            ((f"{prefix}{j}", "") for j in range(d - 1)),
            add_intercept=True).save(dirs["index"] / f"{shard}.json")

    per = -(-rows // TRAIN_PARTS)
    jobs = [(seed, p, min(per, rows - p * per), p * per,
             str(dirs["train"] / f"part-{p:05d}.avro"))
            for p in range(TRAIN_PARTS) if p * per < rows]
    n_val = max(rows // 10, SERVE_ROWS)
    jobs.append((seed, TRAIN_PARTS, n_val, rows,
                 str(dirs["validate"] / "part-00000.avro"),
                 str(dirs["serve"] / "part-00000.avro"), SERVE_ROWS))
    with concurrent.futures.ProcessPoolExecutor(
            max_workers=min(len(jobs), os.cpu_count() or 1),
            mp_context=multiprocessing.get_context("spawn"),
            initializer=_writer_init) as pool:
        written = [f.result() for f in
                   [pool.submit(_write_part, *job) for job in jobs]]
    _emit(phase="data", seed=seed, rows=sum(written[:-1]),
          rows_cut_from=None if rows == ROWS else ROWS,
          validation_rows=written[-1], serve_rows=SERVE_ROWS,
          d_fixed=D_FIXED, users=N_USERS, d_user=D_USER,
          smoke_wall_seconds=round(time.perf_counter() - t0, 1))
    return dirs


# -- what the program itself can show ---------------------------------------


class _CompileCounts:
    """Compile requests and persistent-cache hits, from JAX's own
    monitoring events: hits == requests means nothing was compiled."""

    def __init__(self):
        import jax.monitoring

        self.requests = self.hits = 0
        jax.monitoring.register_event_listener(self._on_event)

    def _on_event(self, event: str, **_) -> None:
        if event == "/jax/compilation_cache/compile_requests_use_cache":
            self.requests += 1
        elif event == "/jax/compilation_cache/cache_hits":
            self.hits += 1

    def take(self) -> dict:
        out = {"requests": self.requests, "cache_hits": self.hits}
        self.requests = self.hits = 0
        return out


@contextlib.contextmanager
def _capture_cd_blocks():
    """Note every fused coordinate-descent block a run dispatches: the
    jitted function and its arguments as shapes with their shardings, so
    the compiled text of THE program that ran can be read afterwards."""
    import jax

    from photon_ml_tpu.algorithm.coordinate_descent import CoordinateDescent

    def abstract(a):
        if isinstance(a, jax.Array):
            # Only a committed array pins the program to its devices.
            return jax.ShapeDtypeStruct(
                a.shape, a.dtype,
                sharding=a.sharding if a.committed else None)
        return a

    calls = []
    original = CoordinateDescent._fused_block_fn

    def spying(self, *span):
        fn = original(self, *span)

        def dispatch(*args):
            calls.append((fn, jax.tree.map(abstract, args)))
            return fn(*args)

        return dispatch

    CoordinateDescent._fused_block_fn = spying
    try:
        yield calls
    finally:
        CoordinateDescent._fused_block_fn = original


def _assert_fused_kernel(call, coordinate: str) -> tuple:
    """Every bucket of the run took the fused Pallas kernel: the compiled
    block holds one custom call per bucket and the routing guard never
    fell back. Returns the bucket shapes and the compiled text."""
    from photon_ml_tpu.algorithm import coordinates

    fn, args = call
    text = fn.lower(*args).compile().as_text()
    # (entities, r, d) per bucket: RandomEffectCoordinate.step_data()[0]
    buckets = [list(block.x.shape) for block in args[0][coordinate][0]]
    found = text.count(KERNEL_MARKER)
    if found < len(buckets):
        raise AssertionError(
            f"{len(buckets)} random-effect buckets {buckets} but only "
            f"{found} {KERNEL_MARKER} in the compiled block")
    if coordinates._FALLBACK_WARNED:
        raise AssertionError("random-effect solve fell back to the vmapped "
                             f"path: {sorted(coordinates._FALLBACK_WARNED)}")
    return buckets, text


def _decoder_kind() -> str:
    from photon_ml_tpu.native import load_avro_native

    native = load_avro_native()
    if native is None or not hasattr(native, "decode_training_block"):
        raise AssertionError("the native Avro decoder did not build or load")
    return "native"


def _scores_by_uid(out_dir: Path) -> dict:
    from photon_ml_tpu.io.avro_codec import read_container

    return {r["uid"]: r["predictionScore"] for r in
            read_container(out_dir / "scores" / "part-00000.avro")}


# -- one chip: train, score, serve ------------------------------------------


def one_chip(seed: int, work: Path, rows: int = ROWS) -> None:
    from photon_ml_tpu.cli import game_scoring_driver, game_training_driver

    counts = _CompileCounts()
    dirs = make_data(seed, work, rows)

    t0 = time.perf_counter()
    with _capture_cd_blocks() as blocks:
        train = game_training_driver.run([
            "--train-input-dirs", str(dirs["train"]),
            "--validate-input-dirs", str(dirs["validate"]),
            "--feature-index-dir", str(dirs["index"]),
            "--output-dir", str(work / "model"),
            "--task-type", "LOGISTIC_REGRESSION",
            "--fixed-effect-data-configurations", "fixed:global",
            "--fixed-effect-optimization-configurations", FIXED_OPT,
            "--random-effect-data-configurations",
            "perUser:userId,user,1,-1,-1,-1",
            "--random-effect-optimization-configurations", USER_OPT,
            "--updating-sequence", "fixed,perUser",
            "--num-iterations", "2", "--evaluators", "AUC"])
    objective = train["objectiveHistory"]
    if not np.all(np.isfinite(objective)) or len(objective) != 4 \
            or not objective[-1] < objective[0]:
        raise AssertionError(f"objective not finite and falling: {objective}")
    auc = max(m["AUC"] for m in train["validationHistory"])
    if not auc > AUC_FLOOR:
        raise AssertionError(f"validation AUC {auc} <= floor {AUC_FLOOR}")
    buckets, _ = _assert_fused_kernel(blocks[0], "perUser")
    _emit(phase="train", smoke_wall_seconds=round(time.perf_counter() - t0, 1),
          rows=train["numRows"], objective=objective, validation_auc=auc,
          decoder=_decoder_kind(), re_buckets_entities_r_d=buckets,
          fused_kernel=True, device=train["device"], compile=counts.take())

    t0 = time.perf_counter()
    score = game_scoring_driver.run([
        "--input-dirs", str(dirs["validate"]),
        "--game-model-input-dir", str(work / "model" / "best"),
        "--output-dir", str(work / "score"), "--evaluators", "AUC"])
    if score["scoring_path"] != "device":
        raise AssertionError(f"scored on {score['scoring_path']!r}, "
                             "not on the device")
    if abs(score["metrics"]["AUC"] - auc) > 1e-3:
        raise AssertionError(f"scoring AUC {score['metrics']['AUC']} != "
                             f"training's validation AUC {auc}")
    _emit(phase="score", smoke_wall_seconds=round(time.perf_counter() - t0, 1),
          rows=score["num_rows"], scorer=score["scoring_path"],
          auc=score["metrics"]["AUC"], compile=counts.take())

    t0 = time.perf_counter()
    serve = game_scoring_driver.run([
        "--input-dirs", str(dirs["serve"]),
        "--game-model-input-dir", str(work / "model" / "best"),
        "--output-dir", str(work / "serve-out"), "--serve",
        "--serve-concurrency", "4", "--request-rows", "8"])
    fe = serve["frontend"]
    if not (fe["admitted"] == fe["completed"] == serve["num_requests"]
            and fe["rejected"] == 0 and fe["failed"] == 0
            and serve["num_rows"] == SERVE_ROWS):
        raise AssertionError(f"not every request was served: {fe}")
    batch, served = _scores_by_uid(work / "score"), \
        _scores_by_uid(work / "serve-out")
    if len(served) != SERVE_ROWS:
        raise AssertionError(f"{len(served)} served scores, "
                             f"expected {SERVE_ROWS}")
    uids = sorted(served)
    # float32 scoring: ~230-term dot products of O(1) terms, summed in
    # another order under another bucket padding.
    np.testing.assert_allclose([served[u] for u in uids],
                               [batch[u] for u in uids],
                               rtol=1e-4, atol=1e-4)
    _emit(phase="serve", smoke_wall_seconds=round(time.perf_counter() - t0, 1),
          requests=serve["num_requests"], admitted=fe["admitted"],
          completed=fe["completed"], rejected=fe["rejected"],
          failed=fe["failed"], rows=serve["num_rows"],
          dispatch_groups=fe["dispatch_groups"], compile=counts.take())


# -- four chips: the mesh paths and what each is compared with ---------------


def _device_peak_bytes() -> list:
    import jax

    return [d.memory_stats()["peak_bytes_in_use"] for d in jax.devices()]


def four_chips(seed: int, work: Path, rows: int = ROWS) -> None:
    import jax

    from photon_ml_tpu.cli import game_training_driver
    from photon_ml_tpu.data.avro_reader import read_game_dataset
    from photon_ml_tpu.data.paldb import load_feature_index_maps
    from photon_ml_tpu.data.random_effect import RandomEffectDataConfiguration
    from photon_ml_tpu.estimators.game_estimator import (
        FixedEffectSpec,
        GameEstimator,
        RandomEffectSpec,
    )
    from photon_ml_tpu.io.model_io import load_game_model
    from photon_ml_tpu.optimization.config import GLMOptimizationConfiguration
    from photon_ml_tpu.parallel import make_mesh
    from photon_ml_tpu.types import TaskType

    counts = _CompileCounts()
    dirs = make_data(seed, work, rows)
    maps = load_feature_index_maps(dirs["index"])

    # 1. The streamed mesh solve through the driver: 1x1, then 4x1, 2x2.
    coefficients = {}
    for shape in ("1x1", "4x1", "2x2"):
        t0 = time.perf_counter()
        out = work / f"stream-{shape}"
        summary = game_training_driver.run([
            "--train-input-dirs", str(dirs["train"]),
            "--feature-index-dir", str(dirs["index"]),
            "--output-dir", str(out),
            "--task-type", "LOGISTIC_REGRESSION",
            "--fixed-effect-data-configurations", "fixed:global",
            "--fixed-effect-optimization-configurations", STREAM_OPT,
            "--updating-sequence", "fixed", "--stream-train",
            "--hbm-budget", STREAM_HBM_BUDGET, "--mesh-shape", shape])
        model = load_game_model(out / "best", maps)
        w = np.asarray(model.models["fixed"].glm.coefficients.means)
        if w.shape != (D_FIXED,) or not np.all(np.isfinite(w)):
            raise AssertionError(f"{shape}: bad coefficients {w.shape}")
        coefficients[shape] = w
        per_device = summary["stream_train"]["cache"]["per_device_bytes"]
        n_dev = 1 if shape == "1x1" else 4
        peaks = _device_peak_bytes()
        if len(per_device) != n_dev or min(per_device) <= 0 \
                or min(peaks[:n_dev]) <= 0:
            raise AssertionError(
                f"{shape}: cached blocks are not on {n_dev} distinct "
                f"devices: cache {per_device}, device peaks {peaks}")
        np.testing.assert_allclose(w, coefficients["1x1"], rtol=1e-5,
                                   err_msg=f"{shape} vs 1x1")
        _emit(phase=f"stream_train_{shape}",
              smoke_wall_seconds=round(time.perf_counter() - t0, 1),
              rows=summary["numRows"], objective=summary["objectiveHistory"],
              per_device_bytes=per_device, device_peak_bytes=peaks,
              allclose_1x1_rtol_1e5=True,
              bitwise_equal_1x1=bool(np.array_equal(w, coefficients["1x1"])),
              compile=counts.take())

    # 2. One coordinate-descent iteration, entity- and row-sharded over a
    # four-device mesh, against the same on one chip.
    t0 = time.perf_counter()
    data, _ = read_game_dataset(dirs["train"], id_types=["userId"],
                                feature_shard_maps=maps)
    specs = [
        FixedEffectSpec("fixed", "global", [
            GLMOptimizationConfiguration.parse(FIXED_OPT.split(":")[1])]),
        RandomEffectSpec(
            "perUser",
            RandomEffectDataConfiguration.parse("userId,user,1,-1,-1,-1"),
            [GLMOptimizationConfiguration.parse(USER_OPT.split(":")[1])],
            intercept_col=maps["user"].intercept_index)]
    fitted = {}
    for label, mesh in (("one_chip", None), ("mesh4", make_mesh(4))):
        with _capture_cd_blocks() as blocks:
            (_, result), = GameEstimator(
                TaskType.LOGISTIC_REGRESSION, specs, num_iterations=1,
                mesh=mesh).fit(data)
        fitted[label] = (result, blocks[0])
    (one, one_call), (sharded, sharded_call) = \
        fitted["one_chip"], fitted["mesh4"]
    buckets, _ = _assert_fused_kernel(one_call, "perUser")
    sharded_buckets, text = _assert_fused_kernel(sharded_call, "perUser")
    if "all-reduce" not in text:
        raise AssertionError("the sharded solve holds no all-reduce")
    # Both runs stop where the objective's relative change falls under
    # the solvers' tolerances (1e-7 fixed, 1e-6 per user), so they reach
    # the same objective to ~1e-6 — and, stopped at relative tolerance
    # eps on a problem whose curvature is at least the L2 weight 1, sit
    # within sqrt(2 eps f) ~ 2.5e-3 of the optimum (f ~ 3 per user).
    np.testing.assert_allclose(sharded.objective_history,
                               one.objective_history, rtol=1e-5)
    tol = dict(rtol=0, atol=5e-3)
    w_one = np.asarray(one.model.models["fixed"].glm.coefficients.means)
    w_mesh = np.asarray(sharded.model.models["fixed"].glm.coefficients.means)
    np.testing.assert_allclose(w_mesh, w_one, **tol)
    worst = float(np.max(np.abs(w_mesh - w_one)))
    for c_one, c_mesh in zip(one.model.models["perUser"].local_coefs,
                             sharded.model.models["perUser"].local_coefs):
        c_one = np.asarray(c_one)
        c_mesh = np.asarray(c_mesh)[:len(c_one)]  # entity padding rows
        np.testing.assert_allclose(c_mesh, c_one, **tol)
        worst = max(worst, float(np.max(np.abs(c_mesh - c_one))))
    _emit(phase="game_estimator_mesh4",
          smoke_wall_seconds=round(time.perf_counter() - t0, 1),
          rows=int(data.num_rows), re_buckets_entities_r_d=buckets,
          sharded_re_buckets_entities_r_d=sharded_buckets,
          all_reduce=True, kernel_under_shard_map=True,
          objective_one_chip=one.objective_history,
          objective_mesh4=sharded.objective_history,
          max_abs_coefficient_difference=worst, tolerance=tol,
          mesh_devices=[str(d) for d in jax.devices()[:4]],
          compile=counts.take())


# -- entry -----------------------------------------------------------------


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument("--four-chips", action="store_true",
                        help="run the mesh phases on four chips and "
                             "nothing else")
    args = parser.parse_args(argv)

    # The C decoder is built from the committed _avro_native.c and from
    # nothing left on disk (a copied tree scrambles the mtimes its
    # staleness check reads).
    shutil.rmtree(ROOT / "photon_ml_tpu" / "native" / "_build",
                  ignore_errors=True)
    from photon_ml_tpu.cli import device_summary
    from photon_ml_tpu.utils.compile_cache import enable_compile_cache

    cache_dir = enable_compile_cache()
    device = device_summary()
    need = 4 if args.four_chips else 1
    if device["platform"] != "tpu" or device["count"] < need:
        print(f"chip_smoke needs {need} tpu device(s), JAX found {device}",
              file=sys.stderr)
        return 1
    _emit(phase="start", device=device, compile_cache_dir=cache_dir,
          seed=args.seed, four_chips=args.four_chips)

    shutil.rmtree(WORK, ignore_errors=True)
    (four_chips if args.four_chips else one_chip)(args.seed, WORK)
    shutil.rmtree(WORK)
    _emit(ok=True, device=device)
    return 0


if __name__ == "__main__":
    sys.exit(main())
