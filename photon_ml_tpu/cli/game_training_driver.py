"""GAME training driver (reference: ml/cli/game/training/Driver.scala:43-298,
params from ml/estimators/GameParams.scala:40-427).

Coordinate mini-DSLs preserved from the reference:
  --fixed-effect-data-configurations   name:featureShardId
  --random-effect-data-configurations  name:reType,shardId,numPartitions,
                                       activeBound,passiveBound,ratio[,proj]
  --fixed-effect-optimization-configurations / --random-effect-...:
                                       name:maxIter,tol,λ,rate,optimizer,reg
                                       (| separates grid points)
  --updating-sequence                  comma-separated coordinate names
Outputs: <output-dir>/best/ (saved GAME model), metrics.json, log.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

from photon_ml_tpu import telemetry
from photon_ml_tpu.cli import device_summary
from photon_ml_tpu.cli.obs import DriverObservability, add_observability_args
from photon_ml_tpu.data.avro_reader import read_game_dataset
from photon_ml_tpu.data.random_effect import RandomEffectDataConfiguration
from photon_ml_tpu.estimators.game_estimator import (
    FactoredRandomEffectSpec,
    FixedEffectSpec,
    GameEstimator,
    RandomEffectSpec,
)
from photon_ml_tpu.evaluation import build_evaluator
from photon_ml_tpu.io.model_io import save_game_model
from photon_ml_tpu.optimization.config import (
    FactoredRandomEffectOptimizationConfiguration,
    GLMOptimizationConfiguration,
)
from photon_ml_tpu.telemetry import span
from photon_ml_tpu.types import TaskType
from photon_ml_tpu.utils.compile_cache import (
    compile_ledger,
    enable_compile_cache,
)
from photon_ml_tpu.utils.date_range import resolve_input_dirs
from photon_ml_tpu.utils.events import (
    EventEmitter,
    PhotonOptimizationLogEvent,
    TrainingFinishEvent,
    TrainingStartEvent,
)
from photon_ml_tpu.utils.logging_utils import setup_photon_logger
from photon_ml_tpu.utils.profiling import maybe_trace


def _parse_named(values, what):
    out = {}
    for item in values or []:
        name, _, rest = item.partition(":")
        if not rest:
            raise ValueError(f"bad {what} {item!r}: expected 'name:...'")
        out[name.strip()] = rest.strip()
    return out


def _positive_int(s: str) -> int:
    v = int(s)
    if v < 1:
        raise argparse.ArgumentTypeError(f"must be >= 1, got {v}")
    return v


def _parse_mesh_shape(s: str) -> tuple:
    """'RxC' -> (R, C): data-axis x model-axis device extents."""
    parts = s.strip().lower().split("x")
    if len(parts) != 2:
        raise argparse.ArgumentTypeError(
            f"bad mesh shape {s!r} (expected RxC, e.g. 2x2, 4x1)")
    try:
        r, c = int(parts[0]), int(parts[1])
    except ValueError:
        raise argparse.ArgumentTypeError(
            f"bad mesh shape {s!r} (expected RxC, e.g. 2x2, 4x1)")
    if r < 1 or c < 1:
        raise argparse.ArgumentTypeError(
            f"mesh extents must be >= 1, got {r}x{c}")
    return (r, c)


def _mesh_shape(args) -> tuple | None:
    """Resolved (data, model) mesh extents: --mesh-shape RxC, or the
    back-compat --mesh-devices N == Nx1; None when neither is given."""
    if args.mesh_shape is not None:
        return args.mesh_shape
    if args.mesh_devices is not None:
        return (args.mesh_devices, 1)
    return None


_BYTE_SUFFIXES = {"K": 1 << 10, "M": 1 << 20, "G": 1 << 30, "T": 1 << 40}


def parse_byte_size(s: str) -> int:
    """'512M', '8G', '1048576' -> bytes."""
    s = s.strip().upper().removesuffix("B")
    mult = 1
    if s and s[-1] in _BYTE_SUFFIXES:
        mult = _BYTE_SUFFIXES[s[-1]]
        s = s[:-1]
    try:
        v = int(float(s) * mult)
    except (ValueError, OverflowError):  # OverflowError: 'inf', '1e999'
        raise argparse.ArgumentTypeError(
            f"bad byte size {s!r} (expected e.g. 512M, 8G, 1048576)")
    if v < 1:
        raise argparse.ArgumentTypeError(f"byte size must be >= 1, got {v}")
    return v


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="photon-game-training-driver",
        description="Train GAME models (fixed + random effects)")
    p.add_argument("--train-input-dirs", required=True)
    p.add_argument("--validate-input-dirs", default=None)
    p.add_argument("--output-dir", required=True)
    p.add_argument("--task-type", required=True,
                   choices=[t.value for t in TaskType])
    p.add_argument("--fixed-effect-data-configurations", nargs="*",
                   default=[], metavar="name:featureShardId")
    p.add_argument("--fixed-effect-optimization-configurations", nargs="*",
                   default=[], metavar="name:optConfig[|optConfig...]")
    p.add_argument("--random-effect-data-configurations", nargs="*",
                   default=[], metavar="name:reDataConfig")
    p.add_argument("--random-effect-optimization-configurations", nargs="*",
                   default=[], metavar="name:optConfig[|optConfig...]")
    p.add_argument("--factored-random-effect-data-configurations", nargs="*",
                   default=[], metavar="name:reDataConfig")
    p.add_argument("--factored-random-effect-optimization-configurations",
                   nargs="*", default=[],
                   metavar="name:reOpt;latentOpt;mfMaxIter,numFactors[|...]")
    p.add_argument("--updating-sequence", required=True,
                   help="comma-separated coordinate order")
    p.add_argument("--train-date-range", default=None,
                   help="yyyyMMdd-yyyyMMdd; expands daily/yyyy/MM/dd "
                        "subdirs of the train input dirs")
    p.add_argument("--train-date-range-days-ago", default=None,
                   help="start-end in days ago, e.g. 90-1")
    p.add_argument("--validate-date-range", default=None)
    p.add_argument("--validate-date-range-days-ago", default=None)
    # >= 1 enforced: the stream-train λ-grid loop reads the last
    # tracker after its solves, and 0 iterations never made a model on
    # any path anyway.
    p.add_argument("--num-iterations", type=_positive_int, default=1)
    p.add_argument("--checkpoint-dir", default=None,
                   help="resumable coordinate-descent checkpoints land "
                        "here; a rerun resumes from the latest")
    p.add_argument("--checkpoint-interval", type=_positive_int, default=1,
                   help="coordinate updates between checkpoints (>=1)")
    p.add_argument("--evaluators", default=None,
                   help="comma-separated evaluator specs (first selects)")
    p.add_argument("--id-types", default=None,
                   help="extra entity id columns to read from metadataMap "
                        "(defaults to the random-effect types)")
    p.add_argument("--ingest-workers", default="auto",
                   help="Avro decode worker processes: 'auto' (usable "
                        "cores) or an int; >= 2 decodes file shards in "
                        "parallel with byte-identical output, 1 forces "
                        "single-process decode")
    p.add_argument("--feature-index-dir", default=None,
                   help="pre-built feature index stores keyed by shard id: "
                        "the reference's partitioned PalDB stores "
                        "(paldb-partition-<shard>-<N>.dat, "
                        "ml/util/PalDBIndexMap.scala) or this package's "
                        "<shard>.json stores; replaces the Avro-scan "
                        "index-building pass")
    p.add_argument("--profile-output-dir", default=None,
                   help="write a jax.profiler trace of training here "
                        "(view with XProf/TensorBoard)")
    p.add_argument("--save-all-models", default="false",
                   choices=["true", "false"],
                   help="model-output-mode ALL vs BEST")
    p.add_argument("--stream-train", action="store_true",
                   help="out-of-core training: ingest the training Avro "
                        "through the block-streaming C-decoded pipeline "
                        "in --batch-rows batches (host memory stays "
                        "O(batch)) instead of one-shot-materializing it. "
                        "Without --hbm-budget the shards assemble into "
                        "the exact one-shot device batch (byte-identical "
                        "model, fused solvers); with --hbm-budget the "
                        "solve streams over a device shard cache with "
                        "replay-aware spill. Supports a single "
                        "fixed-effect "
                        "coordinate")
    p.add_argument("--batch-rows", type=_positive_int, default=4096,
                   help="rows per streamed ingest batch (and per cached "
                        "device shard in --hbm-budget mode)")
    p.add_argument("--hbm-budget", default=None, metavar="BYTES",
                   type=parse_byte_size,
                   help="device-memory budget for cached feature blocks "
                        "(e.g. 512M, 8G): furthest-next-use shards "
                        "spill to host column buffers and re-upload "
                        "overlapped with the accumulate. Selects the "
                        "sharded streaming solve (L2 LBFGS/TRON only). "
                        "With --mesh-devices the budget is PER DEVICE")
    p.add_argument("--grid-batched", choices=["auto", "on", "off"],
                   default="auto",
                   help="batch the λ₂ grid into ONE streamed sweep "
                        "(--stream-train --hbm-budget): coefficients "
                        "stack to [G, d] and every feature pass over "
                        "the shard cache advances ALL G grid points "
                        "through vmapped per-bucket kernels, so a "
                        "sweep costs the slowest point's pass count "
                        "instead of the sum over points (~G× less "
                        "decode + re-upload traffic). 'auto' (default) "
                        "batches when the grid has > 1 point and is "
                        "batchable (homogeneous LBFGS/TRON, L2 only); "
                        "'on' forces batching and errors when it "
                        "can't; 'off' keeps the sequential per-λ "
                        "sweep. G=1 batched delegates to the scalar "
                        "streamed solver (bit-identical model bytes), "
                        "and exact selection ties break to the "
                        "smallest λ on every path "
                        "(docs/SCALE.md §Batched λ-grid)")
    p.add_argument("--mesh-devices", type=_positive_int, default=None,
                   metavar="N",
                   help="fold the --hbm-budget streaming solve over a "
                        "1-D mesh of the first N devices: cached shards "
                        "place round-robin (shard i on device i mod N), "
                        "per-shard partials accumulate on their own "
                        "device, and the fold combines in fixed shard "
                        "order — the model is bit-identical for every "
                        "N (docs/SCALE.md §Training memory envelope). "
                        "Requires --stream-train; N > 1 additionally "
                        "requires --hbm-budget. N=1 is exactly the "
                        "single-device fold. Equivalent to "
                        "--mesh-shape Nx1")
    p.add_argument("--mesh-shape", type=_parse_mesh_shape, default=None,
                   metavar="RxC",
                   help="fold the --hbm-budget streaming solve over a "
                        "2-D (data x model) mesh of R x C devices: "
                        "cached shards place round-robin over the R "
                        "data rows AND split into C column blocks of "
                        "the coefficient dimension, one per model-axis "
                        "device — no device holds a full-width "
                        "coefficient vector (per-device HBM ~ "
                        "budget/(R*C), docs/SCALE.md). Margins chain "
                        "across each row's devices, gradients "
                        "re-assemble by deterministic column concat, "
                        "so the model is bit-identical for every "
                        "shape in {1x1, 2x1, 1x2, 2x2, ...}. "
                        "Back-compat: --mesh-devices N == Nx1 (pass "
                        "one of the two). Requires --stream-train; "
                        "R*C > 1 additionally requires --hbm-budget")
    p.add_argument("--spill-dtype", choices=["f32", "bf16"],
                   default="f32",
                   help="--hbm-budget spill-buffer encoding: 'f32' "
                        "(default) spills evicted feature blocks as the "
                        "raw padded f32/i32 triplet (re-uploads are the "
                        "evicted bytes — today's bitwise guarantees); "
                        "'bf16' spills bfloat16 values + delta-encoded "
                        "u8/u16 indices (~1/3 of the f32 spill bytes "
                        "AND per-epoch re-upload traffic; restore "
                        "decodes back to f32 on device, with documented "
                        "parity bounds vs the f32-spill model — "
                        "docs/SCALE.md)")
    p.add_argument("--spill-source", choices=["buffer", "redecode"],
                   default="buffer",
                   help="where evicted --hbm-budget blocks come back "
                        "from: 'buffer' (default) re-uploads host spill "
                        "buffers (host RAM O(dataset)); 'redecode' "
                        "keeps NO host copy — cache misses re-decode "
                        "the covering Avro container blocks "
                        "(prefetch-overlapped with the accumulate), so "
                        "host memory is O(budget + one block) and "
                        "trainable size is disk-bounded")
    p.add_argument("--feeder", choices=["auto", "native", "python"],
                   default="auto",
                   help="--stream-train decode path (see "
                        "data/block_stream.py); 'python' forces the "
                        "byte-identical record-loop fallback")
    p.add_argument("--prefetch-batches", type=int, default=2,
                   help="decode-ahead depth of the --stream-train feeder "
                        "(and spill re-upload look-ahead); 0 disables")
    p.add_argument("--distmon", action="store_true",
                   help="distribution observability (--stream-train "
                        "only): streaming label/weight/offset/feature "
                        "sketches piggybacked on the decode pass (zero "
                        "extra feature passes; snapshots bitwise-"
                        "identical across residency/feeder/prefetch "
                        "configs), per-λ convergence rings, a "
                        "data_quality metrics.json block, live /distz "
                        "(with --obs-port), and a reference "
                        "distribution snapshot (label + training-score "
                        "quantiles) stamped into the model artifact "
                        "for serving-side drift scoring "
                        "(docs/OBSERVABILITY.md §Distributions & "
                        "drift)")
    p.add_argument("--trace-out", default=None, metavar="PATH",
                   help="write a Chrome trace-event JSON of the run's "
                        "pipeline spans here (load in Perfetto — "
                        "docs/OBSERVABILITY.md)")
    p.add_argument("--job-name", default="photon-game-training",
                   help="job name carried on Training{Start,Finish} "
                        "events")
    p.add_argument("--event-listeners", default=None,
                   help="comma-separated EventListener class paths "
                        "registered by name (utils/events.py) — the "
                        "reference's listener registration, e.g. "
                        "my.module.MyListener")
    add_observability_args(p)
    return p


def run(argv=None) -> dict:
    enable_compile_cache()
    args = build_parser().parse_args(argv)
    out_dir = Path(args.output_dir)
    out_dir.mkdir(parents=True, exist_ok=True)
    logger = setup_photon_logger(out_dir)
    task = TaskType(args.task_type)
    t0 = time.perf_counter()
    # The driver owns this process's telemetry: per-run metrics + stage
    # spans land in metrics.json (and --trace-out); library code is
    # instrumented but silent outside a driver (docs/OBSERVABILITY.md).
    telemetry.reset()
    # Trace sampling is on whenever something will consume traces: a
    # --trace-out export, or the live plane (--obs-port serves /tracez
    # and federation merges it — a plane whose trace tail is always
    # empty breaks the fleet aggregator's per-process attribution).
    telemetry.enable(trace=bool(args.trace_out)
                     or args.obs_port is not None)
    # Live observability plane (docs/OBSERVABILITY.md §Live endpoints):
    # flight recorder armed for the whole run; with --obs-port a
    # multi-hour --stream-train becomes scrapeable, with a 1 Hz
    # heartbeat refreshing liveness gauges / registry deltas / SLO
    # burn between solver iterations. Construction/start INSIDE the
    # try: a bad --slo spec or occupied --obs-port must still unwind
    # through the finally below.
    obs = None
    emitter = EventEmitter()
    try:
        obs = DriverObservability(args, out_dir, heartbeat_s=1.0,
                                  role="training").start()
        for cp in (args.event_listeners or "").split(","):
            if cp.strip():
                emitter.register_listener_by_name(cp.strip())
        emitter.send_event(TrainingStartEvent(args.job_name))
        # Root span: config parsing, event emission, and glue between
        # the named phases land in `driver` SELF time (same scheme as
        # the scoring driver), so the stage table sums to the whole run
        # even on millisecond runs.
        with span("driver"):
            (sequence, results, best_configs, best_result, shard_maps,
             num_rows, stream_info, distmon_out) = _run_training(
                args, logger, task, emitter, obs)
            # Liveness vs readiness split: /readyz flips true only
            # after the solve succeeded (a just-booted process must
            # not scrape ready — docs/OBSERVABILITY.md §Federation).
            obs.mark_ready("solve_complete")
            _save_outputs(args, out_dir, logger, sequence, results,
                          best_configs, best_result, shard_maps,
                          extra_metadata=(
                              {"referenceDistributions":
                               distmon_out["reference"]}
                              if distmon_out is not None else None))
        summary = _write_summary(args, out_dir, logger, task, sequence,
                                 t0, results, best_configs, best_result,
                                 num_rows, stream_info, obs,
                                 data_quality=(
                                     distmon_out["data_quality"]
                                     if distmon_out is not None
                                     else None))
        emitter.send_event(
            TrainingFinishEvent(args.job_name, summary["totalSeconds"]))
        return summary
    except BaseException as e:
        # Unhandled fault: the phase spans have already unwound, so the
        # flight ring's last events cover the failing stage.
        if obs is not None:
            obs.dump_fault(e, logger)
        raise
    finally:
        # Exception or not: close listeners and disarm the process-wide
        # recorder/server so whatever runs next in this process starts
        # clean.
        emitter.clear_listeners()
        if obs is not None:
            obs.stop()
        telemetry.disable()


def _run_training(args, logger, task, emitter, obs):
    """Config parse + train (one-shot estimator or --stream-train);
    returns everything the save/summary tail needs. ``obs`` (the
    driver's observability plane) lets the stream-train path register
    live /statusz providers as its components come up."""
    fe_data = _parse_named(args.fixed_effect_data_configurations,
                           "fixed-effect data config")
    fe_opt = _parse_named(args.fixed_effect_optimization_configurations,
                          "fixed-effect optimization config")
    re_data = {
        name: RandomEffectDataConfiguration.parse(cfg)
        for name, cfg in _parse_named(
            args.random_effect_data_configurations,
            "random-effect data config").items()}
    re_opt = _parse_named(args.random_effect_optimization_configurations,
                          "random-effect optimization config")
    fre_data = {
        name: RandomEffectDataConfiguration.parse(cfg)
        for name, cfg in _parse_named(
            args.factored_random_effect_data_configurations,
            "factored-random-effect data config").items()}
    fre_opt = _parse_named(
        args.factored_random_effect_optimization_configurations,
        "factored-random-effect optimization config")

    sequence = [s.strip() for s in args.updating_sequence.split(",")]
    for name in sequence:
        if name not in fe_data and name not in re_data \
                and name not in fre_data:
            raise ValueError(
                f"updating-sequence entry {name!r} has no data configuration")

    id_types = sorted(
        {c.random_effect_type for c in re_data.values()} |
        {c.random_effect_type for c in fre_data.values()} |
        {s.strip() for s in (args.id_types or "").split(",") if s.strip()})

    preloaded_maps = None
    if args.feature_index_dir:
        from photon_ml_tpu.data.paldb import load_feature_index_maps

        preloaded_maps = load_feature_index_maps(args.feature_index_dir)
        logger.info(
            "loaded feature index stores from %s: %s", args.feature_index_dir,
            {k: len(v) for k, v in sorted(preloaded_maps.items())})

    train_inputs = resolve_input_dirs(
        args.train_input_dirs,
        date_range=args.train_date_range,
        date_range_days_ago=args.train_date_range_days_ago)

    def parse_grid(s: str):
        return [GLMOptimizationConfiguration.parse(part)
                for part in s.split("|")]

    def opt_grid(table, name, flag):
        if name not in table:
            raise ValueError(
                f"coordinate {name!r} has no optimization configuration — "
                f"pass it via {flag} (have {sorted(table) or 'none'})")
        return parse_grid(table[name])

    evaluators = [build_evaluator(s.strip())
                  for s in (args.evaluators or "").split(",") if s.strip()]

    if args.mesh_shape is not None and args.mesh_devices is not None:
        raise ValueError(
            "--mesh-shape and --mesh-devices are two spellings of the "
            "same mesh (--mesh-devices N == --mesh-shape Nx1); pass one")
    mesh_rc = _mesh_shape(args)
    if mesh_rc is not None and not args.stream_train:
        raise ValueError(
            "--mesh-devices/--mesh-shape apply to the --stream-train "
            "solve; pass --stream-train (and --hbm-budget for a mesh "
            "of > 1 device)")
    if mesh_rc is not None and mesh_rc[0] * mesh_rc[1] > 1 \
            and args.hbm_budget is None:
        raise ValueError(
            "a mesh of > 1 device requires --hbm-budget: the device "
            "fold runs over the sharded shard-cache solve (the "
            "resident assembled path is a single fused device batch)")
    if args.grid_batched != "auto" and not args.stream_train:
        raise ValueError(
            "--grid-batched applies to the --stream-train λ-grid "
            "sweep; pass --stream-train (the one-shot estimator "
            "trains the grid one combination at a time)")
    if args.grid_batched == "on" and args.hbm_budget is None:
        raise ValueError(
            "--grid-batched on requires --hbm-budget: the batched "
            "sweep runs over the sharded shard-cache solve (the "
            "resident assembled path reuses the fused one-shot "
            "solvers, which already share the device batch across "
            "the grid)")
    if args.spill_dtype != "f32" and args.hbm_budget is None:
        raise ValueError(
            "--spill-dtype applies to --hbm-budget spill buffers; pass "
            "--stream-train --hbm-budget (the resident assembled path "
            "never spills)")
    if args.spill_source != "buffer" and args.hbm_budget is None:
        raise ValueError(
            "--spill-source applies to --hbm-budget eviction; pass "
            "--stream-train --hbm-budget (the resident assembled path "
            "never evicts)")
    if args.spill_source == "redecode" and args.spill_dtype != "f32":
        raise ValueError(
            "--spill-dtype bf16 compresses host spill buffers, but "
            "--spill-source redecode keeps none — the combination "
            "would silently train as f32; pick one")
    if args.distmon and not args.stream_train:
        raise ValueError(
            "--distmon piggybacks distribution sketches on the "
            "--stream-train decode pass; pass --stream-train (the "
            "one-shot path has data/stats.py BasicStatisticalSummary "
            "for one-shot statistics)")

    if args.stream_train:
        if re_data or len(sequence) != 1 \
                or (sequence[0] not in fe_data
                    and sequence[0] not in fre_data):
            raise ValueError(
                "--stream-train supports exactly one fixed-effect "
                "or factored-random-effect coordinate (plain random "
                "effects need entity grouping over the full dataset); "
                f"got sequence {sequence}")
        if sequence[0] in fre_data and _mesh_shape(args) is not None:
            raise ValueError(
                "--mesh-devices/--mesh-shape are not supported for "
                "streamed MF coordinates yet (the factor-table device "
                "fold is the noted follow-on); drop the flag")
        with maybe_trace(args.profile_output_dir):
            if sequence[0] in fre_data:
                (results, best_configs, best_result, shard_maps,
                 num_rows, stream_info, distmon_out) = _stream_train_mf(
                    args, logger, task, fre_data, fre_opt, sequence,
                    train_inputs, evaluators, preloaded_maps, emitter,
                    obs)
            else:
                (results, best_configs, best_result, shard_maps,
                 num_rows, stream_info, distmon_out) = _stream_train(
                    args, logger, task, fe_data, fe_opt, sequence,
                    train_inputs, evaluators, preloaded_maps, opt_grid,
                    emitter, obs)
        return (sequence, results, best_configs, best_result, shard_maps,
                num_rows, stream_info, distmon_out)

    logger.info("reading training data from %s (ingest workers: %s)",
                train_inputs, args.ingest_workers)
    with span("ingest"):
        data, shard_maps = read_game_dataset(
            train_inputs, id_types=id_types,
            feature_shard_maps=preloaded_maps,
            ingest_workers=args.ingest_workers)
        validation = None
        if args.validate_input_dirs:
            validate_inputs = resolve_input_dirs(
                args.validate_input_dirs,
                date_range=args.validate_date_range,
                date_range_days_ago=args.validate_date_range_days_ago)
            validation, _ = read_game_dataset(
                validate_inputs, id_types=id_types,
                feature_shard_maps=shard_maps,
                ingest_workers=args.ingest_workers)

    specs = []
    for name in sequence:
        if name in fe_data:
            shard = fe_data[name]
            if shard not in shard_maps:
                raise ValueError(
                    f"fixed-effect coordinate {name!r} references unknown "
                    f"feature shard {shard!r} (have {sorted(shard_maps)})")
            specs.append(FixedEffectSpec(
                name=name, feature_shard_id=shard,
                configs=opt_grid(
                    fe_opt, name,
                    "--fixed-effect-optimization-configurations")))
        elif name in fre_data:
            cfg = fre_data[name]
            if cfg.feature_shard_id not in shard_maps:
                raise ValueError(
                    f"factored-random-effect coordinate {name!r} references "
                    f"unknown feature shard {cfg.feature_shard_id!r}")
            if name not in fre_opt:
                raise ValueError(
                    f"coordinate {name!r} has no optimization configuration "
                    "— pass it via "
                    "--factored-random-effect-optimization-configurations")
            specs.append(FactoredRandomEffectSpec(
                name=name, data_config=cfg,
                configs=[FactoredRandomEffectOptimizationConfiguration
                         .parse(part)
                         for part in fre_opt[name].split("|")]))
        else:
            cfg = re_data[name]
            if cfg.feature_shard_id not in shard_maps:
                raise ValueError(
                    f"random-effect coordinate {name!r} references unknown "
                    f"feature shard {cfg.feature_shard_id!r}")
            imap = shard_maps[cfg.feature_shard_id]
            specs.append(RandomEffectSpec(
                name=name, data_config=cfg,
                configs=opt_grid(
                    re_opt, name,
                    "--random-effect-optimization-configurations"),
                intercept_col=(imap.intercept_index
                               if imap.intercept_index >= 0 else None)))

    estimator = GameEstimator(
        task_type=task, coordinate_specs=specs,
        num_iterations=args.num_iterations,
        validation_evaluators=evaluators)
    with maybe_trace(args.profile_output_dir), span("solve"):
        results = estimator.fit(
            data, validation_data=validation,
            checkpoint_dir=(Path(args.checkpoint_dir)
                            if args.checkpoint_dir else None),
            checkpoint_interval=args.checkpoint_interval)
    best_configs, best_result = estimator.select_best(results)
    return (sequence, results, best_configs, best_result, shard_maps,
            int(data.num_rows), None, None)


def _save_outputs(args, out_dir, logger, sequence, results,
                  best_configs, best_result, shard_maps,
                  extra_metadata=None) -> None:
    """Model + index-map save (the ``finalize`` phase) — shared by the
    one-shot and --stream-train paths (identical artifacts either
    way). ``extra_metadata`` merges extra model-metadata.json keys in
    (the --distmon ``referenceDistributions`` snapshot)."""
    from photon_ml_tpu.models.tracking import summarize_trackers

    # Aggregate per-entity optimizer telemetry (convergence-reason counts,
    # iteration/objective stats per coordinate per update) — the
    # operational summary the reference computes via RDD.stats() in
    # ml/optimization/game/*Tracker.scala.
    tracker_summary = summarize_trackers(best_result.trackers)
    for name, per_update in tracker_summary.items():
        if per_update:
            last = per_update[-1]
            logger.info(
                "coordinate %s (last update): %d solves, reasons %s, "
                "iterations mean %.1f max %d", name, last["numSolves"],
                last["convergenceReasons"], last["iterations"]["mean"],
                int(last["iterations"]["max"]))

    with span("finalize"):
        save_game_model(
            out_dir / "best", best_result.best_model, shard_maps,
            metadata_extras={
                "optimizationConfigurations": {
                    k: v.to_json() for k, v in best_configs.items()},
                "updatingSequence": sequence,
                "numIterations": args.num_iterations,
                "optimizationTrackers": tracker_summary,
                **(extra_metadata or {}),
            })
        # Persist the feature index maps next to the model so the scoring
        # driver can decode features identically (the reference ships
        # PalDB stores).
        index_dir = out_dir / "best" / "feature-indexes"
        index_dir.mkdir(parents=True, exist_ok=True)
        for shard, imap in shard_maps.items():
            imap.save(index_dir / f"{shard}.json")
        if args.save_all_models == "true":
            for i, (configs, result) in enumerate(results):
                save_game_model(
                    out_dir / "all" / str(i), result.model, shard_maps,
                    metadata_extras={
                        "optimizationConfigurations": {
                            k: v.to_json() for k, v in configs.items()}})


def _write_summary(args, out_dir, logger, task, sequence, t0, results,
                   best_configs, best_result, num_rows,
                   stream_info, obs, data_quality=None) -> dict:
    """metrics.json + trace export — runs AFTER the root ``driver`` span
    closed, so the telemetry block it snapshots includes the root's
    self time (the otherwise-unattributed driver glue)."""
    wall = time.perf_counter() - t0
    summary = {
        "taskType": task.value,
        "numRows": num_rows,
        "num_rows": num_rows,
        "updatingSequence": sequence,
        "numCombos": len(results),
        "bestConfigs": {k: v.to_string() for k, v in best_configs.items()},
        "objectiveHistory": best_result.objective_history,
        "validationHistory": best_result.validation_history,
        # HOST DISPATCH seconds per coordinate (an enqueue, not device
        # time: docs/OBSERVABILITY.md "The training fit").
        "coordinateSeconds": best_result.timings,
        "totalSeconds": wall,
        "total_seconds": wall,
        "device": device_summary(),
        # What tracing, lowering and compiling (or loading from the
        # persistent cache) cost, by jitted function: the 20 costliest.
        "compile": compile_ledger(top=20),
    }
    if stream_info is not None:
        # ``stream_train`` is the canonical snake_case schema; the
        # deprecated camelCase ``streamTrain`` alias rode one release
        # behind and is now removed (docs/OBSERVABILITY.md §Schema).
        summary["stream_train"] = stream_info
    if data_quality is not None:
        # --distmon: sketch summaries, per-λ convergence tails and the
        # canonical state hash (the residency-independence witness) —
        # docs/OBSERVABILITY.md §Distributions & drift.
        summary["data_quality"] = data_quality
    obs.finish(summary)
    summary["telemetry"] = telemetry.attribution_summary(wall)
    if args.trace_out:
        telemetry.export_chrome_trace(args.trace_out)
        logger.info("pipeline trace written to %s (load in Perfetto)",
                    args.trace_out)
    (out_dir / "metrics.json").write_text(json.dumps(summary, indent=2))
    logger.info("GAME training done in %.1fs", summary["totalSeconds"])
    return summary


def _stream_validate_many(game_models, args, shard_maps, evaluators,
                          logger):
    """Bounded-memory validation of ALL grid models in ONE decode pass:
    the validation container streams once (`BlockGameStream`,
    `--batch-rows` batches) and every model's serving engine scores each
    decoded batch, accumulating ONLY the evaluation columns
    (`StreamedEvalAccumulator` — shared with the scoring driver's
    --stream path) — never features. A G-point grid therefore costs one
    decode + G scores per batch, not G full decode passes. An empty
    validation input yields empty metric dicts."""
    from photon_ml_tpu.data.block_stream import BlockGameStream
    from photon_ml_tpu.evaluation.validation import StreamedEvalAccumulator
    from photon_ml_tpu.serving import StreamingGameScorer

    validate_inputs = resolve_input_dirs(
        args.validate_input_dirs,
        date_range=args.validate_date_range,
        date_range_days_ago=args.validate_date_range_days_ago)
    id_types = sorted({ev.id_type for ev in evaluators
                       if getattr(ev, "id_type", None)})
    engines = [StreamingGameScorer(m) for m in game_models]
    accs = [StreamedEvalAccumulator(id_types) for _ in game_models]
    stream = BlockGameStream(
        validate_inputs, id_types=id_types, feature_shard_maps=shard_maps,
        batch_rows=args.batch_rows, feeder=args.feeder,
        prefetch_depth=max(0, args.prefetch_batches))
    for ds in stream:
        for engine, acc in zip(engines, accs):
            acc.add(ds, engine.score(ds))
    metrics = [acc.metrics(evaluators) for acc in accs]
    logger.info("streamed validation (%d rows, %s feeder, %d models): %s",
                stream.rows, stream.decode_path, len(engines), metrics)
    return metrics


def _solve_grid_batched(args, logger, name, shard, task, grid, cache,
                        mesh, monitor, lam_label):
    """--grid-batched sweep: ONE StreamingFixedEffectCoordinate hosts
    the whole λ-grid and :func:`solve_fixed_effect_grid` advances all
    G points per feature pass over the shard cache ([G, d]
    coefficients, vmapped per-bucket kernels). Observability stays
    per-λ: each grid point keeps its own trace context (annotated with
    its grid row), --distmon convergence ring, and training-score
    sketch sliced from the batched [G, rows] margins. Returns the same
    (configs, CoordinateDescentResult) pairs the sequential sweep
    builds, plus the shared sharded objective for stream_info."""
    import time as _time

    from photon_ml_tpu.algorithm.coordinate_descent import (
        CoordinateDescentResult,
    )
    from photon_ml_tpu.algorithm.coordinates import (
        StreamingFixedEffectCoordinate,
        solve_fixed_effect_grid,
    )
    from photon_ml_tpu.models.game_model import GameModel

    G = len(grid)
    logger.info("λ-grid sweep batched: %d points advance per feature "
                "pass (--grid-batched %s)", G, args.grid_batched)
    coord = StreamingFixedEffectCoordinate(
        name=name, cache=cache, feature_shard_id=shard, task_type=task,
        config=grid[0], mesh=mesh)
    t0 = _time.perf_counter()
    rings, margins_holder = None, []
    if monitor is not None:
        from photon_ml_tpu.optimization.convergence import ConvergenceRing

        rings = []
        for cfg in grid:
            ring = ConvergenceRing()
            monitor.add_ring(lam_label(cfg), ring)
            rings.append(ring)
    # One trace context per λ-grid point, exactly as the sequential
    # sweep mints them — a row's divergence fault carries ITS trace_id
    # (plus grid row + λ) into the flight dump, not the sweep's.
    ctxs = []
    for gi, cfg in enumerate(grid):
        ctx = telemetry.mint("solve")
        ctx.annotate(coordinate=name,
                     reg_weight=cfg.regularization_weight,
                     optimizer=str(cfg.optimizer_type),
                     grid_row=gi, grid_width=G)
        ctxs.append(ctx)
    models = None
    trackers_per = [[] for _ in grid]
    obj_hist_per = [[] for _ in grid]
    for _ in range(args.num_iterations):
        pairs = solve_fixed_effect_grid(
            coord, grid, models=models, trace_ctxs=ctxs,
            convergence_rings=rings, margins_out=margins_holder)
        models = [m for m, _ in pairs]
        for gi, (_, res) in enumerate(pairs):
            trackers_per[gi].append(res)
            obj_hist_per[gi].append(float(res.value))
    shared = coord.sharded_objective
    if monitor is not None and margins_holder:
        for gi, cfg in enumerate(grid):
            monitor.observe_scores(
                lam_label(cfg),
                shared.host_scores_from_margins(
                    shared.grid_row_margins(margins_holder, gi)))
    elapsed = _time.perf_counter() - t0
    results = []
    for gi, cfg in enumerate(grid):
        ctxs[gi].annotate(
            iterations=int(trackers_per[gi][-1].iterations),
            reason=trackers_per[gi][-1].reason_enum().summary)
        ctxs[gi].finish("ok")
        gm = GameModel({name: models[gi]}, task)
        # The sweep IS one solve: every grid point reports the shared
        # wall time (the whole point — G points for one sweep's clock).
        results.append(({name: cfg}, CoordinateDescentResult(
            model=gm, objective_history=obj_hist_per[gi],
            validation_history=[], best_model=gm, best_metric=None,
            trackers={name: trackers_per[gi]},
            timings={name: elapsed})))
    return results, shared


def _stream_train(args, logger, task, fe_data, fe_opt, sequence,
                  train_inputs, evaluators, preloaded_maps, opt_grid,
                  emitter, obs):
    """Out-of-core training path (--stream-train): block-streamed ingest
    (host memory O(batch_rows)) into either

    - the EXACT assembled device batch + the untouched fused solvers
      (no --hbm-budget; model bytes identical to the one-shot driver), or
    - a DeviceShardCache + sharded streaming accumulate solve
      (--hbm-budget; replay-aware feature-block spill, deterministic
      partials — resident and eviction-forced runs write identical
      bytes), optionally folded over a --mesh-devices 1-D device mesh
      (round-robin shard placement, per-device accumulate, fixed-order
      combine — every mesh size writes the same model bytes; the HBM
      budget binds per device).

    Validation (when requested) streams through the serving engine in
    both modes."""
    import time as _time

    from photon_ml_tpu.algorithm.coordinate_descent import (
        CoordinateDescentResult,
    )
    from photon_ml_tpu.algorithm.coordinates import (
        StreamingFixedEffectCoordinate,
        grid_batchable,
        solve_fixed_effect_grid,
    )
    from photon_ml_tpu.data.avro_reader import build_index_map
    from photon_ml_tpu.data.block_stream import BlockGameStream
    from photon_ml_tpu.data.shard_cache import (
        DeviceShardCache,
        assemble_fixed_effect_batch,
    )
    from photon_ml_tpu.models.game_model import GameModel

    name = sequence[0]
    shard = fe_data[name]
    grid = opt_grid(fe_opt, name,
                    "--fixed-effect-optimization-configurations")
    if preloaded_maps is not None:
        if shard not in preloaded_maps:
            raise ValueError(
                f"fixed-effect coordinate {name!r} references unknown "
                f"feature shard {shard!r} "
                f"(have {sorted(preloaded_maps)})")
        shard_maps = {shard: preloaded_maps[shard]}
    else:
        logger.info("building feature index for shard %r from %s",
                    shard, train_inputs)
        with span("build_index"):
            shard_maps = {shard: build_index_map(
                train_inputs, ingest_workers=args.ingest_workers)}

    monitor = None
    if args.distmon:
        from photon_ml_tpu.data.distmon import (
            MonitoredStream,
            StreamingDistributionMonitor,
        )

        # Distribution sketches ride the decode pass: every batch the
        # stream yields is observed on its way to the cache/assembler
        # (on the prefetch thread when the feeder prefetches), so the
        # statistics cost zero extra feature passes and their state is
        # fixed by shard order — residency/feeder/prefetch-independent
        # like the model bytes.
        monitor = StreamingDistributionMonitor(feature_shards=[shard])
        obs.add_dist_provider("training", monitor.snapshot)
        obs.add_scrape_hook("distmon", monitor.publish_gauges)
        obs.add_sketch_provider("training", monitor.sketch_states)

    def make_stream():
        s = BlockGameStream(
            train_inputs, id_types=[], feature_shard_maps=shard_maps,
            batch_rows=args.batch_rows, feeder=args.feeder,
            prefetch_depth=max(0, args.prefetch_batches))
        return s if monitor is None else MonitoredStream(s, monitor)

    def lam_label(cfg):
        return f"{name}:l2={cfg.regularization_weight:g}"

    budget = args.hbm_budget  # parsed to bytes by argparse
    if args.checkpoint_dir and budget is not None:
        logger.warning("--checkpoint-dir is not supported with "
                       "--hbm-budget streaming solves; ignoring")

    if budget is None:
        # -- resident: exact assembly + the one-shot estimator ------------
        logger.info("stream-train (resident): assembling %r from %s in "
                    "%d-row batches", shard, train_inputs, args.batch_rows)
        with span("ingest"):
            data = assemble_fixed_effect_batch(make_stream(), shard)
        estimator = GameEstimator(
            task_type=task,
            coordinate_specs=[FixedEffectSpec(
                name=name, feature_shard_id=shard, configs=grid)],
            num_iterations=args.num_iterations,
            validation_evaluators=evaluators)
        # One trace context per λ-grid point, like the spill path
        # below: the resident fit delegates the whole sweep to the
        # estimator, so every grid point's trace spans the shared fit
        # (the batched-sweep convention — G points, one clock). Without
        # these the resident path's /tracez tail is empty for the whole
        # run, which breaks the fleet aggregator's per-process trace
        # attribution.
        ctxs = [telemetry.mint("solve") for _ in grid]
        for ctx, cfg in zip(ctxs, grid):
            ctx.annotate(coordinate=name, mode="resident",
                         reg_weight=cfg.regularization_weight,
                         optimizer=str(cfg.optimizer_type),
                         grid_points=len(grid))
        with span("solve"):
            results = estimator.fit(
                data, validation_data=None,
                checkpoint_dir=(Path(args.checkpoint_dir)
                                if args.checkpoint_dir else None),
                checkpoint_interval=args.checkpoint_interval)
        for ctx in ctxs:
            ctx.finish("ok")
        num_rows = data.num_rows
        stream_info = {
            "mode": "resident-assembled",
            "batch_rows": args.batch_rows,
            "hbm_budget_bytes": None,
            "mesh_devices": args.mesh_devices,
            "mesh_shape": _mesh_shape(args),
            "spill_dtype": None,  # nothing spills on the resident path
            "spill_source": None,
            "feeder": {k: v for k, v in data.ingest_stats.items()},
            "cache": None,
            # The fused one-shot solvers already share the assembled
            # device batch across the grid; batching is a spill-path
            # concept.
            "grid_batched": False,
            "grid_points": len(grid),
        }
    else:
        # -- spill: sharded streaming accumulate over the device cache ----
        mesh = None
        devices = None
        mesh_rc = _mesh_shape(args)
        col_blocks = 1
        if mesh_rc is not None and mesh_rc[0] * mesh_rc[1] > 1:
            from photon_ml_tpu.parallel import (
                make_mesh_2d, mesh_fold_devices,
            )

            mesh = make_mesh_2d(mesh_rc[0], mesh_rc[1])
            devices = mesh_fold_devices(mesh)
            col_blocks = mesh_rc[1]
        logger.info("stream-train (spill, hbm budget %d bytes%s, "
                    "spill %s/%s): caching %r from %s in %d-row shards",
                    budget,
                    (f" PER DEVICE x {len(devices)} mesh devices "
                     f"({mesh_rc[0]} data x {mesh_rc[1]} model)"
                     if devices else ""), args.spill_dtype,
                    args.spill_source, shard, train_inputs,
                    args.batch_rows)
        fetcher = None
        if args.spill_source == "redecode":
            from photon_ml_tpu.data.block_stream import BlockRandomAccess

            # The out-of-core miss path: evicted blocks re-decode their
            # covering container blocks by global row range instead of
            # re-uploading host spill buffers.
            fetcher = BlockRandomAccess(
                train_inputs, id_types=[], feature_shard_maps=shard_maps,
                feeder=args.feeder)
        with span("ingest"):
            cache = DeviceShardCache.from_stream(
                make_stream(), shard, hbm_budget_bytes=budget,
                prefetch_depth=max(0, args.prefetch_batches),
                devices=devices, spill_dtype=args.spill_dtype,
                spill_source=args.spill_source, redecode_fetch=fetcher,
                col_blocks=col_blocks)
        # Live residency view: a multi-hour spill train's /statusz
        # shows hits/misses/evictions/spill bytes as they happen —
        # mirroring what --serve registers for frontend stats.
        obs.add_status_provider("shard_cache", cache.stats)
        results = []
        shared = None
        batchable, why_not = grid_batchable(grid)
        if args.grid_batched == "on" and not batchable:
            raise ValueError(
                f"--grid-batched on: λ-grid is not batchable: {why_not}")
        use_batched = batchable and (
            args.grid_batched == "on"
            or (args.grid_batched == "auto" and len(grid) > 1))
        if args.grid_batched == "auto" and len(grid) > 1 and not batchable:
            logger.info("λ-grid sweeps sequentially (%s)", why_not)
        with span("solve"):
            if use_batched:
                results, shared = _solve_grid_batched(
                    args, logger, name, shard, task, grid, cache, mesh,
                    monitor, lam_label)
            for cfg in (() if use_batched else grid):
                coord = StreamingFixedEffectCoordinate(
                    name=name, cache=cache, feature_shard_id=shard,
                    task_type=task, config=cfg, sharded_objective=shared,
                    mesh=mesh)
                shared = coord.sharded_objective
                t0 = _time.perf_counter()
                model, trackers, obj_hist = None, [], []
                # --distmon hooks: a live per-λ convergence ring (loss/
                # grad-norm/step per outer iteration, visible on /distz
                # mid-solve) and the solver's final margins, from which
                # training-score quantiles sketch without a scoring
                # pass.
                ring, margins_holder = None, None
                if monitor is not None:
                    from photon_ml_tpu.optimization.convergence import (
                        ConvergenceRing,
                    )

                    ring = ConvergenceRing()
                    monitor.add_ring(lam_label(cfg), ring)
                    margins_holder = []
                # One trace context per λ-grid point: the solve's
                # identity across its outer iterations — slow solves
                # land in the /tracez tail, and a divergence fault
                # carries this trace_id into the flight dump.
                ctx = telemetry.mint("solve")
                ctx.annotate(coordinate=name,
                             reg_weight=cfg.regularization_weight,
                             optimizer=str(cfg.optimizer_type))
                for _ in range(args.num_iterations):
                    model, res = coord.solve(
                        model, trace_ctx=ctx, convergence_ring=ring,
                        margins_out=margins_holder)
                    trackers.append(res)
                    obj_hist.append(float(res.value))
                if monitor is not None and margins_holder:
                    monitor.observe_scores(
                        lam_label(cfg),
                        shared.host_scores_from_margins(margins_holder))
                ctx.annotate(
                    iterations=int(trackers[-1].iterations),
                    reason=trackers[-1].reason_enum().summary)
                ctx.finish("ok")
                gm = GameModel({name: model}, task)
                results.append(({name: cfg}, CoordinateDescentResult(
                    model=gm, objective_history=obj_hist,
                    validation_history=[], best_model=gm,
                    best_metric=None, trackers={name: trackers},
                    timings={name: _time.perf_counter() - t0})))
        num_rows = cache.n_rows
        stream_info = {
            "mode": "spill",
            "batch_rows": args.batch_rows,
            "hbm_budget_bytes": budget,
            "mesh_devices": args.mesh_devices,
            "mesh_shape": mesh_rc,
            "spill_dtype": args.spill_dtype,
            "spill_source": args.spill_source,
            "feeder": cache.ingest_stats,
            "cache": cache.stats(),
            "grid_batched": use_batched,
            "grid_points": len(grid),
            "trace_budgets": shared.trace_budgets(),
            "trace_counts": shared.guard.counts(),
        }
        if fetcher is not None:
            stream_info["redecode"] = {
                "decode_path": fetcher.decode_path,
                "payload_bytes_read": fetcher.payload_bytes_read,
                "blocks_decoded": fetcher.blocks_decoded,
                "rows_fetched": fetcher.rows_fetched,
            }

    if args.validate_input_dirs and evaluators:
        with span("validate"):
            all_metrics = _stream_validate_many(
                [res.model for _, res in results], args, shard_maps,
                evaluators, logger)
        for (_, res), metrics in zip(results, all_metrics):
            res.validation_history.append(metrics)

    # Per-λ optimization telemetry events — the streamed analog of the
    # glm_driver's per-model PhotonOptimizationLogEvent emission (the
    # listener registration existed; the streamed path never emitted).
    for configs, res in results:
        cfg = configs[name]
        trk = list(res.trackers.get(name) or [])
        last = trk[-1] if trk else None
        emitter.send_event(PhotonOptimizationLogEvent(
            reg_weight=cfg.regularization_weight,
            iterations=(int(last.iterations) if last is not None else 0),
            converged_reason=(last.reason_enum().summary
                              if last is not None else "unknown"),
            final_value=(float(last.value) if last is not None
                         else float("nan")),
            metrics=(res.validation_history[-1]
                     if res.validation_history else None)))

    from photon_ml_tpu.estimators.game_estimator import select_best_result

    best_configs, best_result = select_best_result(results, evaluators)

    distmon_out = None
    if monitor is not None:
        import jax.numpy as jnp
        import numpy as np

        best_label = lam_label(best_configs[name])
        if budget is None:
            # Resident path: the fused in-core solvers ran — rings
            # populate post-hoc from the tracker histories, and the
            # best model's training scores come from ONE matvec over
            # the already-resident assembled batch (device work only,
            # no decode pass).
            for configs, res in results:
                # EVERY solve's history appends to the λ's ring (not
                # just the last), matching the live streamed-solver
                # rings under --num-iterations > 1.
                for trk in res.trackers.get(name) or []:
                    monitor.ring_from_history(
                        lam_label(configs[name]),
                        np.asarray(trk.value_history),
                        np.asarray(trk.grad_norm_history))
            batch = data.fixed_effect_batch(shard)
            fe_model = best_result.best_model.models[name]
            w = jnp.asarray(
                np.asarray(fe_model.glm.coefficients.means),
                np.asarray(batch.labels).dtype)
            monitor.observe_scores(
                best_label, np.asarray(batch.features.matvec(w)))
        monitor.publish_gauges()
        distmon_out = {
            "data_quality": monitor.data_quality_block(),
            "reference": monitor.reference(score_label=best_label),
        }

    return (results, best_configs, best_result, shard_maps, num_rows,
            stream_info, distmon_out)


def _stream_train_mf(args, logger, task, fre_data, fre_opt, sequence,
                     train_inputs, evaluators, preloaded_maps, emitter,
                     obs):
    """Out-of-core MATRIX FACTORIZATION training (--stream-train with a
    factored-random-effect coordinate): observations stream through
    `BlockGameStream` (re-decoded per feature pass, host O(one block));
    factor tables live in a budgeted `DeviceFactorCache` (ALX-style
    pow-2 observation-count bucketing, replay-aware eviction, the PR-10
    f32/bf16/redecode spill tiers) so factor tables larger than
    ``--hbm-budget`` train to completion; alternating sweeps run the
    streamed ridge gamma pass + streamed L-BFGS projection refit
    (algorithm/coordinates.py StreamingFactoredRandomEffectCoordinate).
    λ-grid points with the same num_factors share one compiled
    objective, so the grid sweep never recompiles. The factor cache's
    residency stats register as a live /statusz provider."""
    import time as _time

    from photon_ml_tpu.algorithm.coordinate_descent import (
        CoordinateDescentResult,
    )
    from photon_ml_tpu.algorithm.coordinates import (
        StreamingFactoredRandomEffectCoordinate,
    )
    from photon_ml_tpu.data.avro_reader import build_index_map
    from photon_ml_tpu.data.block_stream import BlockGameStream
    from photon_ml_tpu.models.game_model import GameModel

    name = sequence[0]
    data_cfg = fre_data[name]
    shard = data_cfg.feature_shard_id
    re_type = data_cfg.random_effect_type
    if name not in fre_opt:
        raise ValueError(
            f"coordinate {name!r} has no optimization configuration — "
            "pass it via "
            "--factored-random-effect-optimization-configurations")
    grid = [FactoredRandomEffectOptimizationConfiguration.parse(part)
            for part in fre_opt[name].split("|")]

    if preloaded_maps is not None:
        if shard not in preloaded_maps:
            raise ValueError(
                f"factored coordinate {name!r} references unknown "
                f"feature shard {shard!r} "
                f"(have {sorted(preloaded_maps)})")
        shard_maps = {shard: preloaded_maps[shard]}
    else:
        logger.info("building feature index for shard %r from %s",
                    shard, train_inputs)
        with span("build_index"):
            shard_maps = {shard: build_index_map(
                train_inputs, ingest_workers=args.ingest_workers)}

    stream_holder = {}
    monitor = None
    if args.distmon:
        from photon_ml_tpu.data.distmon import (
            MonitoredStream,
            StreamingDistributionMonitor,
        )

        # MF re-decodes observations once per feature pass; the monitor
        # observes exactly ONE full pass (max_passes=1 on the first
        # stream) so every row counts once — the later passes replay
        # identical bytes (the PR 12 determinism contract), so one pass
        # IS the distribution.
        monitor = StreamingDistributionMonitor(
            feature_shards=[shard], id_types=[re_type])
        obs.add_dist_provider("training", monitor.snapshot)
        obs.add_scrape_hook("distmon", monitor.publish_gauges)
        obs.add_sketch_provider("training", monitor.sketch_states)

    def make_stream():
        s = BlockGameStream(
            train_inputs, id_types=[re_type],
            feature_shard_maps=shard_maps,
            batch_rows=args.batch_rows, feeder=args.feeder,
            prefetch_depth=max(0, args.prefetch_batches))
        stream_holder["last"] = s
        if monitor is not None and not stream_holder.get("observed"):
            stream_holder["observed"] = True
            return MonitoredStream(s, monitor, max_passes=1)
        return s

    budget = args.hbm_budget
    if args.checkpoint_dir:
        logger.warning("--checkpoint-dir is not supported with "
                       "--stream-train MF coordinates; ignoring")
    fetcher = None
    if budget is not None and args.spill_source == "redecode":
        from photon_ml_tpu.data.block_stream import BlockRandomAccess

        # Factor-shard misses re-derive from observations: the hook
        # re-decodes ONLY the covering container batches by global row
        # range (the PR-10 out-of-core miss path, re-pointed at the
        # factor tables' normal equations).
        fetcher = BlockRandomAccess(
            train_inputs, id_types=[re_type],
            feature_shard_maps=shard_maps, feeder=args.feeder)
    logger.info(
        "stream-train (mf%s): %r over %r entities from %s in %d-row "
        "batches", "" if budget is None else
        f", hbm budget {budget} bytes, spill {args.spill_dtype}/"
        f"{args.spill_source}", name, re_type, train_inputs,
        args.batch_rows)

    shared = {}  # num_factors -> StreamedMFObjective (kernel sharing)
    results = []

    def _factor_cache_status():
        # Live residency view, mirroring the shard-cache provider of
        # the fixed-effect spill path. Reads THROUGH the shared-
        # objective table so a grid spanning several num_factors values
        # (several caches) stays fully observable — single-k grids keep
        # the flat shard-cache-style schema.
        if len(shared) == 1:
            return next(iter(shared.values())).cache.stats()
        return {f"num_factors_{k}": o.cache.stats()
                for k, o in sorted(shared.items())}

    with span("solve"):
        for cfg in grid:
            coord = StreamingFactoredRandomEffectCoordinate(
                name=name, make_stream=make_stream,
                feature_shard_id=shard, random_effect_type=re_type,
                task_type=task, config=cfg.random_effect,
                latent_config=cfg.latent_factor, mf_config=cfg.mf,
                # seed 0 = GameEstimator.fit's default, so the streamed
                # B0 matches what the in-core driver path initializes
                # (parity tests compare the two end to end).
                seed=0,
                hbm_budget_bytes=budget,
                spill_dtype=(args.spill_dtype if budget is not None
                             else "f32"),
                spill_source=(args.spill_source if budget is not None
                              else "buffer"),
                mf_objective=shared.get(cfg.mf.num_factors),
                random_access=fetcher)
            if not shared:
                obs.add_status_provider("factor_cache",
                                        _factor_cache_status)
            shared[cfg.mf.num_factors] = coord.mf_objective
            t0 = _time.perf_counter()
            model, trackers, obj_hist = None, [], []
            ctx = telemetry.mint("solve")
            ctx.annotate(coordinate=name,
                         reg_weight=cfg.random_effect.regularization_weight,
                         num_factors=cfg.mf.num_factors,
                         mf_sweeps=cfg.mf.max_iterations)
            for _ in range(args.num_iterations):
                model, sweep_trackers = coord.solve(model, trace_ctx=ctx)
                trackers.extend(sweep_trackers)
                obj_hist.append(float(sweep_trackers[-1].value))
            ctx.annotate(
                iterations=int(trackers[-1].iterations),
                reason=trackers[-1].reason_enum().summary)
            ctx.finish("ok")
            gm = GameModel({name: model}, task)
            results.append(({name: cfg}, CoordinateDescentResult(
                model=gm, objective_history=obj_hist,
                validation_history=[], best_model=gm,
                best_metric=None, trackers={name: trackers},
                timings={name: _time.perf_counter() - t0})))

    first_obj = next(iter(shared.values()))
    num_rows = first_obj.n_rows
    stream_info = {
        "mode": "mf-stream",
        "batch_rows": args.batch_rows,
        "hbm_budget_bytes": budget,
        "mesh_devices": None,  # factor-table device fold: follow-on
        "mesh_shape": None,
        "spill_dtype": args.spill_dtype if budget is not None else None,
        "spill_source": (args.spill_source if budget is not None
                         else None),
        "feeder": (stream_holder["last"].stats()
                   if "last" in stream_holder else None),
        "cache": first_obj.cache.stats(),
        "plan": {
            "entities": first_obj.plan.num_entities,
            "shards": first_obj.plan.n_shards,
            "obs_bucket_histogram": {
                str(k): v for k, v in sorted(
                    first_obj.plan.obs_bucket_histogram().items())},
        },
        "trace_budgets": first_obj.trace_budgets(),
        "trace_counts": first_obj.guard.counts(),
    }
    if len(shared) > 1:
        # A grid spanning several num_factors values trains several
        # factor caches; the flat "cache" block above covers the first
        # — report the rest too so none is invisible post-run.
        stream_info["cache_by_num_factors"] = {
            str(k): o.cache.stats() for k, o in sorted(shared.items())}
    if fetcher is not None:
        stream_info["redecode"] = {
            "decode_path": fetcher.decode_path,
            "payload_bytes_read": fetcher.payload_bytes_read,
            "blocks_decoded": fetcher.blocks_decoded,
            "rows_fetched": fetcher.rows_fetched,
        }

    if args.validate_input_dirs and evaluators:
        with span("validate"):
            all_metrics = _stream_validate_many(
                [res.model for _, res in results], args, shard_maps,
                evaluators, logger)
        for (_, res), metrics in zip(results, all_metrics):
            res.validation_history.append(metrics)

    for configs, res in results:
        cfg = configs[name]
        trk = list(res.trackers.get(name) or [])
        last = trk[-1] if trk else None
        emitter.send_event(PhotonOptimizationLogEvent(
            reg_weight=cfg.random_effect.regularization_weight,
            iterations=(int(last.iterations) if last is not None else 0),
            converged_reason=(last.reason_enum().summary
                              if last is not None else "unknown"),
            final_value=(float(last.value) if last is not None
                         else float("nan")),
            metrics=(res.validation_history[-1]
                     if res.validation_history else None)))

    from photon_ml_tpu.estimators.game_estimator import select_best_result

    best_configs, best_result = select_best_result(results, evaluators)

    distmon_out = None
    if monitor is not None:
        monitor.publish_gauges()
        # MF reference carries label quantiles only (no cheap training-
        # score surface exists — scores need a full gather+dot pass);
        # serving drift degrades gracefully without a "score" block.
        distmon_out = {
            "data_quality": monitor.data_quality_block(),
            "reference": monitor.reference(),
        }

    return (results, best_configs, best_result, shard_maps, num_rows,
            stream_info, distmon_out)


def main() -> None:
    run(sys.argv[1:])


if __name__ == "__main__":
    main()
