"""GAME scoring driver (reference: ml/cli/game/scoring/Driver.scala:36-265):
load a saved GAME model, score a dataset, write ScoringResultAvro, optionally
evaluate.

Two execution shapes:

- default: the whole input is read into one GameDataset and scored in a
  single device dispatch (``DeviceGameScorer`` — dataset-resident, exact
  shapes), with a clean host-numpy fallback when a sub-model type is not
  device-scorable;
- ``--stream --batch-rows N``: arbitrarily large Avro inputs score in
  O(N) host memory through the streaming serving engine
  (photon_ml_tpu/serving/): model uploaded once, batches padded into
  static compile buckets, featureization of batch k+1 overlapped with
  the device dispatch of batch k, scores written per batch. Caveat:
  ``--evaluators`` additionally accumulates the per-row EVALUATION
  columns (score/label/offset/weight + entity-id strings) across the
  whole input — features never accumulate, but metric computation is
  O(total rows); omit evaluators to keep streaming strictly bounded.

A third shape, ``--serve``, replays the input as CONCURRENT requests
through the async serving front-end (photon_ml_tpu/serving/frontend.py):
the decoded input is sliced into ``--request-rows``-row requests,
``--serve-concurrency`` requesters submit them over an event loop, and
the front-end coalesces whatever lands inside ``--coalesce-ms`` into
shared bucket dispatches. Scores are identical to the other paths; what
changes is the execution shape — this is the serving-traffic harness
(admission control, queue-wait/coalesce telemetry, per-request P50/P99
in metrics.json ``frontend``), see docs/SCALE.md §Serving front-end.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

import numpy as np

from photon_ml_tpu import telemetry
from photon_ml_tpu.cli import device_summary
from photon_ml_tpu.cli.obs import DriverObservability, add_observability_args
from photon_ml_tpu.data.avro_reader import read_game_dataset
from photon_ml_tpu.evaluation import build_evaluator
from photon_ml_tpu.io import schemas
from photon_ml_tpu.io.avro_codec import write_container
from photon_ml_tpu.io.model_io import load_game_model
from photon_ml_tpu.telemetry import span
from photon_ml_tpu.utils.compile_cache import (
    compile_ledger,
    enable_compile_cache,
)
from photon_ml_tpu.utils.date_range import resolve_input_dirs
from photon_ml_tpu.utils.logging_utils import setup_photon_logger


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(prog="photon-game-scoring-driver")
    p.add_argument("--input-dirs", required=True)
    p.add_argument("--date-range", default=None,
                   help="yyyyMMdd-yyyyMMdd; expands daily/yyyy/MM/dd "
                        "subdirs of the input dirs")
    p.add_argument("--date-range-days-ago", default=None)
    p.add_argument("--game-model-input-dir", required=True)
    p.add_argument("--output-dir", required=True)
    p.add_argument("--feature-index-dir", default=None,
                   help="feature index stores keyed by shard id: "
                        "<shard>.json maps or the reference's partitioned "
                        "PalDB stores (defaults to "
                        "<model-dir>/feature-indexes)")
    p.add_argument("--evaluators", default=None)
    p.add_argument("--id-types", default=None)
    p.add_argument("--stream", action="store_true",
                   help="score through the streaming serving engine in "
                        "bounded memory (O(batch-rows x prefetch depth) "
                        "rows resident; note --evaluators still "
                        "accumulates per-row evaluation columns)")
    p.add_argument("--batch-rows", type=int, default=4096,
                   help="rows per streamed scoring batch (--stream only)")
    p.add_argument("--feeder", choices=["auto", "native", "python"],
                   default="auto",
                   help="--stream decode path: the native C block "
                        "decoder ('auto' falls back to the byte-"
                        "identical python record loop when the "
                        "extension is unbuilt or the schema doesn't "
                        "fit; 'native' errors instead; 'python' forces "
                        "the record loop)")
    p.add_argument("--prefetch-batches", type=int, default=2,
                   help="batches the --stream feeder decodes ahead on a "
                        "background thread (0 = synchronous decode; "
                        "peak resident batches stay bounded by this "
                        "depth + 2)")
    p.add_argument("--serve", action="store_true",
                   help="replay the input as concurrent per-request "
                        "traffic through the async serving front-end "
                        "(request coalescing + admission control; "
                        "mutually exclusive with --stream)")
    p.add_argument("--serve-concurrency", type=int, default=16,
                   help="concurrent closed-loop requesters in --serve "
                        "mode")
    p.add_argument("--coalesce-ms", type=float, default=2.0,
                   help="--serve bounded coalesce window in "
                        "milliseconds (0 = adaptive drain)")
    p.add_argument("--request-rows", type=int, default=1,
                   help="rows per replayed request in --serve mode "
                        "(1 = the single-row serving shape)")
    p.add_argument("--max-pending", type=int, default=1024,
                   help="--serve admission bound (requests admitted and "
                        "unfinished); raised to --serve-concurrency if "
                        "lower, so the closed-loop replay never sheds")
    p.add_argument("--listen", default=None, metavar="ADDR",
                   help="network serving mode (implies --serve): instead "
                        "of replaying the input, open the protocol front "
                        "door on ADDR (PORT, :PORT or HOST:PORT; port 0 "
                        "= ephemeral, written to <output-dir>/net_port) "
                        "speaking HTTP/1.1 JSON (POST /score) AND the "
                        "length-prefixed binary framing on one port, "
                        "both into the front-end's admission path "
                        "(docs/SCALE.md §Serving network front door)")
    p.add_argument("--serve-seconds", type=float, default=None,
                   metavar="S",
                   help="--listen lifetime: serve for S seconds, then "
                        "drain and write the summary (default: until "
                        "SIGINT; the drain still runs)")
    p.add_argument("--adaptive-admission", action="store_true",
                   help="--listen SLO-adaptive admission: a controller "
                        "reads the declared --slo objectives' per-tick "
                        "burn rate and retunes the live shed threshold "
                        "and coalesce window with hysteresis "
                        "(serving/adaptive.py; requires at least one "
                        "--slo)")
    p.add_argument("--distmon", action="store_true",
                   help="distribution observability (--stream/--serve): "
                        "per-model score sketch updated at scatter-back "
                        "(one vectorized update per settled group, "
                        "< 2%% overhead; a no-op without the flag), "
                        "PSI/KS drift scores computed on scrape against "
                        "the model's embedded referenceDistributions "
                        "snapshot (trained with --distmon), exposed as "
                        "serving.model.<label>.score_drift_psi/_ks "
                        "gauges (SLO-able via --slo "
                        "'drift=value:serving.model.default."
                        "score_drift_psi<=0.25'), live /distz with "
                        "--obs-port, and a distributions metrics.json "
                        "block (docs/OBSERVABILITY.md §Distributions & "
                        "drift)")
    p.add_argument("--trace-out", default=None, metavar="PATH",
                   help="write a Chrome trace-event JSON of the run's "
                        "pipeline spans here (load in Perfetto — "
                        "docs/OBSERVABILITY.md)")
    add_observability_args(p)
    return p


def _maybe_enable_cpu_x64():
    """On CPU, enable x64 for this driver process (when not already on)
    BEFORE the model loads, so coefficients and scores keep the f64
    precision the pre-device host-numpy path always had; on real
    accelerators x64 stays off and scoring runs f32 (the serving
    dtype)."""
    import jax

    if not jax.config.jax_enable_x64 and jax.default_backend() == "cpu":
        try:
            jax.config.update("jax_enable_x64", True)
        except Exception:  # noqa: BLE001 — precision upgrade best-effort
            pass


def _scoring_dtype():
    import jax
    import jax.numpy as jnp

    return jnp.float64 if jax.config.jax_enable_x64 else jnp.float32


def _device_scores(model, data, logger):
    """Score a resident dataset on device; host-numpy fallback when a
    sub-model family is not device-scorable (same scores either way).

    The fallback is restricted to the DOCUMENTED contract — the typed
    ``UnsupportedSubModelError`` the scorers raise at construction for a
    sub-model family without a device kernel (or a snapshot past the
    densification ceiling). A bare ``TypeError`` — from construction OR
    dispatch — is a real engine bug and must surface instead of
    silently degrading every score to the slow host path
    (tests/test_cli_drivers.py::test_game_scoring_engine_bug_surfaces)."""
    from photon_ml_tpu.models.device_scoring import DeviceGameScorer
    from photon_ml_tpu.serving.kernels import UnsupportedSubModelError

    try:
        scorer = DeviceGameScorer(model, data, dtype=_scoring_dtype())
    except UnsupportedSubModelError as e:
        logger.info("device scorer unavailable for this model (%s); "
                    "falling back to host numpy scoring", e)
        return model.score(data), "host"
    return np.asarray(scorer.score(model), np.float64), "device"


def run(argv=None) -> dict:
    enable_compile_cache()
    _maybe_enable_cpu_x64()
    args = build_parser().parse_args(argv)
    out_dir = Path(args.output_dir)
    out_dir.mkdir(parents=True, exist_ok=True)
    logger = setup_photon_logger(out_dir)
    t0 = time.perf_counter()
    # Per-run telemetry: phase spans + registry snapshot in metrics.json
    # (plus --trace-out for Perfetto) — docs/OBSERVABILITY.md.
    telemetry.reset()
    # Same contract as the training driver: trace sampling is on when
    # anything consumes traces — --trace-out, or the live plane's
    # /tracez (federation merges the tail per process).
    telemetry.enable(trace=bool(args.trace_out)
                     or args.obs_port is not None)
    # Live observability plane (docs/OBSERVABILITY.md §Live endpoints):
    # flight recorder armed for the whole run, HTTP endpoints when
    # --obs-port is given (a --serve process becomes scrapeable).
    # Construction/start INSIDE the try: a bad --slo spec or an occupied
    # --obs-port must still unwind through the finally below (obs.stop()
    # reverses whatever start() got through — recorder install, SIGTERM
    # handler — before the failure).
    obs = None
    try:
        obs = DriverObservability(args, out_dir,
                                  role="scoring").start()
        # Root span: module imports, logging, and glue between the named
        # phases land in `driver` SELF time — the stage table sums to
        # the whole run (attributed_wall_frac >= 0.9 even on millisecond
        # runs) instead of leaving silent gaps.
        with span("driver"):
            summary = _run_scoring(args, out_dir, logger, obs)

        wall = time.perf_counter() - t0
        summary["total_seconds"] = wall
        summary["device"] = device_summary()
        summary["compile"] = compile_ledger(top=20)
        _apply_legacy_aliases(summary)
        obs.finish(summary)
        summary["telemetry"] = telemetry.attribution_summary(wall)
        if args.trace_out:
            telemetry.export_chrome_trace(args.trace_out)
            logger.info("pipeline trace written to %s (load in Perfetto)",
                        args.trace_out)
        (out_dir / "metrics.json").write_text(
            json.dumps(summary, indent=2))
        logger.info("scoring done: %s", summary["metrics"])
        return summary
    except BaseException as e:
        # Unhandled fault: the spans above have already unwound, so the
        # flight ring's last events cover the failing stage.
        if obs is not None:
            obs.dump_fault(e, logger)
        raise
    finally:
        # Exception (incl. the --stream SystemExit paths) or not: don't
        # leave a process-wide recorder or server armed for whatever
        # runs next in this process.
        if obs is not None:
            obs.stop()
        telemetry.disable()


# snake_case canonical -> deprecated camelCase alias, kept one release
# behind (docs/OBSERVABILITY.md §Schema) — ONE table, so a new key can't
# silently miss its twin.
_LEGACY_ALIASES = {
    "num_rows": "numRows",
    "num_batches": "numBatches",
    "batch_rows": "batchRows",
    "scoring_path": "scoringPath",
    "total_seconds": "totalSeconds",
}


def _apply_legacy_aliases(summary: dict) -> dict:
    for snake, camel in _LEGACY_ALIASES.items():
        if snake in summary:
            summary[camel] = summary[snake]
    return summary


def _run_scoring(args, out_dir, logger, obs) -> dict:
    from photon_ml_tpu.data.paldb import load_feature_index_maps

    # Flag contradictions fail BEFORE the model loads: a bad invocation
    # should not pay (or need) a model-directory read to be diagnosed.
    if args.listen is not None:
        args.serve = True  # --listen IS the network serving shape
    if args.stream and args.serve:
        raise SystemExit("--stream and --serve are mutually exclusive: "
                         "--stream is the bounded-memory bulk path, "
                         "--serve the concurrent-request replay harness")
    if args.adaptive_admission and args.listen is None:
        raise SystemExit("--adaptive-admission retunes a live network "
                         "front door; pass --listen")
    if args.adaptive_admission and not args.slo:
        raise SystemExit("--adaptive-admission steers on the declared "
                         "--slo objectives; pass at least one --slo")
    if args.distmon and not (args.stream or args.serve):
        raise SystemExit("--distmon attaches score sketches to the "
                         "streaming engine's scatter-back; pass "
                         "--stream or --serve")

    model_dir = Path(args.game_model_input_dir)
    index_dir = Path(args.feature_index_dir) if args.feature_index_dir else \
        model_dir / "feature-indexes"
    with span("load_model"):
        shard_maps = load_feature_index_maps(index_dir)
        model = load_game_model(model_dir, shard_maps)
    # Liveness vs readiness split: the model is resident, so this
    # process can serve — /readyz flips 200 here, while /healthz was
    # answering "alive" from the moment the server came up.
    obs.mark_ready("model_loaded")

    with span("setup"):
        meta = json.loads((model_dir / "model-metadata.json").read_text())
        id_types = sorted(
            {c["randomEffectType"] for c in meta["coordinates"]
             if c["kind"] == "random"} |
            # MF coordinates key rows by both their entity axes.
            {c[k] for c in meta["coordinates"] if c["kind"] == "mf"
             for k in ("rowEffectType", "colEffectType")} |
            {s.strip() for s in (args.id_types or "").split(",")
             if s.strip()})

        inputs = resolve_input_dirs(
            args.input_dirs, date_range=args.date_range,
            date_range_days_ago=args.date_range_days_ago)

        evaluators = [build_evaluator(s.strip())
                      for s in (args.evaluators or "").split(",")
                      if s.strip()]
        scores_dir = out_dir / "scores"
        scores_dir.mkdir(exist_ok=True)
        scores_path = scores_dir / "part-00000.avro"

    # The model's embedded reference distributions (stamped by a
    # --stream-train --distmon run) — what serving drift-scores
    # against. None for models trained without --distmon.
    reference = meta.get("referenceDistributions")
    if args.serve:
        summary = _run_serve(args, inputs, id_types, shard_maps, model,
                             evaluators, scores_path, logger, obs,
                             reference)
    elif args.stream:
        summary = _run_stream(args, inputs, id_types, shard_maps, model,
                              evaluators, scores_path, logger, obs,
                              reference)
    else:
        with span("ingest"):
            data, _ = read_game_dataset(inputs, id_types=id_types,
                                        feature_shard_maps=shard_maps)
        with span("score"):
            scores, path_used = _device_scores(model, data, logger)
        logger.info("scored %d rows (%s path)", data.num_rows, path_used)

        with span("write_scores"):
            uids = data.uids if data.uids is not None else \
                np.asarray([str(i) for i in range(data.num_rows)])
            write_container(
                scores_path, schemas.SCORING_RESULT,
                [{"uid": str(u), "predictionScore": float(s + o),
                  "label": float(l), "metadataMap": None}
                 for u, s, o, l in zip(uids, scores, data.offsets,
                                       data.responses)])
        with span("evaluate"):
            metrics = {ev.name: ev.evaluate_dataset(scores, data)
                       for ev in evaluators}
        summary = {
            "num_rows": int(data.num_rows),
            "metrics": metrics,
            "scoring_path": path_used,
        }
    return summary


def _attach_score_monitor(args, engine, label, reference, obs):
    """--distmon: hang a ScoreDistributionMonitor off the engine's
    scatter-back settle, register /distz + the drift-gauge scrape hook
    (drift computes on scrape — /metrics, /statusz, /distz, heartbeat —
    and once more at finish before the SLO block). Returns the monitor
    (None without the flag: the settle path stays a no-op branch)."""
    if not args.distmon:
        return None
    from photon_ml_tpu.data.distmon import ScoreDistributionMonitor

    mon = ScoreDistributionMonitor(label, reference=reference)
    engine.score_monitor = mon
    obs.add_dist_provider("serving", lambda: {label: mon.snapshot()})
    obs.add_scrape_hook("score_drift", mon.publish_gauges)
    obs.add_sketch_provider("serving", mon.sketch_states)
    return mon


def _run_stream(args, inputs, id_types, shard_maps, model, evaluators,
                scores_path, logger, obs, reference=None) -> dict:
    """Bounded-memory scoring through the three-stage decode -> H2D ->
    dispatch pipeline (serving engine `score_container_stream`: the
    block-stream feeder decodes + featureizes batch k+1 on its prefetch
    thread while batch k's dispatch is in flight), with incremental
    ScoringResultAvro writes. Only evaluation columns (when evaluators are
    requested) accumulate across batches — never features — so metrics
    cost O(total rows) of scalars/id strings while feature memory stays
    O(batch_rows x (prefetch + pipeline depth))."""
    from photon_ml_tpu.serving import (
        StreamingGameScorer,
        UnsupportedSubModelError,
    )

    try:
        with span("setup_engine"):
            engine = StreamingGameScorer(model, dtype=_scoring_dtype())
    except UnsupportedSubModelError as e:
        # Only the documented not-device-scorable contract exits cleanly;
        # any other TypeError is an engine bug and propagates.
        raise SystemExit(
            f"--stream requires a device-scorable model: {e}") from e
    score_mon = _attach_score_monitor(args, engine, "default",
                                      reference, obs)

    try:
        # Stream construction scans the container block index (real I/O)
        # — covered so tiny runs still attribute >= 90% of wall time.
        with span("setup_stream"):
            scored = engine.score_container_stream(
                inputs, id_types=id_types, feature_shard_maps=shard_maps,
                batch_rows=args.batch_rows, feeder=args.feeder,
                prefetch_depth=args.prefetch_batches)
    except RuntimeError as e:
        raise SystemExit(str(e)) from e
    logger.info("streamed scoring: %s feeder, prefetch depth %d",
                scored.stream.decode_path, scored.stream.prefetch_depth)
    from photon_ml_tpu.evaluation.validation import StreamedEvalAccumulator

    counters = {"rows": 0, "batches": 0}
    acc = StreamedEvalAccumulator(id_types) if evaluators else None

    def scored_records():
        for ds, scores in scored:
            counters["rows"] += ds.num_rows
            counters["batches"] += 1
            if acc is not None:
                acc.add(ds, scores)
            uids = ds.uids if ds.uids is not None else \
                np.asarray([str(i) for i in range(ds.num_rows)])
            for u, s, o, l in zip(uids, scores, ds.offsets, ds.responses):
                yield {"uid": str(u), "predictionScore": float(s + o),
                       "label": float(l), "metadataMap": None}

    # One phase span over the whole pipeline consumption; the per-stage
    # split (decode / featureize / dispatch / device_wait / ...) nests
    # inside it, decode on the prefetch thread's own trace track.
    with span("score"):
        write_container(scores_path, schemas.SCORING_RESULT,
                        scored_records())
    logger.info("scored %d rows in %d streamed batches (batch-rows=%d)",
                counters["rows"], counters["batches"], args.batch_rows)

    with span("evaluate"):
        metrics = acc.metrics(evaluators) if acc is not None else {}
    summary = {
        "num_rows": counters["rows"],
        "metrics": metrics,
        "scoring_path": "streaming-engine",
        "num_batches": counters["batches"],
        "batch_rows": args.batch_rows,
        "feeder": scored.stream.stats(),
        "engine": engine.stats(),
    }
    if score_mon is not None:
        score_mon.publish_gauges()
        summary["distributions"] = {"default": score_mon.snapshot()}
    return summary


def _run_serve(args, inputs, id_types, shard_maps, model, evaluators,
               scores_path, logger, obs, reference=None) -> dict:
    """Concurrent-request replay through the async serving front-end:
    the decoded input splits into ``--request-rows``-row requests,
    ``--serve-concurrency`` closed-loop requesters submit them on an
    event loop, and the front-end coalesces each ``--coalesce-ms``
    window into shared bucket dispatches. Unlike --stream this harness
    holds the decoded requests (and their scores) in memory — it
    exercises the serving shape, not the bounded-memory one."""
    from photon_ml_tpu.data.avro_reader import iter_game_dataset_batches
    from photon_ml_tpu.evaluation.validation import StreamedEvalAccumulator
    from photon_ml_tpu.serving import (
        FrontendConfig,
        ServingFrontend,
        UnsupportedSubModelError,
    )

    if args.request_rows < 1:
        raise SystemExit("--request-rows must be >= 1")
    try:
        with span("setup_engine"):
            frontend = ServingFrontend(
                {"default": model}, dtype=_scoring_dtype(),
                config=FrontendConfig(
                    coalesce_window_s=args.coalesce_ms / 1e3,
                    max_pending=max(args.max_pending,
                                    args.serve_concurrency)))
    except UnsupportedSubModelError as e:
        raise SystemExit(
            f"--serve requires a device-scorable model: {e}") from e
    # /statusz carries the front-end's live stats() — per-model serving
    # stats, admission counters, and the shared executable cache's
    # tracing-guard counts (docs/OBSERVABILITY.md §Live endpoints).
    obs.add_status_provider("frontend", frontend.stats)
    score_mon = _attach_score_monitor(args, frontend.engine("default"),
                                      "default", reference, obs)

    if args.listen is not None:
        summary = _run_listen(args, frontend, logger, obs)
        if score_mon is not None:
            score_mon.publish_gauges()
            summary["distributions"] = {"default": score_mon.snapshot()}
        return summary

    with span("ingest"):
        requests = []
        for ds in iter_game_dataset_batches(
                inputs, id_types=id_types, feature_shard_maps=shard_maps,
                batch_rows=args.batch_rows, feeder=args.feeder,
                prefetch_depth=args.prefetch_batches):
            for a in range(0, ds.num_rows, args.request_rows):
                requests.append(ds.subset(np.arange(
                    a, min(a + args.request_rows, ds.num_rows))))
    logger.info("serving replay: %d requests (%d rows each), "
                "concurrency %d, coalesce window %.1f ms",
                len(requests), args.request_rows, args.serve_concurrency,
                args.coalesce_ms)

    with span("score"):
        results, info = frontend.replay(
            requests, concurrency=args.serve_concurrency)
    assert info["shed"] == 0, \
        "closed-loop replay can never shed (max_pending >= concurrency)"
    if info["errors"]:
        raise SystemExit(
            f"--serve: {info['errors']} requests failed "
            "(see log; scores would be incomplete)")

    acc = StreamedEvalAccumulator(id_types) if evaluators else None
    counters = {"rows": 0}

    def scored_records():
        uid_base = 0
        for ds, scores in zip(requests, results):
            counters["rows"] += ds.num_rows
            if acc is not None:
                acc.add(ds, scores)
            uids = ds.uids if ds.uids is not None else np.asarray(
                [str(uid_base + i) for i in range(ds.num_rows)])
            uid_base += ds.num_rows
            for u, s, o, l in zip(uids, scores, ds.offsets, ds.responses):
                yield {"uid": str(u), "predictionScore": float(s + o),
                       "label": float(l), "metadataMap": None}

    with span("write_scores"):
        write_container(scores_path, schemas.SCORING_RESULT,
                        scored_records())
    with span("evaluate"):
        metrics = acc.metrics(evaluators) if acc is not None else {}
    summary = {
        "num_rows": counters["rows"],
        "metrics": metrics,
        "scoring_path": "async-frontend",
        "num_requests": len(requests),
        "request_rows": args.request_rows,
        "coalesce_window_ms": args.coalesce_ms,
        "concurrency": args.serve_concurrency,
        "frontend": frontend.stats(),
    }
    if score_mon is not None:
        score_mon.publish_gauges()
        summary["distributions"] = {"default": score_mon.snapshot()}
    return summary


def _parse_listen(addr: str):
    """'PORT', ':PORT' or 'HOST:PORT' -> (host, port); SystemExit on
    anything else (CLI validation, not a fault)."""
    addr = addr.strip()
    if ":" in addr:
        host, _, port = addr.rpartition(":")
        host = host or "127.0.0.1"
    else:
        host, port = "127.0.0.1", addr
    try:
        return host, int(port)
    except ValueError:
        raise SystemExit(f"bad --listen address {addr!r} "
                         "(PORT, :PORT or HOST:PORT)") from None


def _run_listen(args, frontend, logger, obs) -> dict:
    """--listen: open the network front door over the front-end and
    serve real sockets instead of replaying the input (which is ignored
    — requests arrive over the wire). The bound port lands in
    <output-dir>/net_port the moment the listener is up; the drain on
    exit (--serve-seconds elapsed or SIGINT) lets every admitted
    request settle and flush before the summary is written."""
    import asyncio

    from photon_ml_tpu.serving.adaptive import AdaptiveAdmission
    from photon_ml_tpu.serving.netserver import NetServer, NetServerConfig

    host, port = _parse_listen(args.listen)
    out_dir = Path(args.output_dir)
    report = {}

    async def serve() -> None:
        async with frontend:
            server = await NetServer(
                frontend, NetServerConfig(host=host, port=port)).start()
            ctl = None
            try:
                if args.adaptive_admission:
                    ctl = await AdaptiveAdmission(
                        frontend, slo_specs=args.slo).start()
                    obs.add_status_provider("adaptive_admission",
                                            ctl.stats)
                obs.add_status_provider("netserver", server.stats)
                (out_dir / "net_port").write_text(str(server.port))
                obs.mark_ready("serving")
                logger.info(
                    "serving on %s:%d (HTTP/1.1 + binary framing)%s",
                    host, server.port,
                    " with SLO-adaptive admission"
                    if ctl is not None else "")
                if args.serve_seconds is not None:
                    await asyncio.sleep(args.serve_seconds)
                else:
                    while True:
                        await asyncio.sleep(3600)
            finally:
                if ctl is not None:
                    await ctl.stop()
                    report["adaptive_admission"] = ctl.stats()
                await server.close()
                report["net"] = server.stats()

    with span("serve"):
        try:
            asyncio.run(serve())
        except KeyboardInterrupt:
            logger.info("interrupted; network front door drained")
    return {
        "num_rows": 0,  # rows served are in frontend/engine stats
        "metrics": {},
        "scoring_path": "netserver",
        "listen": f"{host}:{port}",
        **report,
        "frontend": frontend.stats(),
    }


def main() -> None:
    run(sys.argv[1:])


if __name__ == "__main__":
    main()
