"""Block coordinate descent over named coordinates — the GAME outer loop.

Reference: ml/algorithm/CoordinateDescent.scala:41-271. Semantics preserved:
for each iteration, for each coordinate in the updating sequence —
subtract the coordinate's own score from the total (residual), re-solve
against the residual as extra offsets, re-score, recompute the full
objective = sum_i w_i l(total_score_i + offset_i, y_i) + sum_c reg_c, and
track the best full model by the first validation evaluator.

TPU re-design: scores are dense device vectors, so the reference's
KeyValueScore fullOuterJoin +/- algebra (partial-score reduce at
CoordinateDescent.scala:150-158) is elementwise add/subtract in HBM, and the
per-coordinate "addScoresToOffsets" shuffle is a gather.
"""

from __future__ import annotations

import dataclasses
import functools
import logging
import time
from typing import (
    Dict,
    List,
    Mapping,
    NamedTuple,
    Optional,
    Sequence,
    Tuple,
    Union,
)

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from photon_ml_tpu import telemetry
from photon_ml_tpu.algorithm.coordinates import Coordinate
from photon_ml_tpu.data.game_data import GameDataset
from photon_ml_tpu.evaluation.evaluators import Evaluator
from photon_ml_tpu.models.game_model import GameModel
from photon_ml_tpu.ops.losses import loss_for_task
from photon_ml_tpu.telemetry import scopes
from photon_ml_tpu.telemetry.spans import phase
from photon_ml_tpu.types import TaskType
from photon_ml_tpu.utils.compile_cache import (
    dispatched_executable,
    note_instructions,
)
from photon_ml_tpu.utils.tracing_guard import TracingGuard

logger = logging.getLogger(__name__)

Array = jax.Array

# Module-level handles (get-or-create by name; inc is a no-op while
# telemetry is off).
_M_RUNS = telemetry.counter(scopes.COUNTER_CD_RUNS)
_M_COLD_STARTS = telemetry.counter(scopes.COUNTER_CD_COLD_STARTS)
_M_DISPATCH_MOVES = telemetry.counter(scopes.COUNTER_CD_DISPATCH_MOVES)
_M_EXCHANGE_DIVIDED = telemetry.counter(scopes.COUNTER_RE_EXCHANGE_DIVIDED)
_M_FE_PRODUCTS = telemetry.counter(scopes.COUNTER_FE_PRODUCTS)
_M_FE_CG_STEPS = telemetry.counter(scopes.COUNTER_FE_CG_STEPS)
_M_FE_TRON_STEPS = telemetry.counter(scopes.COUNTER_FE_TRON_STEPS)
_M_FE_PASSES = telemetry.counter(scopes.COUNTER_FE_PASSES)
_M_MF_ALTERNATIONS = telemetry.counter(scopes.COUNTER_MF_ALTERNATIONS)
_M_MF_REFIT_ITERATIONS = telemetry.counter(
    scopes.COUNTER_MF_REFIT_ITERATIONS)


def _unstack_tracker_block(trs: Dict[str, object], names: Sequence[str],
                           base: Dict[str, list]) -> None:
    """Append one block's host tracker pytrees (leading n_iters axis) into
    per-coordinate per-update lists — shared by eager (checkpoint-save) and
    lazy materialization so both produce identical entry shapes. ``trs``
    holds the coordinates the block's span covers; ``names`` gives their
    order (a jitted function returns a dict's keys sorted)."""
    names = [n for n in names if n in trs]
    n_iters = jax.tree.leaves(trs[names[0]])[0].shape[0]
    for i in range(n_iters):
        for n in names:
            tr = jax.tree.map(lambda a: a[i], trs[n])
            if isinstance(tr, tuple):
                tr = list(tr)
            base[n].append(tr)


class LazyTrackers(Mapping):
    """coordinate name -> per-update optimizer trackers, materialized from
    device on FIRST ACCESS. Tracker pytrees (per-entity value/gnorm
    histories) are the largest per-update artifacts; fetching them eagerly
    at run end would serialize a multi-MB device->host transfer into every
    training run whether or not the caller ever looks at telemetry."""

    def __init__(self, base: Dict[str, list],
                 pending: List[dict], names: Sequence[str]):
        self._base = base
        self._pending = list(pending)
        self._names = list(names)

    def _force(self) -> None:
        if not self._pending:
            return
        host_blocks = jax.device_get(self._pending)
        self._pending = []
        for trs in host_blocks:
            _unstack_tracker_block(trs, self._names, self._base)

    def __getitem__(self, key):
        self._force()
        return self._base[key]

    def __iter__(self):
        self._force()
        return iter(self._base)

    def __len__(self):
        return len(self._base)


@dataclasses.dataclass
class CoordinateDescentResult:
    model: GameModel
    objective_history: List[float]  # one entry per coordinate update
    validation_history: List[Dict[str, float]]  # one entry per iteration
    best_model: Optional[GameModel]
    best_metric: Optional[float]
    # coordinate name -> per-update OptimizerResults (device telemetry is
    # fetched lazily on first access — see LazyTrackers)
    trackers: Mapping[str, list]
    # coordinate name -> HOST DISPATCH seconds: what the host spent
    # enqueueing that coordinate's updates (a fused block's enqueue is
    # split evenly over its coordinates). JAX dispatch is asynchronous, so
    # this is not the device's time: read that under the
    # ``photon.cd.<coordinate>`` scope of a profiler trace
    # (docs/OBSERVABILITY.md, "The training fit").
    timings: Dict[str, float]


def _full_objective(loss, total, rows, penalties):
    """sum_i w_i l(total_i + offset_i, y_i) + every coordinate's penalties
    (``penalties``: (coefficients, l1, l2) triples), under its own scope
    of the device trace."""
    labels, offsets, weights = rows
    with jax.named_scope(scopes.CD_OBJECTIVE):
        obj = jnp.sum(weights * loss.loss(total + offsets, labels))
        for c, l1, l2 in penalties:
            obj = obj + 0.5 * l2 * jnp.sum(jnp.square(c))
            obj = obj + l1 * jnp.sum(jnp.abs(c))
    return obj


class CoordinateDescent:
    def __init__(
        self,
        coordinates: Dict[str, Coordinate],  # ordered updating sequence
        task_type: TaskType,
        validation_data: Optional[GameDataset] = None,
        validation_evaluators: Sequence[Evaluator] = (),
    ):
        if not coordinates:
            raise ValueError("at least one coordinate is required")
        self.coordinates = dict(coordinates)
        self.task_type = task_type
        self.validation_data = validation_data
        self.validation_evaluators = list(validation_evaluators)
        # cd_block programs: by iteration count for whole iterations, by
        # (iterations, first, stop) for a span of fewer coordinates
        self._block_fns: Dict[object, object] = {}
        # block functions whose instruction table is still to be published:
        # each leaves at its first dispatch (run())
        self._table_due: set = set()
        # block function -> {argument leaf path: the sharding its compiled
        # executable takes that leaf with}, read at its first dispatch
        self._arg_shardings: Dict[object, dict] = {}
        self._val_scorer = None
        self._cold_cache = None
        # Shared retrace infrastructure (utils/tracing_guard.py): every
        # fused executable registers here, and run() asserts the hot
        # loop's compile-count invariant — each executable traces exactly
        # once — instead of trusting it silently.
        self.tracing_guard = TracingGuard()
        self._publish_work()

    def _publish_work(self) -> None:
        """Gauges of the work this fit was built with, summed over its
        random-effect coordinates: slots and true rows (padding waste =
        1 - rows / slots), the entities the fused kernel and the
        vmapped fallback solve (``coordinate.routing()`` has each bucket's
        reason), and the rows one scoring gathers through ``slot_of_row``
        with those of them in no slot. Set once, here, and only while
        telemetry is enabled: the true-row count is one small device
        reduction a bucket. Where a coordinate's matrix came from the
        sparse chooser: what it counted and stored (``sparse_work``)."""
        if not telemetry.enabled():
            return
        sparse = self._sparse_counts()
        if sparse:
            telemetry.gauge(scopes.GAUGE_FE_NNZ).set(
                sum(c.nnz for c in sparse))
            telemetry.gauge(scopes.GAUGE_FE_SLOTS).set(
                sum(c.slots for c in sparse))
            telemetry.gauge(scopes.GAUGE_FE_MAX_COL_DEGREE).set(
                max(c.max_col_degree for c in sparse))
            telemetry.gauge(scopes.GAUGE_FE_CODED_SLOTS).set(
                sum(c.coded_slots for c in sparse))
            telemetry.gauge(scopes.GAUGE_FE_CODED_ENTRIES).set(
                sum(c.coded_entries for c in sparse))
        # A factored coordinate's classes are solved at its latent width,
        # and counted apart from the random effects' (``training.mf.*``).
        factored = [c for c in self.coordinates.values() if c.factored]
        routed = [c for c in self.coordinates.values()
                  if hasattr(c, "routing") and not c.factored]
        buckets = [b for c in routed for b in c.routing()]
        on_kernel = sum(b["entities"] for b in buckets
                        if b["path"] == "kernel")
        telemetry.gauge(scopes.GAUGE_RE_SLOTS).set(
            sum(b["slots"] for b in buckets))
        telemetry.gauge(scopes.GAUGE_RE_ROWS).set(
            sum(c.true_rows() for c in routed))
        telemetry.gauge(scopes.GAUGE_RE_KERNEL_ENTITIES).set(on_kernel)
        telemetry.gauge(scopes.GAUGE_RE_FALLBACK_ENTITIES).set(
            sum(b["entities"] for b in buckets) - on_kernel)
        if factored:
            latent = [b for c in factored for b in c.routing()]
            on_kernel = sum(b["entities"] for b in latent
                            if b["path"] == "kernel")
            telemetry.gauge(scopes.GAUGE_MF_FACTORS).set(
                sum(c.mf_config.num_factors for c in factored))
            telemetry.gauge(scopes.GAUGE_MF_SLOTS).set(
                sum(b["slots"] for b in latent))
            telemetry.gauge(scopes.GAUGE_MF_KERNEL_ENTITIES).set(on_kernel)
            telemetry.gauge(scopes.GAUGE_MF_FALLBACK_ENTITIES).set(
                sum(b["entities"] for b in latent) - on_kernel)
        indexed = [c for c in self.coordinates.values()
                   if hasattr(c, "unslotted_rows")]
        telemetry.gauge(scopes.GAUGE_RE_SCORE_ROWS).set(
            sum(c.dataset.n_rows for c in indexed))
        telemetry.gauge(scopes.GAUGE_RE_SCORE_UNSLOTTED_ROWS).set(
            sum(c.unslotted_rows for c in indexed))
        mesh = self._mesh()
        if mesh is None:
            return
        # What each device of the mesh holds, read off the arrays' own
        # shards (padding rows and padding entities included).
        rows: Dict[object, int] = {}
        slots: Dict[object, int] = {}
        for c in self.coordinates.values():
            batch = getattr(c, "_batch", None)
            if batch is not None:
                for shard in batch.labels.addressable_shards:
                    rows[shard.device] = (rows.get(shard.device, 0)
                                          + shard.data.shape[0])
            for block in getattr(getattr(c, "dataset", None), "blocks", ()):
                for shard in block.row_ids.addressable_shards:
                    slots[shard.device] = (slots.get(shard.device, 0)
                                           + shard.data.size)
        telemetry.gauge(scopes.GAUGE_MESH_DEVICES).set(mesh.devices.size)
        if rows:
            telemetry.gauge(scopes.GAUGE_MESH_ROWS_PER_DEVICE).set(
                max(rows.values()))
        if slots:
            telemetry.gauge(scopes.GAUGE_RE_SLOTS_PER_DEVICE_MAX).set(
                max(slots.values()))
            telemetry.gauge(scopes.GAUGE_RE_SLOTS_PER_DEVICE_MEAN).set(
                sum(slots.values()) / mesh.devices.size)

    def _sparse_counts(self) -> list:
        """The ``LayoutCounts`` of every coordinate whose matrix the sparse
        chooser built (``Coordinate.sparse_work``)."""
        counts = [c.sparse_work()[0] for c in self.coordinates.values()]
        return [c for c in counts if c is not None]

    def _mesh(self):
        """The device mesh the coordinates were built over (``mesh=``),
        or None: every coordinate of one fit shares it."""
        for c in self.coordinates.values():
            mesh = getattr(c, "mesh", None)
            if mesh is not None:
                return mesh
        return None

    def _fused_block_fn(self, n_iters: int, first: int = 0,
                        stop: Optional[int] = None):
        """ONE jitted dispatch executing `n_iters` coordinate-descent
        iterations via lax.scan, each over the coordinates `first` to
        `stop` (exclusive) of the updating sequence: all of them by
        default.

        Per-dispatch latency, not device time, dominates a run of one
        dispatch per coordinate update. Scanning whole iterations on
        device leaves one dispatch per sync point (validation/checkpoint/
        run end). A span of fewer coordinates is the same program for a
        resume that lands inside an iteration and for saves that fall
        between iteration boundaries; `step0` is then the base step of the
        iteration the span belongs to.

        Returns (params, scores, objs[n_iters, stop - first], trackers)
        where trackers holds the span's coordinates and its leaves carry a
        leading n_iters axis; everything stays on device until
        `_materialize_pending` fetches it in a single transfer.

        Every update recomputes its residual afresh, draws its key as
        fold_in(base_key, step) and evaluates the full objective
        (reference: CoordinateDescent.scala:150-212), so a resumed run
        repeats the uninterrupted one.
        """
        names = list(self.coordinates)
        n_coords = len(names)
        if stop is None:
            stop = n_coords
        whole = (first, stop) == (0, n_coords)
        cache_key = n_iters if whole else (n_iters, first, stop)
        fn = self._block_fns.get(cache_key)
        if fn is not None:
            return fn
        loss = loss_for_task(self.task_type)

        def cd_block(data_args, pdata_args, params, scores, base_key, step0,
                     rows):
            def one_iteration(carry, it_idx):
                params, scores = carry
                objs = []
                trs = {}
                for ci in range(first, stop):
                    n = names[ci]
                    coord = self.coordinates[n]
                    step = (step0 + it_idx * np.uint32(n_coords)
                            + np.uint32(ci + 1))
                    with jax.named_scope(scopes.cd_coordinate(n)):
                        residual = None
                        for m in names:
                            if m == n:
                                continue
                            residual = (scores[m] if residual is None
                                        else residual + scores[m])
                        key = jax.random.fold_in(base_key, step)
                        new_p, tracker = coord.pure_update(
                            data_args[n], params[n], residual, key)
                        sc = coord.pure_score(data_args[n], new_p)
                        params = {**params, n: new_p}
                        scores = {**scores, n: sc}
                        total = sc if residual is None else residual + sc
                    objs.append(_full_objective(loss, total, rows, [
                        pen for m in names
                        for pen in self.coordinates[m].pure_penalties(
                            params[m], pdata_args[m])]))
                    trs[n] = tracker
                return (params, scores), (jnp.stack(objs), trs)

            (params, scores), (objs, trs) = lax.scan(
                one_iteration, (params, scores),
                jnp.arange(n_iters, dtype=jnp.uint32))
            return params, scores, objs, trs

        fn = jax.jit(cd_block)
        mesh = self._mesh()
        if mesh is not None:
            # the compile ledger's row says for how many devices
            from photon_ml_tpu.utils.compile_cache import note_partitions

            note_partitions(scopes.CD_BLOCK, mesh.devices.size)
        sparse = self._sparse_counts()
        if sparse:
            # ... and in which layout its sparse fixed effect runs
            from photon_ml_tpu.utils.compile_cache import note_layout

            note_layout(scopes.CD_BLOCK, ",".join(sorted(
                {c.layout for c in sparse})),
                sum(c.coded_slots for c in sparse),
                sum(c.coded_entries for c in sparse))
        self._block_fns[cache_key] = fn
        self._table_due.add(fn)
        self.tracing_guard.track(
            f"block:{n_iters}" if whole
            else f"block:{n_iters}:{first}-{stop}", fn)
        return fn

    def _publish_table(self, fn, args) -> None:
        """At a block function's FIRST dispatch, after its asynchronous call
        has returned (the device is busy with it meanwhile): which scope
        each of its compiled instructions belongs to, read from the
        executable that now runs and kept as strings
        (``utils.compile_cache.instruction_scopes``), and the sharding it
        takes each argument leaf with. Every later dispatch of that
        function: one set lookup, and while telemetry is on the count of
        the argument leaves the dispatch had to move to those shardings
        (``training.cd.dispatch_moves``; a leaf the executable does not
        read is not moved). Called after the dispatch, never around it: no
        frame of it is above the solvers when they trace."""
        if fn in self._table_due:
            self._table_due.discard(fn)
            t0 = time.perf_counter()
            compiled = dispatched_executable(scopes.CD_BLOCK, fn, args)
            note_instructions(scopes.CD_BLOCK, compiled, since=t0)
            self._arg_shardings[fn] = dict(
                jax.tree_util.tree_flatten_with_path(
                    compiled.input_shardings[0])[0])
        if telemetry.enabled():
            taken = self._arg_shardings[fn]
            _M_DISPATCH_MOVES.inc(sum(
                1 for path, leaf in jax.tree_util.tree_flatten_with_path(
                    args)[0]
                if isinstance(leaf, jax.Array) and path in taken
                and not leaf.sharding.is_equivalent_to(taken[path],
                                                       leaf.ndim)))

    def run(
        self,
        num_iterations: int,
        seed: int = 0,
        initial_model: Optional[GameModel] = None,
        checkpoint_dir=None,
        checkpoint_interval: int = 1,
        checkpoint_tag: Union[str, Mapping[str, str]] = "",
    ) -> CoordinateDescentResult:
        """checkpoint_dir: save resumable state every `checkpoint_interval`
        coordinate updates, and resume from the latest checkpoint found
        there (the reference has no mid-training checkpointing — SURVEY §5;
        per-step keys use fold_in so a resumed run is bit-identical to an
        uninterrupted one). checkpoint_tag: caller-supplied configuration
        fingerprint (str or mapping) folded into the checkpoint identity
        check; mappings are compared canonically (key order is cosmetic).

        The host phases below are ``telemetry.spans.phase`` spans named in
        telemetry/scopes.py (``photon.cd.run`` and its children): any
        profiler session sees them on the device operations' clock."""
        from photon_ml_tpu.utils import checkpoint as ckpt

        # One ``with`` around the whole body and no helper method: a Python
        # frame between here and the solvers is paid for at every traced
        # equation (JAX captures a traceback per equation; one frame more
        # was +5 s of a 32 s warm-up on the chip's host, PERF.md PR 29).
        with phase(scopes.CD_RUN):
            if checkpoint_interval < 1:
                raise ValueError("checkpoint_interval must be >= 1, got "
                                 f"{checkpoint_interval}")
            names = list(self.coordinates)

            base_key = jax.random.PRNGKey(seed)
            objective_history: List[float] = []
            validation_history: List[Dict[str, float]] = []
            trackers: Dict[str, list] = {n: [] for n in names}
            timings: Dict[str, float] = {n: 0.0 for n in names}
            best_model, best_metric = None, None
            done_steps = 0
            meta = {"seed": seed, "coordinates": names,
                    "taskType": self.task_type.value,
                    "tag": (dict(checkpoint_tag)
                            if isinstance(checkpoint_tag, Mapping)
                            else checkpoint_tag)}

            def _save(step):
                with phase(scopes.CD_CHECKPOINT):
                    _sync_models()
                    _materialize_pending()
                    ckpt.save_checkpoint(checkpoint_dir, ckpt.CheckpointState(
                        step=step, models=models,
                        objective_history=list(objective_history),
                        validation_history=validation_history,
                        best_metric=best_metric,
                        best_models=(dict(best_model.models)
                                     if best_model is not None else None),
                        timings=timings, trackers=trackers, meta=meta))

            with phase(scopes.CD_PREPARE):
                models = None
                if checkpoint_dir is not None:
                    latest = ckpt.latest_checkpoint(checkpoint_dir)
                    if latest is not None:
                        state = ckpt.load_checkpoint(latest)
                        # Canonical-fingerprint comparison: benign dict
                        # reordering (insertion order of the tag/config
                        # mapping) hashes the same, and mapping tags also
                        # match their legacy flattened string form; a
                        # changed seed, task type, or updating SEQUENCE
                        # (list order is semantic) still hard-errors.
                        if (state.meta is not None
                                and not (ckpt.meta_fingerprints(state.meta)
                                         & ckpt.meta_fingerprints(meta))):
                            raise ValueError(
                                f"checkpoint {latest} belongs to a "
                                "different configuration (saved "
                                f"{state.meta}, current {meta}); point "
                                "--checkpoint-dir elsewhere or delete it")
                        done_steps = state.step
                        models = dict(state.models)
                        objective_history = list(state.objective_history)
                        validation_history = list(state.validation_history)
                        best_metric = state.best_metric
                        timings = dict(state.timings)
                        trackers = {n: list(state.trackers.get(n, []))
                                    for n in names}
                        if state.best_models is not None:
                            best_model = GameModel(dict(state.best_models),
                                                   self.task_type)
                        logger.info("resumed from %s (step %d)", latest,
                                    done_steps)
                # A start is COLD when nothing was handed in and nothing
                # restored: the state the block starts from is then the
                # coordinates' own initial models, known from shapes alone.
                cold = initial_model is None and models is None
                _M_RUNS.inc()

                # The fused path: params/scores dicts are the authoritative
                # training state on device; model objects are materialized
                # lazily (checkpoint, validation, return) so the hot loop is
                # exactly ONE dispatch per span of updates.
                data_args = {n: self.coordinates[n].step_data() for n in names}
                pdata_args = {n: self.coordinates[n].penalty_data()
                              for n in names}
                if cold:
                    _M_COLD_STARTS.inc()
                    start = self._cold_start(data_args)
                    models, params = dict(start.models), dict(start.params)
                else:
                    if models is None:
                        models = {n: initial_model.get_model(n)
                                  for n in names}
                    params = {n: self.coordinates[n].params_of(models[n])
                              for n in names}
                    # Canonicalize param leaves to device arrays:
                    # checkpoint-loaded models carry host np.ndarray leaves,
                    # and np inputs key a SEPARATE pjit executable from the
                    # device arrays of steady-state calls — one silent
                    # recompile per coordinate on every resume (surfaced by
                    # tracing_guard's per_fn=1 invariant below).
                    params = {n: jax.tree.map(jnp.asarray, p)
                              for n, p in params.items()}
                    # laid out as a cold start's are: one executable of
                    # the block whatever the start
                    params, _ = _place_params(self.coordinates, params)

            def _sync_models():
                for m in names:
                    models[m] = self.coordinates[m].model_of(
                        params[m], models[m])

            with phase(scopes.CD_INITIAL_SCORES):
                # Cold: the coordinates whose initial model scores zero by
                # construction (``Coordinate.zero_start``) get their zero
                # vectors from ONE program and no pass over the data; every
                # other start is scored.
                built = _zero_vectors(start.score_specs) if cold else {}
                scores: Dict[str, Array] = {
                    n: (built[n] if n in built else
                        self.coordinates[n].pure_score(data_args[n],
                                                       params[n]))
                    for n in names}
                first_score = next(iter(scores.values()))
                score_dtype = np.dtype(first_score.dtype)
                rows = self._training_rows(score_dtype, first_score.sharding)

            # Device-resident results of fused iteration BLOCKS, appended in
            # step order and fetched host-side in ONE transfer per sync point.
            pending_blocks: List[tuple] = []
            # Tracker blocks left on device at run end (lazy fetch).
            pending_tracker_blocks: List[dict] = []
            n_coords = len(names)

            def _materialize_pending(include_trackers: bool = True):
                """The objective history has one source, the blocks'
                ``objs`` ([iterations, coordinates of the span]), appended
                in step order."""
                if not pending_blocks:
                    return
                objs_dev = [objs for objs, _ in pending_blocks]
                trs_dev = [trs for _, trs in pending_blocks]
                pending_blocks.clear()
                with phase(scopes.CD_WAIT):
                    if include_trackers:
                        objs_host, trs_host = jax.device_get(
                            (objs_dev, trs_dev))
                    else:
                        # Objectives only (small); tracker blocks stay on
                        # device for lazy fetch via LazyTrackers.
                        objs_host, trs_host = jax.device_get(objs_dev), ()
                        pending_tracker_blocks.extend(trs_dev)
                for objs in objs_host:
                    objective_history.extend(float(v) for v in objs.ravel())
                for trs in trs_host:
                    _unstack_tracker_block(trs, names, trackers)

            validating = (self.validation_data is not None
                          and bool(self.validation_evaluators))
            # Whole iterations go as one block when checkpoint saves land on
            # iteration boundaries; otherwise spans of one coordinate (below)
            # keep every save at the step it is due.
            blockable = (checkpoint_dir is None
                         or checkpoint_interval % n_coords == 0)

            def _run_validation(it):
                nonlocal best_metric, best_model
                with phase(scopes.CD_VALIDATE):
                    _sync_models()
                    game_model = GameModel(dict(models), self.task_type)
                    # Device-side scoring: the validation shards live in
                    # HBM (uploaded once at first use); per-iteration scoring
                    # is one jitted dispatch + ONE transfer of the score
                    # vector, vs the reference's per-submodel score joins
                    # (FixedEffectModel.scala:94-105, RandomEffectModel.scala).
                    if self._val_scorer is None:
                        from photon_ml_tpu.models.device_scoring import (
                            DeviceGameScorer,
                        )
                        self._val_scorer = DeviceGameScorer(
                            game_model, self.validation_data, dtype=score_dtype)
                    val_scores = np.asarray(self._val_scorer.score(game_model))
                    metrics = {
                        ev.name: ev.evaluate_dataset(val_scores,
                                                     self.validation_data)
                        for ev in self.validation_evaluators}
                    validation_history.append(metrics)
                    head = self.validation_evaluators[0]
                    m0 = metrics[head.name]
                    if head.better_than(m0, best_metric):
                        best_metric, best_model = m0, game_model
                    logger.info("iter %d validation: %s", it, metrics)

            step = 0
            it = 0
            while it < num_iterations:
                if step + n_coords <= done_steps:
                    # Whole iteration was restored, incl. its validation.
                    step += n_coords
                    it += 1
                    continue
                # resume lands mid-iteration
                partial_resume = step < done_steps

                if blockable and not partial_resume:
                    # ------ fused block path: one dispatch per sync span ---
                    if validating:
                        span = 1
                    elif checkpoint_dir is not None:
                        next_save = ((step // checkpoint_interval) + 1
                                     ) * checkpoint_interval
                        span = (next_save - step) // n_coords
                    else:
                        span = num_iterations - it
                    span = max(1, min(span, num_iterations - it))
                    t0 = time.perf_counter()
                    with phase(scopes.CD_DISPATCH):
                        block_fn = self._fused_block_fn(span)
                        block_args = (data_args, pdata_args, params, scores,
                                      base_key, np.uint32(step), rows)
                        params, scores, objs, trs = block_fn(*block_args)
                        self._publish_table(block_fn, block_args)
                        del block_args
                    pending_blocks.append((objs, trs))
                    # Host seconds to ENQUEUE the block (and to trace, lower
                    # and load it on the first call), split evenly: not any
                    # coordinate's device time (CoordinateDescentResult).
                    elapsed = time.perf_counter() - t0
                    for n in names:
                        timings[n] += elapsed / n_coords
                    step += span * n_coords
                    it += span
                    logger.info(
                        "iterations %d-%d enqueued as one device block "
                        "(host dispatch %.1f ms)", it - span, it - 1,
                        1e3 * elapsed)
                    if validating:
                        _run_validation(it - 1)
                    if (checkpoint_dir is not None
                            and (validating
                                 or step % checkpoint_interval == 0)):
                        # Iteration-boundary save (carries this iteration's
                        # validation entry + best model when validating).
                        _save(step)
                    continue

                # ---- a resume that lands inside an iteration, or saves ----
                # ---- between iteration boundaries: the same program over ---
                # ---- a span of one coordinate ------------------------------
                step0 = np.uint32(step)  # this iteration's base step
                for ci, n in enumerate(names):
                    step += 1
                    if step <= done_steps:
                        continue  # resumed past this update
                    t0 = time.perf_counter()
                    with phase(scopes.CD_DISPATCH):
                        span_fn = self._fused_block_fn(1, ci, ci + 1)
                        block_args = (data_args, pdata_args, params, scores,
                                      base_key, step0, rows)
                        params, scores, objs, trs = span_fn(*block_args)
                        self._publish_table(span_fn, block_args)
                        del block_args
                    pending_blocks.append((objs, trs))
                    timings[n] += time.perf_counter() - t0
                    logger.info("iter %d coordinate %s enqueued (host "
                                "dispatch %.1f ms)", it, n,
                                1e3 * (time.perf_counter() - t0))
                    # Defer the last-coordinate save to after validation:
                    # one save per iteration boundary, and a crash during
                    # validation resumes from before the final update, so the
                    # re-run never skips the iteration's validation/best-model
                    # bookkeeping.
                    last_of_iteration = ci == n_coords - 1
                    if (checkpoint_dir is not None
                            and step % checkpoint_interval == 0
                            and not (last_of_iteration and validating)):
                        _save(step)

                if validating:
                    _run_validation(it)
                    if checkpoint_dir is not None:
                        _save(step)
                it += 1

            # The host blocks here, on the objectives' transfer, until the
            # device has finished the last block (trackers stay on the device).
            _materialize_pending(include_trackers=False)
            with phase(scopes.CD_FINISH):
                _sync_models()
                # Hot-loop compile invariant: every fused executable (one
                # block fn per span) traced exactly once this run — the
                # runtime complement of jaxlint's retrace-hazard rule. A
                # trip here means argument shapes/dtypes/statics drifted
                # call-to-call and every "one dispatch" above silently paid
                # a recompile.
                self.tracing_guard.assert_max_retraces(per_fn=1)
                # The random-effect coordinates built over a mesh: each
                # divides its score exchange over it.
                for c in self.coordinates.values():
                    if (getattr(c, "mesh", None) is not None
                            and hasattr(c, "dataset")):
                        _M_EXCHANGE_DIVIDED.inc()
                if logger.isEnabledFor(logging.INFO) and objective_history:
                    logger.info("objective history: %s",
                                ["%.6f" % v for v in objective_history])
                final = GameModel(dict(models), self.task_type)
                if best_model is None:
                    best_model = final
                lazy = LazyTrackers(trackers, pending_tracker_blocks, names)
                if telemetry.enabled() and self._sparse_counts():
                    # the sparse products the solves ran: a fetch of the
                    # trackers, so only where somebody is listening
                    _M_FE_PRODUCTS.inc(sum(
                        c.sparse_work(lazy[name])[1]
                        for name, c in self.coordinates.items()))
                if telemetry.enabled():
                    # what the factored coordinates' updates and the
                    # trust-region solves ran: a fetch of their trackers too
                    for name, c in self.coordinates.items():
                        if c.tron:
                            cg, attempted = c.tron_work(lazy[name])
                            _M_FE_CG_STEPS.inc(cg)
                            _M_FE_TRON_STEPS.inc(attempted)
                            _M_FE_PASSES.inc(c.tron_passes(lazy[name]))
                        if c.factored:
                            runs, its = c.factored_work(lazy[name])
                            _M_MF_ALTERNATIONS.inc(runs)
                            _M_MF_REFIT_ITERATIONS.inc(its)
                return CoordinateDescentResult(
                    model=final,
                    objective_history=list(objective_history),
                    validation_history=validation_history,
                    best_model=best_model,
                    best_metric=best_metric,
                    trackers=lazy,
                    timings=timings,
                )

    def _cold_start(self, data_args) -> "_ColdStart":
        """What a cold ``run()`` starts from, derived ONCE per object (like
        ``_rows_cache``): every job after the first finds it here, because
        whatever the host does before the block's enqueue is device idle.

        The coordinates' initial models and their parameters are immutable
        device arrays and the block donates nothing, so every cold run
        starts from the same ones. The scores are the block's carry, which
        it replaces: each run needs vectors of its own, of exactly the
        shape and dtype ``pure_score`` returns (``jax.eval_shape``: a
        trace, nothing runs) and, where the coordinate's data spans
        devices, with the sharding its compiled output carries.

        Where a coordinate's data lies over a mesh, its parameters are laid
        out here with the shardings the block takes them with
        (``Coordinate.param_shardings``): left on one device, every cold
        dispatch would move each leaf over the mesh again while the chips
        wait. On one device they stay as ``jnp.asarray`` made them."""
        if self._cold_cache is not None:
            return self._cold_cache
        models = {n: c.initialize_model()
                  for n, c in self.coordinates.items()}
        params = {n: jax.tree.map(jnp.asarray, c.params_of(models[n]))
                  for n, c in self.coordinates.items()}
        specs = tuple((n, _score_spec(c, data_args[n], params[n]))
                      for n, c in self.coordinates.items() if c.zero_start)
        params, placed = _place_params(self.coordinates, params)
        telemetry.gauge(scopes.GAUGE_CD_COLD_PLACED_LEAVES).set(placed)
        self._cold_cache = _ColdStart(models, params, specs)
        return self._cold_cache

    def _training_rows(self, dtype, sharding) -> Tuple[Array, Array, Array]:
        """(labels, offsets, weights) aligned with the global row order,
        taken from the first coordinate's data. Cached — built once per run,
        kept in HBM. Over a mesh they are laid out as the score vectors are
        (``sharding``), as the block takes them."""
        cached = getattr(self, "_rows_cache", None)
        if cached is not None:
            return cached
        first = self.coordinates[list(self.coordinates)[0]]
        data = getattr(first, "data", None)
        if isinstance(data, GameDataset) or hasattr(data, "responses"):
            # GameDataset (host f64 columns) or a streamed-ingest shim
            # (data/shard_cache.StreamedFixedEffectData — device f32
            # columns, for which the asarray cast is a no-op and the
            # values match the one-shot cast bit for bit).
            rows = (jnp.asarray(data.responses, dtype),
                    jnp.asarray(data.offsets, dtype),
                    jnp.asarray(data.weights, dtype))
        else:
            # Random-effect-only: reconstruct from the blocks.
            rows = _rows_from_blocks(first.dataset)
            rows = tuple(r.astype(dtype) for r in rows)
        if self._mesh() is not None:
            rows = jax.device_put(rows, sharding)
        self._rows_cache = rows
        return rows


class _ColdStart(NamedTuple):
    """``CoordinateDescent._cold_start``: by coordinate name, the initial
    models, their parameters, and the spec of the initial score vector of
    each coordinate whose ``zero_start`` says it may be built."""
    models: Dict[str, object]
    params: Dict[str, object]
    score_specs: Tuple[Tuple[str, jax.ShapeDtypeStruct], ...]


def _place_params(coordinates: Mapping[str, Coordinate], params):
    """``params`` with the parameters of every coordinate that states its
    ``param_shardings()`` laid out with them, in ONE ``device_put``, and the
    number of leaves so placed."""
    shardings = {n: s for n, c in coordinates.items()
                 if (s := c.param_shardings()) is not None}
    if not shardings:
        return params, 0
    placed = jax.device_put({n: params[n] for n in shardings}, shardings)
    return {**params, **placed}, len(jax.tree.leaves(shardings))


def _score_spec(coord: Coordinate, data, params) -> jax.ShapeDtypeStruct:
    """Shape, dtype and sharding of ``coord.pure_score(data, params)``
    without running it. Data on one device: a trace. Data over a mesh: the
    compiler chooses the output's sharding, so the scoring program is
    compiled (as the scored branch would compile it) and asked."""
    out = jax.eval_shape(coord.pure_score, data, params)
    if all(len(leaf.sharding.device_set) == 1
           for leaf in jax.tree.leaves((data, params))
           if isinstance(leaf, jax.Array)):
        return jax.ShapeDtypeStruct(out.shape, out.dtype)
    compiled = jax.jit(coord.pure_score).lower(data, params).compile()
    return jax.ShapeDtypeStruct(out.shape, out.dtype,
                                sharding=compiled.output_shardings)


@functools.partial(jax.jit, static_argnames=("specs",))
def _zero_vectors(specs: Tuple[Tuple[str, jax.ShapeDtypeStruct], ...]):
    """One program, keyed by the specs, for all the zero vectors a cold
    start needs: one dispatch a run whatever the number of coordinates."""
    return {
        n: (jnp.zeros(s.shape, s.dtype) if s.sharding is None else
            lax.with_sharding_constraint(jnp.zeros(s.shape, s.dtype),
                                         s.sharding))
        for n, s in specs}


def _rows_from_blocks(ds) -> Tuple[Array, Array, Array]:
    n = ds.n_rows
    labels = np.zeros(n + 1, np.float32)
    offsets = np.zeros(n + 1, np.float32)
    weights = np.zeros(n + 1, np.float32)
    for blocks in (ds.blocks, [b for b in ds.passive_blocks if b is not None]):
        for b in blocks:
            rid = np.asarray(b.row_ids).ravel()
            labels[rid] = np.asarray(b.labels).ravel()
            offsets[rid] = np.asarray(b.offsets).ravel()
            weights[rid] = np.asarray(b.weights).ravel()
    return (jnp.asarray(labels[:-1]), jnp.asarray(offsets[:-1]),
            jnp.asarray(weights[:-1]))
