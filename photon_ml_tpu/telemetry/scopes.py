"""The names of the training fit's phases: one table, imported by the code
that opens each name, by the tests and by ``dev_scripts/trace_scopes.py``
(docs/OBSERVABILITY.md §The training fit lists each with its metric).

Device phases are ``jax.named_scope`` inside the jitted block and step
(HLO metadata only: the ``op_name`` path of every operation traced under
the scope carries the name, and the profiler's device events carry that
path; nothing runs for it). Host phases are ``telemetry.spans.phase``
spans of ``CoordinateDescent.run``. Both land in the profiler's one trace,
on one clock.
"""

from __future__ import annotations

import re

PREFIX = "photon."

# -- device scopes (jax.named_scope inside traced code) -----------------------
FE_SOLVE = "photon.fe.solve"      # body of _solve_fixed
FE_SCORE = "photon.fe.score"      # _fe_score_impl
# the two products of a fixed effect whose matrix is SPARSE (X.w by gathers
# from w, X^T.u by a scatter-add into f[d]): children of FE_SOLVE and of
# FE_SCORE, opened by the layout's own ``matvec`` / ``rmatvec`` (a dense
# matrix opens neither: its products are the solve's fusions)
FE_MATVEC = "photon.fe.matvec"
FE_RMATVEC = "photon.fe.rmatvec"
# the two parts of a matvec over a slot-major ELL that has coded slots
# (``ops.features.SlotMajorEllFeatures``): the slots read through a code and
# the slot's few table entries, and the slots read by gather. Children of
# FE_MATVEC, opened only where a matrix has a coded slot.
FE_MATVEC_CODED = "photon.fe.matvec.coded"
FE_MATVEC_GATHERED = "photon.fe.matvec.gathered"
# one Hessian-vector product of a trust-region (TRON) solve, one a CG step:
# with the margin-cached product (``GLMObjective.make_tron_hvp_at_margins``,
# under bounds ``make_tron_hvp``) a matvec and an rmatvec over the
# curvature weights of the outer iteration. A child
# of FE_SOLVE (a random effect's TRON opens it under RE_SOLVE), never a
# leaf: the trial's gradient of an outer iteration (and under bounds its
# margin pass and the trial's value) stays under the solve's own name
FE_HVP = "photon.fe.hvp"
# the programs that count a sparse matrix on the device and lay it out
# (``ops.features.sparse_rows_to_device``): at construction, never in a fit
FE_LAYOUT = "photon.fe.layout"
# residual gather into the blocks' slots; under a mesh each device gathers
# its own, after ONE all-reduce that makes the residual whole (under the scope)
RE_GATHER = "photon.re.gather"
RE_SOLVE = "photon.re.solve"      # kernel or vmapped solve, one child a class
RE_MARGINS = "photon.re.margins"  # block.local_margins
# the margins' way back into row order: one gather by row through
# slot_of_row; under a mesh each device gathers its own row range, after ONE
# all-gather of the flat margins (under the scope). The name is the
# exchange's way back, whatever operation does it.
RE_SCATTER = "photon.re.scatter"
CD_OBJECTIVE = "photon.cd.objective"  # the loss sum and the penalties
# the factored (matrix-factorization) coordinate's own phases, under its
# ``photon.cd.<coordinate>``; its residual gather, its margins and their way
# back keep RE_GATHER / RE_MARGINS / RE_SCATTER
MF_FLATTEN = "photon.mf.flatten"  # _flatten_factored_static: x, y, off, w
MF_PROJECT = "photon.mf.project"  # x . B^T, one einsum a size class
MF_LATENT = "photon.mf.latent"    # the latent solves, one child a class
MF_REFIT = "photon.mf.refit"      # per-slot factors and the solve for B

#: The leaf scopes: an operation counts under the innermost of these on
#: its path.
DEVICE_SCOPES = (FE_SOLVE, FE_SCORE, RE_GATHER, RE_SOLVE, RE_MARGINS,
                 RE_SCATTER, CD_OBJECTIVE)
#: The factored coordinate's leaf scopes: only a fit that has such a
#: coordinate has operations under them, so they are no part of
#: ``DEVICE_SCOPES`` (which every random-effect fit fills).
MF_SCOPES = (MF_FLATTEN, MF_PROJECT, MF_LATENT, MF_REFIT)
#: Children of ``FE_SOLVE`` / ``FE_SCORE``: an operation under one of them
#: counts there, and what is left of the parent's row is the d-space work
#: (the two-loop, the line search's n-vectors). Not in ``DEVICE_SCOPES``:
#: a fit over a dense matrix has no such operation.
FE_PRODUCT_SCOPES = (FE_MATVEC, FE_RMATVEC)
#: Children of ``FE_MATVEC`` where the matrix has coded slots: together
#: they are the matvec, and the script prints each under it.
FE_MATVEC_PARTS = (FE_MATVEC_CODED, FE_MATVEC_GATHERED)
#: gather + margins + scatter: the score exchange.
EXCHANGE_SCOPES = (RE_GATHER, RE_MARGINS, RE_SCATTER)

_CD = "photon.cd."


def cd_coordinate(name: str) -> str:
    """The scope around one coordinate's whole update inside the block:
    the place to read a coordinate's device time."""
    return _CD + name


def re_size_class(rows: int) -> str:
    """Child of ``RE_SOLVE`` and of ``MF_LATENT``: one per bucket size
    class (padded rows)."""
    return f"r{int(rows)}"


# -- an operation's place in the table -----------------------------------------
#: Every leaf scope of the table; the factored coordinate's own only where a
#: fit has such a coordinate.
LEAF_SCOPES = DEVICE_SCOPES + MF_SCOPES
_SIZE_CLASS = re.compile(r"^r\d+$")


def place(path: str) -> dict:
    """Where an operation counts, from its ``op_name`` path (the HLO
    metadata ``jax.named_scope`` writes; a profiler's device event and the
    block's instruction table, ``utils.compile_cache.instruction_scopes``,
    carry the same string): ONE definition for ``dev_scripts/
    trace_scopes.py``, the benchmark's readers and an operator.

    ``leaf``: the innermost table scope on the path; ``coordinate``: its
    ``photon.cd.<name>``; ``size_class``: the ``r<rows>`` under
    ``photon.re.solve`` or ``photon.mf.latent``; ``product``: the sparse product
    (``photon.fe.matvec`` / ``.rmatvec``) under the leaf, as
    ``<leaf>/<product>``, and so the trust-region solve's Hessian-vector
    product (``photon.fe.hvp``; a sparse product inside it keeps its own
    key); ``part``: the matvec's coded or gathered slots, as
    ``<leaf>/<product>/<part>``;
    ``scoped``: under any ``photon.*`` at all."""
    leaf = coordinate = size_class = product = piece = None
    parts = path.split("/")
    for i, part in enumerate(parts):
        if (part in FE_PRODUCT_SCOPES or part == FE_HVP) and leaf:
            product = f"{leaf}/{part}"
        elif part in FE_MATVEC_PARTS and product:
            piece = f"{product}/{part}"
        elif part in LEAF_SCOPES:
            leaf = part
            if (part in (RE_SOLVE, MF_LATENT)
                    and i + 1 < len(parts)
                    and _SIZE_CLASS.match(parts[i + 1])):
                size_class = parts[i + 1]
        elif part.startswith(_CD):
            coordinate = part
    return {"leaf": leaf, "coordinate": coordinate, "size_class": size_class,
            "product": product, "part": piece,
            "scoped": leaf is not None or coordinate is not None}


# Operations that are collectives. One the partitioner (or a compiler pass)
# made is named after its opcode (``%all-reduce.12``: what the v5e prints,
# looked at by hand, PR 31, JAX 0.9.0, ``PERF.md`` section 5); one the
# program wrote is named after JAX's primitive and shows its opcode only
# behind the `` = `` (``%psum_invariant.16 = f32[20000265]{...}
# all-reduce(...)``: the divided exchange's two, PR 32). Either counts. An
# asynchronous one is two events, ``<name>-start`` and ``<name>-done``:
# both carry the prefix, both count.
COLLECTIVE_PREFIXES = ("all-reduce", "all-gather", "reduce-scatter",
                       "all-to-all", "collective-permute")
_COLLECTIVE_OPCODE = re.compile(
    r" = (?:\([^=]*?\)|\S+) (?:%s)(?:-start|-done)?\("
    % "|".join(COLLECTIVE_PREFIXES))


def is_collective(name: str) -> bool:
    """Whether a device event (its full HLO text: ``%name = shape
    opcode(...)``; the name alone where that is all there is) is a
    collective."""
    short = name.split(" = ")[0].lstrip("%")
    return (short.startswith(COLLECTIVE_PREFIXES)
            or _COLLECTIVE_OPCODE.search(name) is not None)


# -- the kernel and the jitted programs ---------------------------------------
#: ``name=`` of the entity solver's ``pallas_call`` (a mode suffix follows):
#: the prefix of its device events.
KERNEL = "pallas_entity_lbfgs"
#: Function name of the jitted block, whatever span of coordinates it
#: covers: JAX's compile events and the trace's ``XLA Modules`` line carry
#: it as ``jit_<name>``.
CD_BLOCK = "cd_block"

# -- host phases of CoordinateDescent.run (telemetry.spans.phase) ------------
CD_RUN = "photon.cd.run"                        # parent of all
# prepare: checkpoint restore, step_data, models and params. Cold start (no
# initial_model, nothing restored): both come from the object's cache, no
# dispatch after its first run. Warm or resumed: params_of the models given.
CD_PREPARE = "photon.cd.run.prepare"
# initial_scores: cold, the enqueue of ONE program that makes the zero
# vectors (about nothing); warm or resumed, or a coordinate without a
# declared zero start: the enqueue of one eager pure_score a coordinate
# (device: a pass over X, the margins and a scatter of every slot).
CD_INITIAL_SCORES = "photon.cd.run.initial_scores"
CD_DISPATCH = "photon.cd.run.dispatch"  # enqueue; trace/lower/load on the 1st
CD_WAIT = "photon.cd.run.wait"          # device_get: host blocked on device
CD_FINISH = "photon.cd.run.finish"      # _sync_models, tracing guard, result
CD_VALIDATE = "photon.cd.run.validate"
CD_CHECKPOINT = "photon.cd.run.checkpoint"

#: Phases every run opens; validate and checkpoint only where they run.
HOST_PHASES = (CD_PREPARE, CD_INITIAL_SCORES, CD_DISPATCH, CD_WAIT,
               CD_FINISH)
OPTIONAL_HOST_PHASES = (CD_VALIDATE, CD_CHECKPOINT)

# -- gauges set when a random-effect coordinate is built ----------------------
GAUGE_RE_SLOTS = "training.re.slots"
GAUGE_RE_ROWS = "training.re.rows"
GAUGE_RE_KERNEL_ENTITIES = "training.re.kernel_entities"
GAUGE_RE_FALLBACK_ENTITIES = "training.re.fallback_entities"
#: The index work of one scoring, summed over the coordinates that score
#: through ``slot_of_row``: rows gathered (n a coordinate, beside the slots
#: above that a scatter-add would walk), and those of them that sit in no
#: slot and read the appended zero.
GAUGE_RE_SCORE_ROWS = "training.re.score.rows"
GAUGE_RE_SCORE_UNSLOTTED_ROWS = "training.re.score.unslotted_rows"

# -- gauges set when a factored random-effect coordinate is built --------------
#: Summed over the fit's factored coordinates: the latent width k, the slots
#: of the blocks they solve over (entities x padded rows, every class), and
#: the entities whose latent solve (at width k, not d: the guard decides by
#: ``r x k``) goes to the fused kernel or to the vmapped solver. The
#: ``training.re.*`` gauges above leave these coordinates out.
GAUGE_MF_FACTORS = "training.mf.factors"
GAUGE_MF_SLOTS = "training.mf.slots"
GAUGE_MF_KERNEL_ENTITIES = "training.mf.kernel_entities"
GAUGE_MF_FALLBACK_ENTITIES = "training.mf.fallback_entities"

# -- gauges of a fit whose fixed effect's matrix is sparse ---------------------
#: Summed over the fixed-effect coordinates whose matrix came from the
#: chooser (``ops.features.layout_counts``): the stored values that are not
#: 0, the slots the chosen layout stores (padding included: slots / nnz is
#: what every product pays over the true work), and the non-zeros of the
#: fullest column (how hard the scatter-add's collisions can be).
GAUGE_FE_NNZ = "training.fe.nnz"
GAUGE_FE_SLOTS = "training.fe.slots"
GAUGE_FE_MAX_COL_DEGREE = "training.fe.max_col_degree"
#: Of a row's k slots, those the slot-major ELL reads by code in its
#: row-wise products (at most ``ops.features.CODED_SLOT_TOP_CLASS`` distinct
#: columns over all rows): 0 says the mechanism found nothing to engage on
#: (rows sorted by column id scatter the fields over the slots). And the
#: entries of those slots' tables, each padded to its slot's class: what the
#: coded side pays for, and how far the classes reached on this matrix.
GAUGE_FE_CODED_SLOTS = "training.fe.coded_slots"
GAUGE_FE_CODED_ENTRIES = "training.fe.coded_entries"

# -- gauges of a fit whose coordinates lie over a device mesh (mesh=) ----------
GAUGE_MESH_DEVICES = "training.mesh.devices"
#: Rows of the fixed effect's batch on the fullest device (padding included).
GAUGE_MESH_ROWS_PER_DEVICE = "training.mesh.rows_per_device"
#: Random-effect slots (entities x padded rows, every size class, the empty
#: entities that fill a class to a multiple of the mesh included) on the
#: fullest device, and the mean over devices.
GAUGE_RE_SLOTS_PER_DEVICE_MAX = "training.re.slots_per_device.max"
GAUGE_RE_SLOTS_PER_DEVICE_MEAN = "training.re.slots_per_device.mean"

# -- counters of CoordinateDescent.run (inc is a no-op while telemetry is off) -
COUNTER_CD_RUNS = "training.cd.runs"
#: Runs that started cold (no ``initial_model``, no checkpoint restored):
#: the runs whose initial scores were built and not computed.
COUNTER_CD_COLD_STARTS = "training.cd.cold_starts"
#: Gauge, set once per ``CoordinateDescent`` object by its first cold run:
#: the parameter leaves the cold start placed with a coordinate's
#: ``param_shardings()`` (1 + the size classes of a mesh GLMix fit; 0 on
#: one device).
GAUGE_CD_COLD_PLACED_LEAVES = "training.cd.cold_placed_leaves"
#: Block-argument leaves at a dispatch whose sharding is not the one the
#: compiled block takes its argument with: each is a transfer the dispatch
#: makes before the block can run. Counted while telemetry is on.
COUNTER_CD_DISPATCH_MOVES = "training.cd.dispatch_moves"
#: Per run, the sparse products its fixed-effect solves ran, from the
#: solvers' own counts: a margin-cached L-BFGS solve of ``it`` iterations is
#: ``it + 1`` matvec and ``it + 1`` rmatvec (``OptimizerResult.iterations``);
#: the block's scoring pass is one matvec more a sweep and is not counted
#: here. A TRON solve is its own ``feature_passes`` (below). 0 where no
#: fixed effect is sparse; an OWL-QN or bounded L-BFGS solve (whose
#: iterations are not products) adds nothing.
COUNTER_FE_PRODUCTS = "training.fe.products"
#: Per run, over its fixed-effect coordinates' trust-region (TRON) solves,
#: from the solvers' own counts: the inner CG steps, one Hessian-vector
#: product (``photon.fe.hvp``) each (``OptimizerResult.cg_iterations``), and
#: the outer iterations run, accepted or rejected
#: (``OptimizerResult.attempted_iterations``), and the passes over the
#: feature matrix they made (``OptimizerResult.feature_passes``: 2 + attempted
#: + 2 cg a solve where the loop carries the margins, as it does without
#: bounds). 0 where no fixed effect runs TRON.
COUNTER_FE_CG_STEPS = "training.fe.cg_steps"
COUNTER_FE_TRON_STEPS = "training.fe.tron_steps"
COUNTER_FE_PASSES = "training.fe.passes"
#: Per run, over its factored coordinates' updates: the alternations (latent
#: solves then a refit of B) they ran, and the solver iterations of those
#: refits, from the trackers' ``iterations`` (a fetch: only while telemetry
#: is enabled). 0 where no coordinate is factored.
COUNTER_MF_ALTERNATIONS = "training.mf.alternations"
COUNTER_MF_REFIT_ITERATIONS = "training.mf.refit_iterations"
#: Per run, the random-effect coordinates built over a mesh: each divides
#: its score exchange over it (each device gathers the residual into the
#: slots of its own entities and the margins into its own rows, one
#: collective each way, under ``photon.re.gather`` / ``photon.re.scatter``).
#: 0 without a mesh.
COUNTER_RE_EXCHANGE_DIVIDED = "training.re.exchange.divided"
