"""The training fit names itself (telemetry/scopes.py): device scopes in
the fused block's compiled text, the kernel's name on its ``pallas_call``,
host phases of ``CoordinateDescent.run`` under telemetry and in a profiler
trace, the compile ledger, the work gauges, and
``dev_scripts/trace_scopes.py``'s reduction from a trace to ms by scope.
All on the CPU at a tiny size: names and counts, no times."""

import functools
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dev_scripts import trace_scopes
from photon_ml_tpu import telemetry
from photon_ml_tpu.algorithm import CoordinateDescent
from photon_ml_tpu.evaluation import build_evaluator
from photon_ml_tpu.telemetry import scopes
from photon_ml_tpu.telemetry.spans import _NOOP, phase, span
from photon_ml_tpu.types import TaskType
from photon_ml_tpu.utils import compile_cache
from tests.test_coordinate_descent import build_coordinates, make_glmix_data

TASK = TaskType.LOGISTIC_REGRESSION
RECORDED = Path(__file__).parent / "data" / "trace_glmix_fit_scopes.json"
# make_glmix_data's 12 users at 400 rows fall in two size classes.
SIZE_CLASSES = ("r32", "r64")
BLOCK_SCOPES = scopes.DEVICE_SCOPES + (
    scopes.cd_coordinate("fixed"), scopes.cd_coordinate("perUser"),
) + SIZE_CLASSES


@pytest.fixture(autouse=True)
def _clean_telemetry():
    telemetry.disable()
    telemetry.reset()
    telemetry.tracer().record_events = False
    yield
    telemetry.disable()
    telemetry.reset()
    telemetry.tracer().record_events = False


def _data(seed=20260729):
    return make_glmix_data(np.random.default_rng(seed))[0]


def _descent(data=None, **kw):
    return CoordinateDescent(build_coordinates(data or _data()), TASK, **kw)


# -- device scopes in the block's text -----------------------------------------

@pytest.fixture(scope="module")
def block_texts():
    """The lowered and the compiled text of ``cd_block`` at the arguments
    ``run()`` gives it (recorded by standing in for the cached block)."""
    cd = _descent()
    fn = cd._fused_block_fn(2)
    seen = {}

    def recorder(*args):
        seen["args"] = args
        return fn(*args)

    cd._block_fns[2] = recorder
    cd.run(2)
    lowered = fn.lower(*seen["args"])
    return {"fn": fn, "cd": cd,
            "lowered": lowered.as_text(debug_info=True),
            "compiled": lowered.compile().as_text()}


@pytest.mark.parametrize("stage", ["lowered", "compiled"])
@pytest.mark.parametrize("scope", BLOCK_SCOPES)
def test_block_text_carries_scope(block_texts, stage, scope):
    """Some operation's name path holds the scope as a whole component."""
    text = block_texts[stage]
    assert f"{scope}/" in text or f"/{scope}\"" in text, scope
    if stage == "compiled":
        import re

        paths = re.findall(r'op_name="([^"]*)"', text)
        assert any(scope in p.split("/") for p in paths)


def test_size_classes_sit_under_re_solve(block_texts):
    assert f"{scopes.RE_SOLVE}/r32/" in block_texts["compiled"]
    assert f"{scopes.RE_SOLVE}/r64/" in block_texts["compiled"]


def test_jitted_functions_carry_the_table_names(block_texts):
    cd = block_texts["cd"]
    assert block_texts["fn"].__name__ == scopes.CD_BLOCK
    assert f"jit({scopes.CD_BLOCK})" in block_texts["compiled"]
    # a span of fewer coordinates is the same function
    assert cd._fused_block_fn(1, 1, 2).__name__ == scopes.CD_BLOCK


@pytest.mark.parametrize("mode", ["lbfgs", "owlqn", "tron"])
def test_pallas_call_carries_the_kernel_name(mode):
    from photon_ml_tpu.ops.losses import loss_for_task
    from photon_ml_tpu.ops.pallas_entity_solver import pallas_entity_lbfgs

    e, r, d = 4, 8, 4
    f32 = functools.partial(jnp.zeros, dtype=jnp.float32)
    fn = functools.partial(
        pallas_entity_lbfgs, loss_for_task(TASK), max_iter=3, tol=1e-4,
        mode=mode, interpret=True)
    jaxpr = jax.make_jaxpr(fn)(
        f32((e, r, d)), f32((e, r)), f32((e, r)), jnp.ones((e, r), "f4"),
        f32((e, d)), 1.0, 0.5 if mode == "owlqn" else 0.0)

    def walk(jp):
        for eqn in jp.eqns:
            if eqn.primitive.name == "pallas_call":
                yield eqn
            for sub in jax.core.jaxprs_in_params(eqn.params):
                yield from walk(sub)

    calls = list(walk(jaxpr.jaxpr))
    assert len(calls) == 1
    name = calls[0].params["name"]
    assert name.startswith(scopes.KERNEL)
    assert name == (scopes.KERNEL if mode == "lbfgs"
                    else f"{scopes.KERNEL}_{mode}")


# -- host phases -----------------------------------------------------------------

@pytest.fixture(scope="module")
def phase_events(tmp_path_factory):
    """Span events of one run with validation and checkpoints, telemetry
    enabled with raw events kept."""
    telemetry.reset()
    telemetry.enable(trace=True)
    try:
        data = _data()
        cd = _descent(data, validation_data=data,
                      validation_evaluators=[build_evaluator("AUC")])
        cd.run(2, checkpoint_dir=tmp_path_factory.mktemp("ckpt"),
               checkpoint_interval=2)
        events = list(telemetry.tracer().events)
        attribution = telemetry.stage_attribution()
    finally:
        telemetry.disable()
        telemetry.reset()
        telemetry.tracer().record_events = False
    return events, attribution


@pytest.mark.parametrize(
    "name", scopes.HOST_PHASES + scopes.OPTIONAL_HOST_PHASES)
def test_run_records_phase_nested_under_run(phase_events, name):
    events, attribution = phase_events
    assert attribution[name]["count"] >= 1
    runs = [e for e in events if e["name"] == scopes.CD_RUN]
    assert len(runs) == 1
    lo, hi = runs[0]["ts"], runs[0]["ts"] + runs[0]["dur"]
    mine = [e for e in events if e["name"] == name]
    assert mine and all(lo <= e["ts"] and e["ts"] + e["dur"] <= hi
                        and e["tid"] == runs[0]["tid"] for e in mine)


def test_run_self_time_is_what_no_phase_covers(phase_events):
    _, attribution = phase_events
    run = attribution[scopes.CD_RUN]
    assert 0.0 <= run["self_s"] < run["total_s"]


def test_result_is_bitwise_the_same_with_telemetry_on_and_off():
    data = _data()
    off = _descent(data).run(2, seed=3)
    telemetry.enable()
    on = _descent(data).run(2, seed=3)
    assert on.objective_history == off.objective_history
    np.testing.assert_array_equal(
        np.asarray(on.model.get_model("fixed").glm.coefficients.means),
        np.asarray(off.model.get_model("fixed").glm.coefficients.means))
    for a, b in zip(on.model.get_model("perUser").local_coefs,
                    off.model.get_model("perUser").local_coefs):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def _names_in_profile(trace_dir) -> set:
    from jax.profiler import ProfileData

    files = sorted(Path(trace_dir).rglob("*.xplane.pb"))
    profile = ProfileData.from_file(str(files[-1]))
    return {ev.name for plane in profile.planes for line in plane.lines
            for ev in line.events}


def test_phases_reach_a_profiler_trace_without_telemetry(tmp_path):
    """Any profiler session sees the fit's phases; ``span()`` itself stays
    the shared no-op while telemetry is disabled."""
    cd = _descent()
    cd.run(1)  # compile outside the trace
    assert span("photon.test.stage") is _NOOP
    with jax.profiler.trace(str(tmp_path)):
        with span("photon.test.stage"):
            cd.run(1)
        with phase("photon.test.phase"):
            pass
    names = _names_in_profile(tmp_path)
    assert {scopes.CD_RUN, *scopes.HOST_PHASES} <= names
    assert "photon.test.phase" in names
    assert "photon.test.stage" not in names
    assert telemetry.stage_attribution() == {}


def test_enabled_span_sits_in_the_profiler_trace(tmp_path):
    telemetry.enable()
    with jax.profiler.trace(str(tmp_path)):
        with span("photon.test.stage"):
            pass
    assert "photon.test.stage" in _names_in_profile(tmp_path)
    assert telemetry.stage_attribution()["photon.test.stage"]["count"] == 1


# -- the compile ledger ------------------------------------------------------------

def test_ledger_gains_the_block_once():
    compile_cache._listen()
    compile_cache.reset_compile_ledger()
    cd = _descent()
    cd.run(2)
    first = compile_cache.compile_ledger()
    row = first["functions"][scopes.CD_BLOCK]
    assert row["traces"] == 1 and row["lowerings"] == 1
    assert row["compiles"] == 1
    assert row["trace_s"] > 0 and row["lower_s"] > 0 and row["backend_s"] > 0
    # traced inside the block: in both rows
    assert first["functions"]["_solve_fixed"]["traces"] >= 1
    assert first["totals"]["trace_s"] >= row["trace_s"]
    cd.run(2)  # same shapes: nothing is traced, lowered or compiled
    assert compile_cache.compile_ledger()["functions"][scopes.CD_BLOCK] == row
    top = compile_cache.compile_ledger(top=1)["functions"]
    assert len(top) == 1


@pytest.mark.parametrize("reported, row", [
    ("cd_block", "cd_block"), ("jit(cd_block)", "cd_block"),
    ("pmap(step)", "step"), (None, "?"),
])
def test_ledger_names_one_row_per_function(reported, row):
    assert compile_cache._function_name(reported) == row


def test_ledger_claims_cache_retrieval_for_the_function():
    compile_cache.reset_compile_ledger()
    compile_cache._on_event("/jax/compilation_cache/compile_requests_use_cache")
    compile_cache._on_event("/jax/compilation_cache/cache_hits")
    compile_cache._on_duration(
        "/jax/compilation_cache/cache_retrieval_time_sec", 0.25)
    compile_cache._on_duration(
        "/jax/core/compile/backend_compile_duration", 0.5,
        fun_name="jit(cd_block)")
    compile_cache._on_duration("/jax/unrelated", 9.0, fun_name="x")
    ledger = compile_cache.compile_ledger()
    row = ledger["functions"]["cd_block"]
    assert (row["backend_s"], row["retrieval_s"], row["cache_hits"]) == (
        0.5, 0.25, 1)
    assert ledger["totals"]["cache_requests"] == 1
    assert ledger["totals"]["retrieval_s"] == 0.25
    assert "x" not in ledger["functions"]
    compile_cache.reset_compile_ledger()


# -- the work gauges ---------------------------------------------------------------

@pytest.mark.parametrize("interpret", [False, True])
def test_gauges_read_slots_rows_and_routing(monkeypatch, interpret):
    """Two buckets (12 users in 32- and 64-row slots), 400 true rows. Off
    the TPU every entity is on the vmapped fallback; with the kernel
    forced (interpret mode) every one is on the kernel."""
    if interpret:
        monkeypatch.setenv("PHOTON_ML_TPU_PALLAS_INTERPRET", "1")
    telemetry.enable()
    cd = _descent()
    gauges = telemetry.snapshot()["gauges"]
    coord = cd.coordinates["perUser"]
    routing = coord.routing()
    shapes = [b.x.shape for b in coord.dataset.blocks]
    assert [s[1] for s in shapes] == [32, 64]
    assert sum(s[0] for s in shapes) == 12
    assert [(b["rows"], b["entities"], b["slots"]) for b in routing] == [
        (r, e, e * r) for e, r, _ in shapes]
    assert gauges[scopes.GAUGE_RE_SLOTS] == sum(e * r for e, r, _ in shapes)
    assert gauges[scopes.GAUGE_RE_ROWS] == 400 < gauges[scopes.GAUGE_RE_SLOTS]
    kernel, fallback = (12, 0) if interpret else (0, 12)
    assert gauges[scopes.GAUGE_RE_KERNEL_ENTITIES] == kernel
    assert gauges[scopes.GAUGE_RE_FALLBACK_ENTITIES] == fallback
    assert {b["path"] for b in routing} == {
        "kernel" if interpret else "vmapped"}
    assert all((b["reason"] is None) == interpret for b in routing)


def test_gauges_are_not_computed_while_telemetry_is_off():
    _descent()
    assert telemetry.gauge(scopes.GAUGE_RE_SLOTS).calls == 0


def test_fallback_reasons_still_warn_once(monkeypatch):
    from photon_ml_tpu.algorithm import coordinates

    monkeypatch.setenv("PHOTON_ML_TPU_PALLAS_INTERPRET", "1")
    monkeypatch.setattr(coordinates, "_FALLBACK_WARNED", set())
    coord = build_coordinates(_data())["perUser"]
    x = jnp.zeros((128, 16384, 32), jnp.float32)
    assert not coordinates._use_pallas_entity_solver(
        coord._objective, coord.config, x)
    reason, loud = coordinates._kernel_refusal(
        coord._objective, coord.config, x)
    assert loud and "VMEM" in reason
    assert coordinates._FALLBACK_WARNED == {reason}


# -- dev_scripts/trace_scopes.py ---------------------------------------------------

_BLOCK = "jit(cd_block)/while/body/closed_call"


@pytest.mark.parametrize("path, leaf, coordinate, size_class", [
    (f"{_BLOCK}/photon.cd.perUser/jit(_solve_block)/photon.re.solve/r512/"
     "vmap(jit(_minimize))/while/body/dot_general",
     scopes.RE_SOLVE, "photon.cd.perUser", "r512"),
    (f"{_BLOCK}/photon.cd.perUser/jit(_solve_block)/photon.re.gather/gather",
     scopes.RE_GATHER, "photon.cd.perUser", None),
    (f"{_BLOCK}/photon.cd.fixed/jit(_solve_fixed)/photon.fe.solve/while",
     scopes.FE_SOLVE, "photon.cd.fixed", None),
    (f"{_BLOCK}/photon.cd.objective/reduce_sum",
     scopes.CD_OBJECTIVE, None, None),
    (f"{_BLOCK}/photon.cd.perUser/add", None, "photon.cd.perUser", None),
    ("jit(_re_score_impl)/photon.re.scatter/scatter-add",
     scopes.RE_SCATTER, None, None),
    ("jit(cd_block)/while", None, None, None),
    ("", None, None, None),
])
def test_place_of_an_operation(path, leaf, coordinate, size_class):
    where = scopes.place(path)
    assert (where["leaf"], where["coordinate"], where["size_class"]) == (
        leaf, coordinate, size_class)
    assert where["scoped"] == (leaf is not None or coordinate is not None)


@pytest.mark.parametrize("tail, product, part", [
    # a matvec whose matrix has coded slots (PR 36): each part is a row
    # under the product, which is a row under the scope that ran it
    ("photon.fe.matvec/photon.fe.matvec.coded/while/body/closed_call/"
     "checkpoint/while/body/select_n",
     "photon.fe.solve/photon.fe.matvec",
     "photon.fe.solve/photon.fe.matvec/photon.fe.matvec.coded"),
    ("photon.fe.matvec/photon.fe.matvec.gathered/while/body/closed_call/"
     "gather",
     "photon.fe.solve/photon.fe.matvec",
     "photon.fe.solve/photon.fe.matvec/photon.fe.matvec.gathered"),
    ("photon.fe.matvec/while/body/gather",
     "photon.fe.solve/photon.fe.matvec", None),
    ("photon.fe.rmatvec/while/body/scatter-add",
     "photon.fe.solve/photon.fe.rmatvec", None),
    # a part under no product is no part
    ("photon.fe.matvec.coded/select_n", None, None),
])
def test_place_of_a_sparse_product_and_its_parts(tail, product, part):
    where = scopes.place(
        f"{_BLOCK}/photon.cd.fixed/jit(_solve_fixed)/photon.fe.solve/{tail}")
    assert where["leaf"] == scopes.FE_SOLVE
    assert (where["product"], where["part"]) == (product, part)


@pytest.mark.parametrize("event, want", [
    ("%all-reduce.12 = f32[200]{0:T(256)} all-reduce(f32[200]{0} %dot.3), "
     "channel_id=3", True),
    ("%all-gather-start.2", True),
    ("%collective-permute-done.7 = f32[1]{0} collective-permute-done(...)",
     True),
    # written by the program: named after JAX's primitive (PR 32)
    ("%psum_invariant.16 = f32[20000265]{0:T(1024)S(1)} "
     "all-reduce(f32[20000265]{0:T(1024)} %dynamic_update_slice.4), "
     "channel_id=1", True),
    ("%all-reduce.147 = (f32[401]{0}, f32[], f32[]) all-reduce("
     "f32[401]{0} %wrapped_scatter.5, f32[] %a, f32[] %b)", True),
    ("%reduce_scatter.3 = f32[100]{0} reduce-scatter(f32[400]{0} %x)", True),
    ("%fusion.21 = f32[5983,32]{1,0} fusion(f32[20000265]{0} "
     "%psum_invariant.16), kind=kLoop", False),
    ("%get-tuple-element.9 = f32[401]{0} get-tuple-element((f32[401]{0}, "
     "f32[]) %all-reduce.147), index=0", False),
    ("%multiply_reduce_fusion.318", False),
])
def test_a_collective_is_known_by_name_or_by_opcode(event, want):
    assert scopes.is_collective(event) is want


def _hand_trace():
    """One job of 100 us by hand. The scan's ``while`` (no scope) holds
    everything from 10 to 90; scoped operations cover 10-30 (fixed effect,
    a ``while`` of its own with two body operations), 30-40 (gather),
    40-60 (kernel, r32), 60-70 (vmapped, r512), 70-75 (margins), 75-80
    (scatter), 82-86 (objective); 80-82 and 86-90 run under no scope (a
    copy), 0-10 and 90-100 are idle: a 10 us gap in ``prepare`` and one
    split between ``wait`` (6) and ``bench.settle`` (4)."""
    us = 1000
    fe = f"{_BLOCK}/photon.cd.fixed/jit(_solve_fixed)/photon.fe.solve"
    re = f"{_BLOCK}/photon.cd.perUser/jit(_solve_block)"
    sc = f"{_BLOCK}/photon.cd.perUser/jit(_re_score_impl)"
    ops = [
        ["%while.9 = (...) while(...)", 10 * us, 80 * us, "jit(cd_block)/while"],
        ["%while.1 = (...) while(...)", 10 * us, 20 * us, fe + "/while"],
        ["%fusion.1 = f32[8] fusion(...)", 11 * us, 8 * us,
         fe + "/while/body/dot_general"],
        ["%fusion.2", 20 * us, 9 * us, fe + "/while/body/dot_general"],
        ["%fusion.3", 30 * us, 10 * us, re + "/photon.re.gather/gather"],
        ["%pallas_entity_lbfgs.4 = (...) custom-call(...)", 40 * us, 20 * us,
         re + "/photon.re.solve/r32/jit(pallas_entity_lbfgs)/"
         "pallas_entity_lbfgs"],
        ["%fusion.5", 60 * us, 10 * us,
         re + "/photon.re.solve/r512/vmap(jit(_minimize))/while/body/add"],
        ["%fusion.6", 70 * us, 5 * us, sc + "/photon.re.margins/dot_general"],
        ["%fusion.7", 75 * us, 5 * us, sc + "/photon.re.scatter/scatter-add"],
        ["%copy.8", 80 * us, 2 * us, ""],
        ["%fusion.10", 82 * us, 4 * us,
         f"{_BLOCK}/photon.cd.objective/reduce_sum"],
        ["%copy.11", 86 * us, 4 * us, ""],
        ["%fusion.12", 150 * us, 10 * us, fe],  # after the job
    ]
    spans = [
        ["bench.job", 0, 100 * us, ""], ["bench.run", 0, 96 * us, ""],
        [scopes.CD_RUN, 1 * us, 95 * us, ""],
        [scopes.CD_PREPARE, 1 * us, 10 * us, ""],
        [scopes.CD_DISPATCH, 11 * us, 2 * us, ""],
        [scopes.CD_WAIT, 13 * us, 83 * us, ""],
        ["bench.settle", 96 * us, 4 * us, ""],
    ]
    return {"planes": [
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Modules", "events": [["jit_cd_block", 0, 1, ""]]},
            {"name": "XLA Ops", "events": ops}]},
        {"name": "/host:CPU", "lines": [
            {"name": "python3", "events": spans}]}]}


def test_reduce_scopes_by_hand():
    result = trace_scopes.reduce_scopes(_hand_trace(), gap_ms=0.005)
    (job,) = result["jobs"]
    ms = lambda us: pytest.approx(us / 1000)
    assert job["window_ms"] == ms(100) and job["busy_ms"] == ms(80)
    assert job["scope_ms"] == {
        scopes.FE_SOLVE: ms(20), scopes.FE_SCORE: 0.0,
        scopes.RE_GATHER: ms(10), scopes.RE_SOLVE: ms(30),
        scopes.RE_MARGINS: ms(5), scopes.RE_SCATTER: ms(5),
        scopes.CD_OBJECTIVE: ms(4)}
    assert job["exchange_ms"] == ms(20)
    assert job["size_class_ms"] == {
        "r32": {"ms": ms(20), "path": "kernel"},
        "r512": {"ms": ms(10), "path": "vmapped"}}
    assert job["coordinate_ms"] == {
        "photon.cd.fixed": ms(20), "photon.cd.perUser": ms(50)}
    assert job["unattributed_ms"] == ms(6)
    assert job["unattributed_share"] == pytest.approx(6 / 80)
    # where no scoped operation runs: the copies, inside the scan's while
    assert sorted(job["unattributed_ops"]) == sorted(
        [["%copy.11", ms(4)], ["%copy.8", ms(2)], ["%while.9", ms(6)]])
    gaps = [(g["ms"], g["phase"]) for g in job["idle_gaps"]]
    assert gaps == [(ms(10), scopes.CD_PREPARE), (ms(10), scopes.CD_WAIT)]
    assert result["mean"]["scope_ms"] == job["scope_ms"]


def test_reduce_scopes_over_several_chips_and_their_collectives():
    """A second chip's plane, with collectives inside the scopes: every
    number is the mean over the chips, a scope's collective time is the
    union of its collective operations (``-start`` and ``-done`` both),
    and the busy time is given chip by chip."""
    us = 1000
    trace = _hand_trace()
    one = trace["planes"][0]
    fe = f"{_BLOCK}/photon.cd.fixed/jit(_solve_fixed)/photon.fe.solve"
    sc = f"{_BLOCK}/photon.cd.perUser/jit(_re_score_impl)/photon.re.scatter"
    two_ops = [
        ["%while.1", 10 * us, 30 * us, fe + "/while"],
        ["%all-reduce.3 = f32[200] all-reduce(...)", 12 * us, 4 * us,
         fe + "/while/body/dot_general"],
        ["%all-reduce.3 = f32[200] all-reduce(...)", 14 * us, 4 * us,
         fe + "/while/body/dot_general"],       # overlaps: a union
        ["%all-gather-start.5", 40 * us, 1 * us, sc + "/scatter-add"],
        ["%all-gather-done.5", 44 * us, 2 * us, sc + "/scatter-add"],
        ["%fusion.7", 46 * us, 4 * us, sc + "/scatter-add"],
        ["%collective-permute.2", 60 * us, 5 * us, ""],
        ["%gather_all-reduce.fusion", 70 * us, 5 * us, ""],  # no collective
    ]
    trace["planes"].insert(1, {"name": "/device:TPU:1", "lines": [
        {"name": "XLA Ops", "events": two_ops}]})
    result = trace_scopes.reduce_scopes(trace, gap_ms=0.005)
    (job,) = result["jobs"]
    ms = lambda us: pytest.approx(us / 1000)
    alone = trace_scopes.reduce_scopes(
        {"planes": [one, trace["planes"][2]]}, gap_ms=0.005)["jobs"][0]
    assert alone["collective_ms"] == {} and alone["collectives_ms"] == 0
    assert alone["chip_busy_ms"] == [ms(80)]
    # chip two: fe.solve 10-40, scatter 40-41 + 44-50, 10 more unscoped
    assert job["chip_busy_ms"] == [ms(80), ms(47)]
    assert job["busy_ms"] == ms((80 + 47) / 2)
    assert job["scope_ms"][scopes.FE_SOLVE] == ms((20 + 30) / 2)
    assert job["scope_ms"][scopes.RE_SCATTER] == ms((5 + 7) / 2)
    assert job["collective_ms"] == {
        scopes.FE_SOLVE: ms(6 / 2), scopes.RE_SCATTER: ms(3 / 2),
        trace_scopes.NO_SCOPE: ms(5 / 2)}
    assert job["collectives_ms"] == ms(14 / 2)
    assert result["mean"]["chip_busy_ms"] == job["chip_busy_ms"]
    # the gaps listed are the first chip's
    assert [g["phase"] for g in job["idle_gaps"]] == [
        g["phase"] for g in alone["idle_gaps"]]
    import io

    buf = io.StringIO()
    trace_scopes.print_report(result, out=buf)
    out = buf.getvalue()
    assert "of it collectives, ms" in out
    assert f"| `{scopes.FE_SOLVE}` | 25.000" not in out  # ms, not us
    assert "device busy by chip, ms: 0.080, 0.047" in out


def test_pack_round_trips_and_cut_keeps_one_job():
    trace = _hand_trace()
    host = trace["planes"][1]["lines"][0]["events"]
    host.append(["bench.job", 140_000, 30_000, ""])
    packed = trace_scopes.pack(trace)
    assert trace_scopes.unpack(json.loads(json.dumps(packed))) == trace
    cut = trace_scopes.cut_jobs(trace, 1)
    assert len(trace_scopes.job_spans(cut)) == 1
    assert len(trace_scopes.reduce_scopes(cut)["jobs"]) == 1
    assert len(trace_scopes.reduce_scopes(trace)["jobs"]) == 2


def test_reduce_scopes_needs_a_device_plane_and_a_job():
    with pytest.raises(ValueError, match="no device plane"):
        trace_scopes.reduce_scopes({"planes": [
            {"name": "/host:CPU", "lines": []}]})
    trace = _hand_trace()
    trace["planes"][1]["lines"][0]["events"] = []
    with pytest.raises(ValueError, match="bench.job"):
        trace_scopes.reduce_scopes(trace)


def test_reduce_scopes_on_the_recorded_chip_trace():
    """One traced job of ``glmix.fit`` on the TPU v5e (PR 29): every table
    scope has device time, the ten size classes split four on the kernel
    and six on the fallback, under 5% of the busy time is outside every
    scope, and every idle gap belongs to a phase of the fit."""
    result = trace_scopes.reduce_scopes(json.loads(RECORDED.read_text()))
    (job,) = result["jobs"]
    assert job["busy_ms"] == pytest.approx(433.438202)
    assert job["window_ms"] == pytest.approx(447.67413)
    assert all(job["scope_ms"][s] > 0 for s in scopes.DEVICE_SCOPES)
    assert job["scope_ms"][scopes.FE_SOLVE] == pytest.approx(146.542096)
    assert job["exchange_ms"] == pytest.approx(231.44584)
    assert job["exchange_ms"] == pytest.approx(
        sum(job["scope_ms"][s] for s in scopes.EXCHANGE_SCOPES))
    paths = {c: v["path"] for c, v in job["size_class_ms"].items()}
    assert paths == {**dict.fromkeys(("r32", "r64", "r128", "r256"), "kernel"),
                     **dict.fromkeys(("r512", "r1024", "r2048", "r4096",
                                      "r8192", "r16384"), "vmapped")}
    assert sum(v["ms"] for v in job["size_class_ms"].values()) == \
        pytest.approx(job["scope_ms"][scopes.RE_SOLVE], rel=1e-3)
    assert set(job["coordinate_ms"]) == {"photon.cd.fixed",
                                         "photon.cd.perUser"}
    assert 0 < job["unattributed_share"] < 0.05
    # what the scopes cover and what they leave is the whole busy time
    assert sum(job["scope_ms"].values()) + job["unattributed_ms"] == \
        pytest.approx(job["busy_ms"], rel=2e-3)
    gaps = job["idle_gaps"]
    assert len(gaps) == 14 and max(g["ms"] for g in gaps) < 3.1
    assert {g["phase"] for g in gaps} == {
        scopes.CD_RUN, scopes.CD_PREPARE, scopes.CD_WAIT}


RECORDED_COLD = RECORDED.with_name("trace_glmix_fit_cold.json")


@pytest.mark.parametrize("recorded, before", [
    # PR 29's tree: every start paid the eager initial-scores pass
    (RECORDED, {scopes.FE_SCORE: 6.352, scopes.RE_MARGINS: 1.501,
                scopes.RE_SCATTER: 84.229}),
    # PR 30's: a cold start builds its zero scores
    (RECORDED_COLD, {}),
])
def test_reduction_says_what_ran_before_the_block(recorded, before):
    """One job of ``glmix.fit`` as the chip ran it, before and after cold
    starts stopped scoring zero models: the scoring scopes' time ahead of
    ``cd_block``'s first operation, and nothing else, is what went."""
    (job,) = trace_scopes.reduce_scopes(
        json.loads(recorded.read_text()))["jobs"]
    early = {s: ms for s, ms in job["before_block_ms"].items() if ms}
    assert early == pytest.approx(before, abs=1e-3)
    in_block = {s: job["scope_ms"][s] - job["before_block_ms"][s]
                for s in scopes.DEVICE_SCOPES}
    assert in_block[scopes.RE_SCATTER] == pytest.approx(84.6, abs=0.3)
    assert in_block[scopes.FE_SCORE] == pytest.approx(6.35, abs=0.05)
    assert in_block[scopes.FE_SOLVE] == pytest.approx(146.54, abs=0.1)


# A hand-encoded XSpace: protobuf wire format, as the profiler writes it.

def _varint(n: int) -> bytes:
    out = bytearray()
    while True:
        n, low = n >> 7, n & 0x7F
        out.append(low | (0x80 if n else 0))
        if not n:
            return bytes(out)


def _field(number: int, value) -> bytes:
    if isinstance(value, int):
        return _varint(number << 3) + _varint(value)
    if isinstance(value, str):
        value = value.encode()
    return _varint(number << 3 | 2) + _varint(len(value)) + value


def _entry(key: int, message: bytes) -> bytes:
    return _field(1, key) + _field(2, message)


def test_read_xspace_keeps_the_metadata_stats(tmp_path):
    """The scope path is a stat of the event's METADATA (``tf_op``), the
    device's times are the event's own; an event starts at its line's
    timestamp plus its offset."""
    tf_op = "jit(cd_block)/while/body/photon.cd.fixed/photon.fe.solve/dot:"
    metadata = (_field(1, 7) + _field(2, "%fusion.7 = f32[8] fusion(...)")
                + _field(5, _field(1, 1) + _field(5, tf_op))    # str stat
                + _field(5, _field(1, 2) + _field(4, 1234))     # int64 stat
                + _field(5, _field(1, 4) + _field(7, 3)))       # ref stat
    event = (_field(1, 7) + _field(2, 5_000_000) + _field(3, 2_000_000)
             + _field(4, _field(1, 3) + _field(3, 99)))         # uint64 stat
    device = (_field(2, "/device:TPU:0")
              + _field(3, _field(2, "XLA Ops") + _field(3, 1000)
                       + _field(4, event))
              + _field(4, _entry(7, metadata))
              + _field(5, _entry(1, _field(1, 1) + _field(2, "tf_op")))
              + _field(5, _entry(2, _field(1, 2) + _field(2, "flops")))
              + _field(5, _entry(3, _field(1, 3)
                                 + _field(2, "device_offset_ps")))
              + _field(5, _entry(4, _field(1, 4) + _field(2, "category"))))
    host = (_field(2, "/host:CPU")
            + _field(3, _field(2, "python") + _field(3, 0)
                     + _field(4, _field(1, 1) + _field(2, 0)
                              + _field(3, 9_000_000))
                     + _field(4, _field(1, 2) + _field(2, 0)
                              + _field(3, 1_000_000)))
            + _field(4, _entry(1, _field(1, 1) + _field(2, "bench.job")))
            + _field(4, _entry(2, _field(1, 2) + _field(2, "PjitFunction"))))
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(_field(1, device) + _field(1, host)
                     + _field(4, "hostname"))
    planes = trace_scopes.read_xspace(path)
    assert [p["name"] for p in planes] == ["/device:TPU:0", "/host:CPU"]
    (ev,) = planes[0]["lines"][0]["events"]
    assert ev["name"].startswith("%fusion.7")
    assert (ev["start_ns"], ev["duration_ns"]) == (1000 + 5000, 2000)
    assert ev["stats"] == {"device_offset_ps": 99}
    assert ev["metadata_stats"] == {"tf_op": tf_op, "flops": 1234,
                                    "category": "device_offset_ps"}
    flat = trace_scopes.flatten_with_paths(planes)
    assert flat["planes"][0]["lines"][0]["events"] == [
        [ev["name"], 6000, 2000, tf_op.rstrip(":")]]
    # of the host's events only the photon.* and bench.* spans are kept
    assert flat["planes"][1]["lines"][0]["events"] == [
        ["bench.job", 0, 9000, ""]]
    assert scopes.place(tf_op.rstrip(":"))["leaf"] == scopes.FE_SOLVE


def test_run_puts_no_frame_of_its_own_above_the_solvers(monkeypatch):
    """JAX captures a traceback at every traced equation, so a helper
    frame between ``run`` and the solvers is paid for at each one (+5 s of
    a 32 s warm-up on the chip's host for ONE frame, PERF.md PR 29): the
    repo's own frames from ``run`` down to a coordinate's update are the
    block's and nothing else."""
    import traceback

    from photon_ml_tpu.algorithm import coordinates

    seen = []
    real = coordinates.FixedEffectCoordinate.pure_update

    def spy(self, *args):
        if not seen:
            seen.append([f.name for f in traceback.extract_stack()
                         if "/photon_ml_tpu/" in f.filename])
        return real(self, *args)

    monkeypatch.setattr(coordinates.FixedEffectCoordinate, "pure_update", spy)
    _descent().run(1)
    assert seen[0] == ["run", scopes.CD_BLOCK, "one_iteration"]


@pytest.mark.parametrize("stats, path", [
    ({"tf_op": "jit(cd_block)/photon.fe.solve/dot:", "flops": 3},
     "jit(cd_block)/photon.fe.solve/dot"),
    ({"hlo_op": "fusion.3", "some_new_stat": "a/photon.re.gather/b"},
     "a/photon.re.gather/b"),
    ({"hlo_op": "fusion.3", "flops": 12}, ""),
])
def test_path_of_an_event(stats, path):
    assert trace_scopes.path_of(stats) == path
