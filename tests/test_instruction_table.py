"""The block says which scope each of its compiled instructions belongs to
(PR 40): ``CoordinateDescent.run`` publishes, at a block function's first
dispatch, the instruction table of the executable that runs
(``utils.compile_cache.note_instructions`` / ``instruction_scopes``), strings
only; ``telemetry.scopes.place`` resolves a path; ``utils.profiling
.maybe_trace`` writes the table beside a profile; and
``benchmark/scope_seconds.py`` joins it with per-operation seconds, held
here against the two chip traces recorded in ``tests/data``. All on the CPU
at a tiny size: names and counts, and the chip's recorded times."""

import gc
import json
import weakref
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import scope_seconds, trace_reduce
from dev_scripts import trace_scopes
from photon_ml_tpu.algorithm import coordinate_descent
from photon_ml_tpu.telemetry import scopes
from photon_ml_tpu.utils import compile_cache, profiling
from tests.test_fit_tracing import BLOCK_SCOPES, _data, _descent

DATA = Path(__file__).parent / "data"


@pytest.fixture(scope="module")
def fitted():
    """Two runs of one tiny fit, the calls of ``note_instructions``
    counted, the ledger and the table as they stood after each."""
    compile_cache._listen()
    compile_cache.reset_compile_ledger()
    calls = []
    real = coordinate_descent.note_instructions

    def counting(fun_name, compiled, **kw):
        calls.append(fun_name)
        return real(fun_name, compiled, **kw)

    coordinate_descent.note_instructions = counting
    try:
        cd = _descent()
        first = cd.run(2, seed=3)
        after_first = (dict(compile_cache.instruction_scopes()),
                       compile_cache.compile_ledger()["functions"]
                       [scopes.CD_BLOCK], len(calls))
        second = cd.run(2, seed=3)
        after_second = (dict(compile_cache.instruction_scopes()),
                        compile_cache.compile_ledger()["functions"]
                        [scopes.CD_BLOCK], len(calls))
    finally:
        coordinate_descent.note_instructions = real
    return {"cd": cd, "results": (first, second),
            "after_first": after_first, "after_second": after_second,
            "opcodes": compile_cache.instruction_opcodes()}


def test_table_is_there_after_the_first_run(fitted):
    table, row, calls = fitted["after_first"]
    assert calls == 1 and len(table) > 100
    assert all(isinstance(k, str) and isinstance(v, str)
               for k, v in table.items())
    assert not any(name.startswith("%") for name in table)
    assert row["instructions"] == len(table)
    assert 0 < row["scoped_instructions"] < row["instructions"]
    assert row["scoped_instructions"] == sum(
        scopes.PREFIX in path for path in table.values())
    assert row["instructions_s"] > 0
    assert set(fitted["opcodes"]) == set(table)


@pytest.mark.parametrize("scope", BLOCK_SCOPES)
def test_every_block_scope_is_some_instructions_place(fitted, scope):
    """Leaf scopes as a leaf, ``photon.cd.<coordinate>`` as the coordinate,
    the size classes as a class: through ``place``, as a reader would."""
    places = [scopes.place(p) for p in fitted["after_first"][0].values()]
    assert any(scope in (w["leaf"], w["coordinate"], w["size_class"])
               for w in places), scope


def test_table_size_classes_sit_under_re_solve(fitted):
    places = [scopes.place(p) for p in fitted["after_first"][0].values()]
    classes = {w["size_class"] for w in places if w["size_class"]}
    assert classes == {"r32", "r64"}
    assert all(w["leaf"] == scopes.RE_SOLVE for w in places
               if w["size_class"])


def test_table_holds_what_can_show_as_a_device_event(fitted):
    """The scan's ``while`` and its body's operations are there with their
    opcodes; the insides of fused computations are not."""
    opcodes = fitted["opcodes"]
    assert "while" in opcodes.values() and "fusion" in opcodes.values()
    assert "parameter" in opcodes.values()  # the entry's own
    assert not any(name.startswith("param_") for name in opcodes)


def test_a_second_run_does_no_work_for_the_table(fitted):
    assert fitted["after_second"] == fitted["after_first"]
    assert fitted["cd"]._table_due == set()


def test_the_lookup_is_no_retrace(fitted):
    """JAX fires one zero-length trace event for ``fn.lower`` with the
    call's own arguments: the ledger and the guard still say traced once."""
    row = fitted["after_second"][1]
    assert (row["traces"], row["lowerings"], row["compiles"]) == (1, 1, 1)
    fitted["cd"].tracing_guard.assert_max_retraces(per_fn=1)
    assert fitted["cd"].tracing_guard.counts() == {"block:2": 1}


def test_result_is_bitwise_what_it_was_without_the_table(monkeypatch):
    data = _data()
    with_table = _descent(data).run(2, seed=3)
    monkeypatch.setattr(coordinate_descent.CoordinateDescent,
                        "_publish_table", lambda self, fn, args: None)
    compile_cache.reset_compile_ledger()
    without = _descent(data).run(2, seed=3)
    assert compile_cache.instruction_scopes() == {}
    assert with_table.objective_history == without.objective_history
    np.testing.assert_array_equal(
        np.asarray(with_table.model.get_model("fixed").glm.coefficients.means),
        np.asarray(without.model.get_model("fixed").glm.coefficients.means))
    for a, b in zip(with_table.model.get_model("perUser").local_coefs,
                    without.model.get_model("perUser").local_coefs):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_nothing_of_the_fit_stays_alive_through_the_table():
    cd = _descent()
    result = cd.run(1)
    fn = weakref.ref(cd._fused_block_fn(1))
    alive = weakref.ref(cd)
    assert compile_cache.instruction_scopes()
    del cd, result
    gc.collect()
    assert alive() is None and fn() is None
    assert compile_cache.instruction_scopes()  # strings: they stay


def test_reset_clears_the_table():
    _descent().run(1)
    assert compile_cache.instruction_scopes()
    compile_cache.reset_compile_ledger()
    assert compile_cache.instruction_scopes() == {}
    assert compile_cache.instruction_opcodes() == {}
    assert compile_cache.instruction_scopes("no_such_function") == {}


def test_the_table_is_of_the_block_dispatched_last(tmp_path):
    """A checkpointed fit whose saves fall inside an iteration runs the
    block over spans of one coordinate: each span function publishes once,
    and the table is the last one's."""
    compile_cache.reset_compile_ledger()
    cd = _descent()
    cd.run(1, checkpoint_dir=tmp_path, checkpoint_interval=1)
    coordinates = {scopes.place(p)["coordinate"]
                   for p in compile_cache.instruction_scopes().values()}
    assert coordinates == {None, scopes.cd_coordinate("perUser")}
    assert cd._table_due == set()
    cd.tracing_guard.assert_max_retraces(per_fn=1)


# -- the text's parse ------------------------------------------------------------

# a block's optimized HLO text in small, by hand: a scan's ``while`` with a
# fusion, two all-reduces (one named by JAX), a kernel's custom-call whose
# ``backend_config`` must never be read, a conditional; a fused computation's
# insides, a reducer and a computation nothing calls
_HLO = (DATA / "hlo_instruction_table.txt").read_text()


def test_parse_keeps_event_instructions_with_opcode_and_path():
    table = compile_cache.parse_instructions(_HLO)
    assert table["fusion.7"] == (
        "fusion",
        "jit(cd_block)/while/body/photon.cd.fixed/photon.fe.solve/neg")
    assert table["while.9"] == ("while", "jit(cd_block)/while")
    assert table["x"] == ("parameter", "x")
    assert table["tuple.1"] == ("tuple", "")
    # a tuple-shaped result: the opcode is what follows the whole shape;
    # no path of its own: the path of the ``while`` whose body it sits in
    assert table["all-reduce.4"] == ("all-reduce", "jit(cd_block)/while")
    assert table["tuple.8"][0] == "tuple" and table["out.1"][0] == (
        "get-tuple-element")
    # named after JAX's primitive: the opcode says what it is
    assert table["psum_invariant.2"][0] == "all-reduce"
    # the kernel's call: its own metadata, nothing from behind it
    assert table["pallas_entity_lbfgs.5"] == (
        "custom-call",
        "jit(cd_block)/while/body/photon.re.solve/r32/pallas_call")
    # the while's condition and both branches of the conditional run as
    # events of their own
    assert table["lt.1"] == ("constant", "jit(cd_block)/while/cond/lt")
    assert table["in_a.1"][0] == "copy" and "in_b.1" in table
    assert scopes.place(table["in_a.1"][1])["leaf"] == scopes.RE_GATHER


@pytest.mark.parametrize("name", [
    "inside.1", "param_0",   # the insides of a fused computation
    "add.9", "a",            # a reducer
    "z",                     # a computation nothing reaches
])
def test_parse_leaves_out_what_never_shows_as_an_event(name):
    assert name not in compile_cache.parse_instructions(_HLO)


def test_an_instruction_without_a_path_takes_its_callers():
    """The compiler's own copies into the fast memory space carry no
    metadata; the chip's trace gives them the path of the loop they run in
    (looked at on the v5e, PR 40: ``copy-done.5`` under the L-BFGS ``while``
    of ``photon.fe.solve``, 1.8 ms a ``glmix.fit`` job in all), and so
    does the table."""
    solve = ("jit(cd_block)/while/body/closed_call/photon.cd.fixed/"
             "jit(_solve_fixed)/photon.fe.solve/jit(_minimize)/while")
    text = (DATA / "hlo_pathless_copies.txt").read_text().replace(
        "@SOLVE@", solve)
    table = compile_cache.parse_instructions(text)
    assert table["copy-done.1"] == ("copy-done", solve)
    assert table["copy-start.1"][1] == solve and table["lt.2"][1] == solve
    assert table["fusion.3"][1] == solve + "/body/dot_general"
    assert table["copy.9"] == ("copy", "")  # the entry's: nobody's
    assert scopes.place(table["copy-done.1"][1])["leaf"] == scopes.FE_SOLVE


def test_parse_of_nothing_is_nothing():
    assert compile_cache.parse_instructions("") == {}
    assert compile_cache.parse_instructions("HloModule m\n\n") == {}


def test_a_collective_is_known_by_the_tables_opcode():
    table = compile_cache.parse_instructions(_HLO)
    collectives = {name for name, (opcode, _) in table.items()
                   if opcode.startswith(scopes.COLLECTIVE_PREFIXES)}
    assert collectives == {"psum_invariant.2", "all-reduce.4"}


class _Text:
    def __init__(self, text):
        self.text = text

    def as_text(self):
        return self.text


def test_note_instructions_keeps_strings_and_counts():
    compile_cache.reset_compile_ledger()
    compile_cache._on_duration(
        "/jax/core/compile/backend_compile_duration", 0.5,
        fun_name="jit(cd_block)")
    compiled = _Text(_HLO)
    alive = weakref.ref(compiled)
    compile_cache.note_instructions(scopes.CD_BLOCK, compiled)
    del compiled
    gc.collect()
    assert alive() is None
    table = compile_cache.instruction_scopes()
    assert table["fusion.7"].endswith("photon.fe.solve/neg")
    assert compile_cache.instruction_opcodes()["cond.6"] == "conditional"
    row = compile_cache.compile_ledger()["functions"][scopes.CD_BLOCK]
    assert (row["instructions"], row["scoped_instructions"]) == (
        len(table), sum(scopes.PREFIX in p for p in table.values()))
    assert row["scoped_instructions"] == 4
    # the accessor hands out a copy
    table.clear()
    assert compile_cache.instruction_scopes()
    compile_cache.reset_compile_ledger()


def test_dispatched_executable_hides_the_lookup_and_shows_a_retrace():
    compile_cache._listen()
    compile_cache.reset_compile_ledger()

    @jax.jit
    def toy_block(x):
        with jax.named_scope(scopes.FE_SOLVE):
            return jnp.tanh(x) * 2.0

    x = jnp.ones(8)
    toy_block(x).block_until_ready()
    row = compile_cache.compile_ledger()["functions"]["toy_block"]
    totals = compile_cache.compile_ledger()["totals"]
    compiled = compile_cache.dispatched_executable("toy_block", toy_block,
                                                   (x,))
    assert compile_cache.compile_ledger()["functions"]["toy_block"] == row
    assert compile_cache.compile_ledger()["totals"]["trace_s"] == (
        pytest.approx(totals["trace_s"]))
    assert toy_block._cache_size() == 1
    assert scopes.FE_SOLVE in compiled.as_text()
    # arguments that are not the call's: a real retrace, and it shows
    compile_cache.dispatched_executable("toy_block", toy_block,
                                        (jnp.ones(16),))
    after = compile_cache.compile_ledger()["functions"]["toy_block"]
    assert (after["traces"], after["lowerings"]) == (2, 2)
    compile_cache.reset_compile_ledger()


# -- the operator's file -----------------------------------------------------------

def test_maybe_trace_writes_the_table_beside_the_profile(tmp_path):
    cd = _descent()
    with profiling.maybe_trace(str(tmp_path)):
        cd.run(1)
    (written,) = tmp_path.rglob(profiling.INSTRUCTION_SCOPES_FILE)
    assert list(written.parent.glob("*.xplane.pb"))  # the trace's own folder
    doc = json.loads(written.read_text())
    assert doc["function"] == scopes.CD_BLOCK
    assert doc["scopes"] == compile_cache.instruction_scopes()
    assert set(doc["opcodes"]) == set(doc["scopes"])
    assert any(scopes.place(p)["leaf"] == scopes.RE_SCATTER
               for p in doc["scopes"].values())


def test_maybe_trace_without_a_block_or_a_directory_writes_nothing(tmp_path):
    compile_cache.reset_compile_ledger()
    with profiling.maybe_trace(None):
        pass
    assert profiling.write_instruction_scopes(tmp_path) is None
    assert list(tmp_path.iterdir()) == []


def test_the_script_takes_place_and_is_collective_from_the_program():
    assert trace_scopes.place is scopes.place
    assert trace_scopes.is_collective is scopes.is_collective
    assert trace_scopes.LEAF_SCOPES == scopes.LEAF_SCOPES == (
        scopes.DEVICE_SCOPES + scopes.MF_SCOPES)


# -- the join, against what the chip recorded --------------------------------------

def _without_paths(trace: dict) -> dict:
    """The flattened form ``benchmark/trace_reduce.py`` reads: events of
    three fields, the scope path dropped."""
    return {"planes": [
        {"name": p["name"], "lines": [
            {"name": ln["name"], "events": [e[:3] for e in ln["events"]]}
            for ln in p["lines"]]} for p in trace["planes"]]}


def _as_the_benchmark_would_see(recorded: Path):
    """From a recorded trace of one ``glmix.fit`` job (events with their
    paths): the table the program WOULD have published (name -> path of the
    ``cd_block`` events; the block's operations that carry no path are in
    the real table too, under ``""``) and ``op_seconds`` as
    ``trace_reduce.reduce`` sums them (by short name, containers out)."""
    trace = trace_scopes.unpack(json.loads(recorded.read_text()))
    (plane,) = trace_reduce.device_planes(trace)
    ((lo, hi, _),) = trace_scopes.job_spans(trace)
    reduced = trace_reduce.reduce(_without_paths(trace))
    table, paths = {}, {}
    for name, s, d, path in trace_reduce.op_events(plane):
        if s + d <= lo or s >= hi:
            continue
        name = trace_reduce.short_name(name).lstrip("%")
        paths.setdefault(name, set()).add(path)
        if trace_scopes.in_block(path):
            table[name] = path
    for name, seen in paths.items():
        if seen == {""}:
            table[name] = ""
    ctx = {"trace": reduced, "instruction_scopes": table}
    return ctx, paths, trace_scopes.reduce_scopes(trace)["mean"]


def test_join_equals_the_scripts_union_on_the_cold_chip_trace():
    """A cold start, as every benchmark job is: no instruction name carries
    two paths, so the SUM by name is the script's UNION of intervals, scope
    by scope, to 0.01 ms."""
    ctx, paths, script = _as_the_benchmark_would_see(
        DATA / "trace_glmix_fit_cold.json")
    assert not [n for n, seen in paths.items() if len(seen) > 1]
    assert ctx["trace"]["traced_jobs"] == 1
    got = scope_seconds.by_scope(ctx)
    assert got["coverage"] == pytest.approx(1.0)
    for scope in scopes.DEVICE_SCOPES:
        assert got["leaf"].get(scope, 0.0) == pytest.approx(
            script["scope_ms"][scope], abs=0.01), scope
    assert set(got["leaf"]) == set(scopes.DEVICE_SCOPES)
    assert got["kernel"] == {scopes.RE_SOLVE: pytest.approx(
        1e3 * trace_reduce.op_sum(ctx["trace"], "%" + scopes.KERNEL))}
    for coordinate, ms in script["coordinate_ms"].items():
        assert got["coordinate"][coordinate] == pytest.approx(ms, abs=0.01)
    # the script's remainder also holds the scan's own gaps between its
    # body's operations (busy less the union of what is scoped)
    assert got["unscoped"] == pytest.approx(1.867, abs=0.01)
    assert script["unattributed_ms"] - got["unscoped"] == pytest.approx(
        script["busy_ms"] - got["total"], abs=0.01)
    assert sum(got["leaf"].values()) + got["unscoped"] == pytest.approx(
        got["total"])
    assert got["total"] == pytest.approx(341.675, abs=0.01)


@pytest.mark.parametrize("metric, expected", [
    ("exchange_ms", 145.715), ("fe_solve_job_ms", 146.539),
    ("re_solve_job_ms", 41.081), ("fe_score_ms", 6.474),
    ("unscoped_ms", 1.867),
    ("mf_solve_job_ms", None), ("mf_kernel_ms", None),
    ("fe_matvec_job_ms", None), ("fe_rmatvec_job_ms", None),
])
def test_the_nine_readers_on_the_cold_chip_trace(metric, expected):
    import importlib

    ctx, _, _ = _as_the_benchmark_would_see(DATA / "trace_glmix_fit_cold.json")
    got = importlib.import_module(f"benchmark.metrics.{metric}").read(ctx)
    assert got == (None if expected is None
                   else pytest.approx(expected, abs=0.01))


def test_join_says_nothing_of_a_warm_start_and_where_its_names_would_land():
    """A warm start ran two eager scoring programs in the job beside
    ``cd_block`` (PR 29's tree): a fifth of the device time is under names
    the block's table does not hold, so the helper says NOTHING. Asked all
    the same (``floor=0``), it puts another program's operations under
    ``unscoped``, except the 22 of the 23 two-path names that the block
    shares with them, which land in the BLOCK's scope of that name: 8.72 ms
    of the eager way back under ``photon.re.scatter`` (rightly named,
    wrongly the block's), 0.89 ms of eager margins under
    ``photon.re.solve``."""
    ctx, paths, script = _as_the_benchmark_would_see(
        DATA / "trace_glmix_fit_scopes.json")
    two = [n for n, seen in paths.items() if len(seen) > 1]
    assert len(two) == 23
    assert sum(n in ctx["instruction_scopes"] for n in two) == 22
    assert scope_seconds.by_scope(ctx) is None
    got = scope_seconds.by_scope(ctx, floor=0.0)
    assert got["coverage"] == pytest.approx(0.81, abs=0.005)
    in_block = {s: script["scope_ms"][s] - script["before_block_ms"][s]
                for s in scopes.DEVICE_SCOPES}
    over = {s: got["leaf"][s] - in_block[s] for s in scopes.DEVICE_SCOPES}
    assert over[scopes.RE_SCATTER] == pytest.approx(8.724, abs=0.01)
    assert over[scopes.RE_SOLVE] == pytest.approx(0.890, abs=0.01)
    for scope in (scopes.FE_SOLVE, scopes.FE_SCORE, scopes.RE_GATHER,
                  scopes.RE_MARGINS, scopes.CD_OBJECTIVE):
        assert over[scope] == pytest.approx(0.0, abs=0.01), scope
    # the rest of the eager pass: nobody's
    eager = sum(script["before_block_ms"].values())
    assert got["unscoped"] == pytest.approx(
        eager - 8.724 - 0.890 + 1.946, abs=0.05)


def test_the_scripts_join_reads_the_trace_as_the_benchmark_does(
        tmp_path, monkeypatch):
    """``dev_scripts/trace_scopes.py --join``: the cell's table-reading
    metrics through the benchmark's own reduction and readers, beside the
    script's number for the same scope, from one trace."""
    import io

    recorded = DATA / "trace_glmix_fit_cold.json"
    ctx, _, script = _as_the_benchmark_would_see(recorded)
    trace = trace_scopes.unpack(json.loads(recorded.read_text()))
    flat = tmp_path / "flat.json"  # what trace_reduce.load reads: no paths
    flat.write_text(json.dumps(_without_paths(trace)))
    monkeypatch.setitem(compile_cache._instructions, scopes.CD_BLOCK, {
        "scopes": ctx["instruction_scopes"], "opcodes": {}, "scoped": 0,
        "seconds": 0.0})
    out = io.StringIO()
    joined = trace_scopes.join(flat, "glmix.fit", script, out=out)
    assert set(joined["metrics"]) == {
        "exchange_ms", "fe_solve_job_ms", "re_solve_job_ms", "fe_score_ms",
        "unscoped_ms"}
    assert joined["metrics"]["exchange_ms"] == pytest.approx(
        script["exchange_ms"], abs=0.01)
    assert joined["by_scope"]["coverage"] == pytest.approx(1.0)
    text = out.getvalue()
    assert "| `exchange_ms` | 145.715 | 145.715 |" in text
    assert "| `fe_solve_job_ms` | 146.539 | 146.539 |" in text
    assert "coverage 100.000%" in text
    # a cell without random effects reads other rows, and nothing where the
    # trace holds no such operation
    sparse = trace_scopes.join(flat, "sparse-lr.fit", {
        **script, "product_ms": {}}, out=io.StringIO())
    assert set(sparse["metrics"]) == {
        "fe_solve_job_ms", "fe_matvec_job_ms", "fe_rmatvec_job_ms",
        "fe_score_ms", "unscoped_ms"}
    assert sparse["metrics"]["fe_matvec_job_ms"] is None
