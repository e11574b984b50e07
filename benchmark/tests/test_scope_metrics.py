"""The nine readers of the block's instruction table (PR 40), on hand-made
``ctx``s: per-operation seconds by instruction name, a table from name to
``op_name`` path, and what each metric sums; and that ``BENCHMARK.json``'s
nine entries name files and cells that exist."""

import importlib
import json
from pathlib import Path

import pytest

from benchmark import scope_seconds
from photon_ml_tpu.telemetry import scopes

# over a program from before the table (this PR's parent) every reader says
# nothing: only the tests of that hold there
needs_table = pytest.mark.skipif(
    not hasattr(scopes, "place"),
    reason="the program publishes no instruction table")

ROOT = Path(__file__).resolve().parents[2]
METRICS = ("exchange_ms", "fe_solve_job_ms", "re_solve_job_ms",
           "mf_solve_job_ms", "mf_kernel_ms", "fe_matvec_job_ms",
           "fe_rmatvec_job_ms", "fe_score_ms", "unscoped_ms")

_B = "jit(cd_block)/while/body/closed_call"
_FE = f"{_B}/photon.cd.fixed/jit(_solve_fixed)/photon.fe.solve"
_USER = f"{_B}/photon.cd.perUser"
_MF = f"{_B}/photon.cd.perMovieMF"
# name -> (path, seconds over two traced jobs)
OPS = {
    "fusion.1": (_FE + "/while/body/dot_general", 0.020),
    "fusion.2": (_FE + "/photon.fe.matvec/photon.fe.matvec.coded/select_n",
                 0.004),
    "gather.3": (_FE + "/photon.fe.matvec/photon.fe.matvec.gathered/gather",
                 0.006),
    "scatter.4": (_FE + "/photon.fe.rmatvec/while/body/scatter-add", 0.030),
    "gather.5": (f"{_B}/photon.cd.fixed/jit(_fe_score_impl)/photon.fe.score/"
                 "photon.fe.matvec/gather", 0.002),
    "fusion.6": (f"{_B}/photon.cd.fixed/jit(_fe_score_impl)/photon.fe.score/"
                 "add", 0.001),
    "fusion.7": (f"{_B}/photon.cd.objective/reduce_sum", 0.0005),
    "fusion.8": (_USER + "/jit(_solve_block)/photon.re.gather/gather", 0.010),
    "pallas_entity_lbfgs.9": (
        _USER + "/jit(_solve_block)/photon.re.solve/r32/pallas_call", 0.008),
    "fusion.10": (_USER + "/jit(_solve_block)/photon.re.solve/r512/"
                  "vmap(jit(_minimize))/while/body/add", 0.012),
    "fusion.11": (_USER + "/jit(_re_score_impl)/photon.re.margins/dot", 0.002),
    "fusion.12": (_USER + "/jit(_re_score_impl)/photon.re.scatter/gather",
                  0.016),
    "fusion.13": (_MF + "/photon.re.gather/gather", 0.014),
    "fusion.14": (_MF + "/photon.mf.flatten/concatenate", 0.003),
    "fusion.15": (_MF + "/photon.mf.project/dot_general", 0.005),
    "pallas_entity_lbfgs.16": (_MF + "/photon.mf.latent/r64/pallas_call",
                               0.007),
    "fusion.17": (_MF + "/photon.mf.latent/r4096/while/body/add", 0.009),
    "fusion.18": (_MF + "/photon.mf.refit/while/body/dot_general", 0.011),
    "add.19": (_USER + "/add", 0.0007),           # a coordinate, no leaf
    "copy.20": ("", 0.0009),                       # the block's, no path
    "copy.21": ("jit(cd_block)/while/body/copy", 0.0011),
    "while.22": ("jit(cd_block)/while", 0.180),    # a container: by opcode
}
OTHER_PROGRAM = {"%broadcast.1": 0.0003}  # the zero vectors: not the table's


def _ctx(ops=OPS, extra=OTHER_PROGRAM, traced_jobs=2, table=True):
    op_seconds = {"%" + n: s for n, (_, s) in ops.items()}
    op_seconds.update(extra)
    ctx = {"trace": {"op_seconds": op_seconds, "traced_jobs": traced_jobs}}
    if table:
        ctx["instruction_scopes"] = {n: p for n, (p, _) in ops.items()}
        ctx["instruction_opcodes"] = {
            n: "while" if n.startswith("while") else "fusion" for n in ops}
    return ctx


# ms a job, by hand: seconds over two jobs -> x 1e3 / 2
EXPECTED = {
    "exchange_ms": 500 * (0.010 + 0.002 + 0.016 + 0.014),
    "fe_solve_job_ms": 500 * (0.020 + 0.004 + 0.006 + 0.030),
    "re_solve_job_ms": 500 * (0.008 + 0.012),
    "mf_solve_job_ms": 500 * (0.003 + 0.005 + 0.007 + 0.009 + 0.011),
    "mf_kernel_ms": 500 * 0.007,
    "fe_matvec_job_ms": 500 * (0.004 + 0.006 + 0.002),
    "fe_rmatvec_job_ms": 500 * 0.030,
    "fe_score_ms": 500 * (0.002 + 0.001 + 0.0005),
    "unscoped_ms": 500 * (0.0009 + 0.0011 + 0.0003),
}


def _read(metric, ctx):
    return importlib.import_module(f"benchmark.metrics.{metric}").read(ctx)


@needs_table
@pytest.mark.parametrize("metric", METRICS)
def test_reader_sums_its_scopes_by_hand(metric):
    assert _read(metric, _ctx()) == pytest.approx(EXPECTED[metric])


@pytest.mark.parametrize("metric", METRICS)
@pytest.mark.parametrize("why, ctx", [
    ("no trace", {"trace": None}),
    ("nothing at all", {}),
    ("no traced job", _ctx(traced_jobs=0)),
    ("an empty table", {**_ctx(table=False), "instruction_scopes": {}}),
    # another program's operations are over 5% of the time: the table is
    # not this trace's
    ("coverage under 95%", _ctx(extra={"%fusion.900": 0.02})),
])
def test_reader_says_nothing(metric, why, ctx):
    assert _read(metric, ctx) is None, why


@needs_table
def test_reader_asks_the_program_for_the_table(monkeypatch):
    """No table in ``ctx`` (the harness puts none there): the program's
    own, of the block it dispatched last; nothing while it has none."""
    from photon_ml_tpu.utils import compile_cache

    compile_cache.reset_compile_ledger()
    ctx = _ctx(table=False)
    assert _read("exchange_ms", ctx) is None
    full = _ctx()
    monkeypatch.setitem(compile_cache._instructions, "cd_block", {
        "scopes": full["instruction_scopes"],
        "opcodes": full["instruction_opcodes"], "scoped": 0, "seconds": 0.0})
    assert _read("exchange_ms", ctx) == pytest.approx(
        EXPECTED["exchange_ms"])
    assert _read("unscoped_ms", ctx) == pytest.approx(
        EXPECTED["unscoped_ms"])


@needs_table
def test_reader_on_a_program_from_before_the_table(monkeypatch):
    """The parent commit: no ``instruction_scopes`` to import, no
    ``scopes.place``. Every reader returns nothing and none raises."""
    from photon_ml_tpu.utils import compile_cache

    ctx = _ctx(table=False)
    monkeypatch.delattr(compile_cache, "instruction_scopes")
    assert [_read(m, ctx) for m in METRICS] == [None] * len(METRICS)
    monkeypatch.undo()
    monkeypatch.delattr(scopes, "place")
    assert [_read(m, _ctx()) for m in METRICS] == [None] * len(METRICS)


@needs_table
def test_by_scope_adds_up_and_keeps_containers_out():
    found = scope_seconds.by_scope(_ctx())
    leaves = sum(found["leaf"].values())
    coordinate_only = 500 * 0.0007
    assert leaves + coordinate_only + found["unscoped"] == pytest.approx(
        found["total"])
    assert found["total"] == pytest.approx(
        500 * (sum(s for n, (_, s) in OPS.items() if n != "while.22")
               + 0.0003))
    assert found["coverage"] == pytest.approx(
        1 - 500 * 0.0003 / found["total"])
    assert found["coordinate"]["photon.cd.perUser"] == pytest.approx(
        500 * (0.010 + 0.008 + 0.012 + 0.002 + 0.016 + 0.0007))
    assert found["kernel"] == {
        "photon.re.solve": pytest.approx(4.0),
        "photon.mf.latent": pytest.approx(3.5)}
    assert found["product"][
        "photon.fe.solve/photon.fe.matvec/photon.fe.matvec.coded"] == (
            pytest.approx(2.0))


def test_the_nine_entries_name_files_and_cells_that_exist():
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    cells = {w["name"] for w in bench["workloads"]}
    entries = {m["name"]: m for m in bench["per_layer"]}
    assert [m["name"] for m in bench["per_layer"]][-9:] == list(METRICS)
    for name in METRICS:
        entry = entries[name]
        assert (ROOT / "benchmark" / "metrics" / f"{name}.py").is_file()
        assert callable(importlib.import_module(
            f"benchmark.metrics.{name}").read)
        assert set(entry["workloads"]) <= cells and entry["workloads"]
        assert (entry["unit"], entry["better"], entry["source"],
                entry["moves"]) == ("ms", "lower", "device_trace", "fit_s")
    assert entries["exchange_ms"]["layer"] == "score exchange"
    assert set(entries["unscoped_ms"]["workloads"]) == cells
