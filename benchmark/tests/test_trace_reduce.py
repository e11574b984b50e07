"""The reduction from a trace to numbers, on a small trace recorded on the
chip (one traced job of ``glmix.fit`` in this PR's first round, when a job
still carried its score pass, kept in ``tests/data/``) and on
intervals small enough to do by hand."""

import json
from pathlib import Path

import pytest

from benchmark import trace_reduce as tr
from benchmark import work_model

DATA = Path(__file__).resolve().parent / "data"
RECORDED = DATA / "trace_glmix_fit_one_job.json"


def test_union_clip_total_by_hand():
    merged = tr.union([(5, 9), (0, 3), (2, 4), (9, 10), (20, 30), (22, 25)])
    assert merged == [(0, 4), (5, 10), (20, 30)]
    assert tr.total(merged) == 19
    assert tr.clip(merged, 3, 21) == [(3, 4), (5, 10), (20, 21)]


def _trace(ops, spans):
    return {"planes": [
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Modules", "events": [["jit_block", 0, 100]]},
            {"name": "XLA Ops", "events": ops}]},
        {"name": "/host:CPU", "lines": [
            {"name": "python3", "events": spans}]}]}


def test_busy_idle_and_kernel_sum_by_hand():
    ops = [["%while.1 = (...) while(...)", 10, 50],  # holds the next two
           ["%fusion.2 = f32[8] fusion(...)", 10, 20],
           ["%pallas_entity_lbfgs.3 = (...) custom-call(...)", 35, 20],
           ["%pallas_entity_lbfgs.4 = (...) custom-call(...)", 70, 10],
           ["%fusion.9 = f32[8] fusion(...)", 150, 10]]  # after the window
    spans = [["bench.job", 0, 100], ["bench.run", 0, 62],
             ["bench.settle", 62, 38],
             ["bench.probe.fe_solve", 140, 15],  # holds [150,155) of an op
             ["bench.probe.fe_solve", 170, 10]]  # holds none
    out = tr.reduce(_trace(ops, spans))
    assert out["window_s"] == pytest.approx(100e-9)
    assert out["busy_s"] == pytest.approx(60e-9)  # [10,60) and [70,80)
    assert out["idle_share"] == pytest.approx(0.4)
    assert out["traced_jobs"] == 1
    assert tr.op_sum(out, "%pallas_entity_lbfgs") == pytest.approx(30e-9)
    assert out["probe_busy_s"] == {
        "fe_solve": [pytest.approx(5e-9), pytest.approx(0.0)]}
    names = [n for n, _ in out["breakdown"]["device_ops"]]
    assert names == ["%fusion.2", "%pallas_entity_lbfgs.3",
                     "%pallas_entity_lbfgs.4"]  # no %while, none outside
    gaps = out["breakdown"]["idle_gaps"]
    assert gaps[0] == ["bench.settle", pytest.approx(20e-9)]  # [80,100)
    assert sorted(g[1] for g in gaps) == pytest.approx([10e-9, 10e-9, 20e-9])


def test_recorded_trace_reduces_to_the_sweep_line_count():
    trace = tr.load(RECORDED)
    out = tr.reduce(trace)
    job = tr.host_spans(trace, tr.JOB_SPAN)[0]
    ops = tr.op_events(tr.device_planes(trace)[0])
    # busy time again, by counting depth along the sorted end points
    points = sorted([(max(s, job[0]), 1) for _, s, d in ops
                     if s < job[1] and s + d > job[0]]
                    + [(min(s + d, job[1]), -1) for _, s, d in ops
                       if s < job[1] and s + d > job[0]])
    busy = depth = 0
    last = None
    for t, step in points:
        if depth > 0:
            busy += t - last
        depth += step
        last = t
    assert out["busy_s"] == pytest.approx(busy / 1e9, rel=1e-12)
    assert out["window_s"] == pytest.approx(0.964908475)
    assert 0.0 < out["idle_share"] < 0.05
    kernel = sum(d for n, s, d in ops if n.startswith("%pallas_entity_lbfgs")
                 and s < job[1] and s + d > job[0])
    assert kernel > 0
    assert tr.op_sum(out, "%pallas_entity_lbfgs") == pytest.approx(
        kernel / 1e9)
    assert out["probe_busy_s"] == {}  # recorded before the probes had spans
    assert len(out["breakdown"]["device_ops"]) == 10
    assert all(not n.startswith("%while")
               for n, _ in out["breakdown"]["device_ops"])
    assert all(g[0].startswith("bench.") or g[0] == "outside bench spans"
               for g in out["breakdown"]["idle_gaps"])


def test_a_trace_without_device_work_is_an_error():
    with pytest.raises(ValueError):
        tr.reduce(_trace([], [["bench.job", 0, 100]]))
    with pytest.raises(ValueError):
        tr.reduce({"planes": [{"name": "/host:CPU", "lines": []}]})


def test_unknown_device_kind_raises():
    peaks = json.loads((DATA.parents[1] / "peaks.json").read_text())
    ctx = {"device": {"kind": "TPU v5 lite"}, "peaks": peaks}
    assert work_model.peaks_of(ctx)["hbm_bytes_per_s"] == 819e9
    ctx["device"]["kind"] = "TPU v9 imaginary"
    with pytest.raises(KeyError):
        work_model.peaks_of(ctx)
