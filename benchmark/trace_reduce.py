"""From a profiler trace to numbers: busy union, idle gaps, kernel sums.

The JAX profiler writes ``<dir>/plugins/profile/<time>/*.xplane.pb``;
``jax.profiler.ProfileData`` reads it with nothing but JAX. This module
first flattens a trace to plain data,

    {"planes": [{"name": str, "lines": [{"name": str,
                  "events": [[name, start_ns, duration_ns], ...]}]}]}

(``flatten``; the small recorded trace of the tests is such a file), and
reduces that. Every later PR computes the same numbers the same way.

What a TPU trace looks like (looked at by hand, PR 27): one plane per chip,
``/device:TPU:<i>``, whose line ``XLA Ops`` holds one event per executed
HLO op, named by the whole HLO instruction (``%fusion.2 = f32[...] ...``;
cut at `` = `` here), where a ``while`` spans its body's events, so busy
time is the union and never the sum, and whose line ``XLA Modules`` holds
one event per dispatched program; ``/host:CPU`` holds the
``TraceAnnotation`` spans of the job kind (``bench.job`` around each job,
``bench.probe.<layer>`` around a layer run alone) on the line ``python3``. All planes
share one clock.
"""

from __future__ import annotations

import json
from pathlib import Path
from typing import Iterable, List, Tuple

DEVICE_PLANE_PREFIXES = ("/device:TPU:", "/device:GPU:")
OPS_LINE = "XLA Ops"
JOB_SPAN = "bench.job"
# A layer run alone under a span of its own, after the traced jobs: the
# device time inside ``bench.probe.<layer>`` is that layer's.
PROBE_SPAN = "bench.probe."
# Ops that only hold other ops: counted in the union, left out of the list
# of operations that took most time.
CONTAINER_OPS = ("%while", "%conditional", "%call")

Interval = Tuple[int, int]


def flatten(profile) -> dict:
    """``jax.profiler.ProfileData`` -> the plain form above."""
    return {"planes": [
        {"name": plane.name, "lines": [
            {"name": line.name,
             "events": [[ev.name, int(ev.start_ns), int(ev.duration_ns)]
                        for ev in line.events]}
            for line in plane.lines]}
        for plane in profile.planes]}


def load(path: Path) -> dict:
    """A flattened trace from ``.json`` or from an ``.xplane.pb``."""
    path = Path(path)
    if path.suffix == ".json":
        return json.loads(path.read_text())
    from jax.profiler import ProfileData

    return flatten(ProfileData.from_file(str(path)))


def union(intervals: Iterable[Interval]) -> List[Interval]:
    """Sorted, disjoint intervals covering the same points."""
    out: List[Interval] = []
    for lo, hi in sorted(intervals):
        if out and lo <= out[-1][1]:
            if hi > out[-1][1]:
                out[-1] = (out[-1][0], hi)
        else:
            out.append((lo, hi))
    return out


def clip(intervals: Iterable[Interval], lo: int, hi: int) -> List[Interval]:
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if min(b, hi) > max(a, lo)]


def total(intervals: Iterable[Interval]) -> int:
    return sum(b - a for a, b in intervals)


def device_planes(trace: dict) -> List[dict]:
    return [p for p in trace["planes"]
            if p["name"].startswith(DEVICE_PLANE_PREFIXES)]


def op_events(plane: dict) -> List[list]:
    for line in plane["lines"]:
        if line["name"] == OPS_LINE:
            return line["events"]
    return []


def host_spans(trace: dict, name: str) -> List[Interval]:
    spans = []
    for plane in trace["planes"]:
        if plane["name"].startswith(DEVICE_PLANE_PREFIXES):
            continue
        for line in plane["lines"]:
            spans += [(s, s + d) for n, s, d in line["events"] if n == name]
    return sorted(spans)


def host_activity(trace: dict, lo: int, hi: int) -> str:
    """What the host was doing over [lo, hi): the ``bench.*`` span that
    covers most of it, or ``outside bench spans``."""
    best, best_cover = "outside bench spans", 0
    for plane in trace["planes"]:
        if plane["name"].startswith(DEVICE_PLANE_PREFIXES):
            continue
        for line in plane["lines"]:
            for n, s, d in line["events"]:
                if (not n.startswith("bench.") or n == JOB_SPAN
                        or n.startswith(PROBE_SPAN)):
                    continue
                cover = min(s + d, hi) - max(s, lo)
                if cover > best_cover:
                    best, best_cover = n, cover
    return best


def probe_spans(trace: dict) -> dict:
    """``{layer: [(start, end), ...]}`` of the ``bench.probe.*`` spans."""
    out: dict = {}
    for plane in trace["planes"]:
        if plane["name"].startswith(DEVICE_PLANE_PREFIXES):
            continue
        for line in plane["lines"]:
            for n, s, d in line["events"]:
                if n.startswith(PROBE_SPAN):
                    out.setdefault(n[len(PROBE_SPAN):], []).append((s, s + d))
    return {k: sorted(v) for k, v in out.items()}


def short_name(name: str) -> str:
    return name.split(" = ")[0]


def op_sum(reduction: dict, prefix: str) -> float:
    """Summed seconds, over the traced jobs, of the operations whose name
    starts with ``prefix`` (a kernel's events, say)."""
    return sum(s for n, s in reduction["op_seconds"].items()
               if n.startswith(prefix))


def reduce(trace: dict) -> dict:
    """Over the traced jobs (first ``bench.job`` span's start to the
    last's end): busy seconds (union of op intervals, mean over chips),
    the window, the idle gaps by what the host was doing, and every
    operation's summed seconds. Over each ``bench.probe.<layer>`` span:
    the busy seconds inside it."""
    planes = device_planes(trace)
    if not planes:
        raise ValueError("the trace holds no device plane")
    jobs = host_spans(trace, JOB_SPAN)
    if jobs:
        lo, hi = jobs[0][0], jobs[-1][1]
    else:  # no host span recorded: the device's own first to last event
        evs = [e for p in planes for e in op_events(p)]
        lo = min(s for _, s, _ in evs)
        hi = max(s + d for _, s, d in evs)
    busy_ns, by_name = [], {}
    gaps: List[Tuple[int, int]] = []
    probes = probe_spans(trace)
    probe_ns = {layer: [0] * len(spans) for layer, spans in probes.items()}
    for plane in planes:
        evs = op_events(plane)
        everything = union((s, s + d) for _, s, d in evs)
        merged = clip(everything, lo, hi)
        busy_ns.append(total(merged))
        for layer, spans in probes.items():
            for i, (a, b) in enumerate(spans):
                probe_ns[layer][i] += total(clip(everything, a, b))
        edges = [lo] + [t for iv in merged for t in iv] + [hi]
        gaps += [(edges[i], edges[i + 1])
                 for i in range(0, len(edges), 2)
                 if edges[i + 1] > edges[i]]
        for n, s, d in evs:
            if s + d <= lo or s >= hi:
                continue
            n = short_name(n)
            if not n.startswith(CONTAINER_OPS):
                by_name[n] = by_name.get(n, 0) + d
    n_chips = len(planes)
    busy_s = sum(busy_ns) / n_chips / 1e9
    if busy_s <= 0:
        raise ValueError("no operation ran on the device in the trace")
    window_s = (hi - lo) / 1e9
    top_ops = sorted(by_name.items(), key=lambda kv: -kv[1])[:10]
    top_gaps = sorted(gaps, key=lambda g: g[0] - g[1])[:10]
    return {
        "busy_s": busy_s, "window_s": window_s,
        "idle_share": 1.0 - busy_s / window_s,
        "traced_jobs": len(jobs),
        "op_seconds": {n: ns / n_chips / 1e9 for n, ns in by_name.items()},
        # device-busy seconds inside each probe span, mean over chips
        "probe_busy_s": {layer: [ns / n_chips / 1e9 for ns in per_span]
                         for layer, per_span in probe_ns.items()},
        "breakdown": {
            "device_ops": [[n, ns / n_chips / 1e9] for n, ns in top_ops],
            "idle_gaps": [[host_activity(trace, a, b), (b - a) / 1e9]
                          for a, b in top_gaps]},
    }


def reduce_dir(trace_dir: Path) -> dict:
    files = sorted(Path(trace_dir).rglob("*.xplane.pb"))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return reduce(load(files[-1]))
