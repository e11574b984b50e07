#!/usr/bin/env python3
"""Where a fit's device time goes, by the program's own names.

    python3 dev_scripts/trace_scopes.py --workload glmix.fit --seed <n>
    python3 dev_scripts/trace_scopes.py --from-json <recorded.json>

Builds the benchmark cell's job (``benchmark/`` is imported read-only: the
cell, its recipe and ``jobs/cd_fit.py``), warms it up, traces ``--jobs``
jobs in a profiler session of its own, reads the ``.xplane.pb`` KEEPING
each device event's scope path (the ``op_name`` of the HLO metadata, which
``jax.named_scope`` writes and the benchmark's ``trace_reduce.flatten``
drops), and prints per job:

- device ms by scope of ``photon_ml_tpu/telemetry/scopes.py``: an operation
  counts under the innermost table scope on its path; a scope's time is the
  union of its operations' intervals; the size classes ``r<rows>`` are
  listed under ``photon.re.solve`` with the path each took (and, where a
  fit has a factored coordinate, its ``photon.mf.*`` rows with the latent
  classes under ``photon.mf.latent``);
- the same rolled up by ``photon.cd.<coordinate>`` (with more than two
  coordinates, each one's update by leaf scope under it);
- the exchange (gather + margins + scatter);
- what of each scope ran before ``cd_block``'s first operation (the eager
  initial-scores pass of a warm start; nothing on a cold one);
- the remainder under no ``photon.*`` scope, with its largest operations;
- every idle gap over ``--gap-ms`` with the innermost ``photon.cd.*`` host
  span that covers it (``bench.*`` where none does);
- of every scope's time, the part that is collectives (operations named
  ``all-reduce*``, ``all-gather*``, ``reduce-scatter*``, ``all-to-all*``,
  ``collective-permute*``, or with such an opcode under a name JAX gave:
  ``is_collective``), and the device-busy time chip by chip. Over a
  trace of several chips every number is the mean over the chips' planes;
  the gaps and the unscoped operations listed are the first chip's.

Where the path lives (looked at by hand on the v5e, JAX 0.9.0, PR 29): in
the stat ``tf_op`` of the event's METADATA (one per HLO instruction of a
program: ``jit(cd_block)/while/body/closed_call/photon.cd.perUser/
jit(_solve_block)/photon.re.solve/r32/.../pallas_call:``), not of the
event, whose own stats are ``device_offset_ps``, ``device_duration_ps``
and ``Time Scale Multiplier``. ``jax.profiler.ProfileData`` shows only the
event's own stats and names an event by its metadata's name, which two
programs can share, so this script reads the file's protobuf wire format
itself (``read_xspace``: six message types, no dependency).

The persistent compile cache's key leaves metadata out, so a program
compiled before a scope was named is loaded with its OLD names (seen on
the chip, PR 29: ``jit(_re_score_impl)`` came from an older checkout's
entry, without ``photon.re.scatter``). This script therefore turns the
persistent cache off for its own process: it compiles what it traces,
with the tree's names, and leaves the machine's cache as it found it.

``--join`` (PR 40) also reads the SAME trace as the benchmark does: its
frozen reduction (``benchmark/trace_reduce.py``: seconds by instruction name,
no path) joined with the instruction table the program published for the
block it dispatched (``utils.compile_cache.instruction_scopes``) through
``benchmark/scope_seconds.py``, and prints every ``per_layer`` metric of the
cell that a file under ``benchmark/metrics`` reads from it beside this
script's own number for the same scope. ``--cached`` leaves the persistent
cache on (half the set-up; right where no scope was renamed since the cache
was filled, and the join is right either way: table and trace are read from
the one executable that ran).

``--save-trace`` keeps the flattened trace with its paths (one job with
``--cut-jobs 1``: the recorded trace of ``tests/test_fit_tracing.py``);
``--dump-stats N`` prints every stat of the N longest device events, to
see by hand which one carries the path on a new chip or JAX.

How a path resolves to the table's scopes (``place``) and what a collective
is (``is_collective``) are ``photon_ml_tpu/telemetry/scopes.py``'s since
PR 40, shared with the benchmark's readers of the block's instruction table
(``benchmark/scope_seconds.py``), which serve the same numbers by summing
``op_seconds`` by instruction name; this script edits nothing there.
"""

from __future__ import annotations

import argparse
import json
import struct
import sys
from pathlib import Path
from typing import Dict, Iterable, List, Tuple

ROOT = Path(__file__).resolve().parent.parent
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from benchmark.trace_reduce import (  # noqa: E402
    CONTAINER_OPS,
    DEVICE_PLANE_PREFIXES,
    JOB_SPAN,
    OPS_LINE,
    clip,
    short_name,
    total,
    union,
)
from photon_ml_tpu.telemetry import scopes  # noqa: E402

HOST_SPAN_PREFIXES = (scopes.PREFIX, "bench.")
# How a path resolves to the table's scopes, and what a collective is, are
# the program's (one definition for this script, the benchmark's readers and
# an operator: PR 40).
place = scopes.place
is_collective = scopes.is_collective


NO_SCOPE = "(no scope)"
LEAF_SCOPES = scopes.LEAF_SCOPES
# The stat that carries the HLO metadata's op_name (``tf_op`` on the v5e,
# PERF.md §5); any other stat whose value holds a ``photon.`` scope is
# taken where a later profiler renames it.
PATH_STATS = ("tf_op", "op_name")

Interval = Tuple[int, int]


# -- from the profiler's file to plain data, paths kept -----------------------

def _varint(buf, i: int) -> Tuple[int, int]:
    value = shift = 0
    while True:
        byte = buf[i]
        i += 1
        value |= (byte & 0x7F) << shift
        if byte < 0x80:
            return value, i
        shift += 7


def _fields(buf):
    """(field number, value) of one protobuf message: an int for a varint,
    a memoryview for a length-delimited or fixed field."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        else:
            if wire == 2:
                size, i = _varint(buf, i)
            elif wire in (1, 5):
                size = 8 if wire == 1 else 4
            else:
                raise ValueError(f"wire type {wire} at byte {i}")
            value, i = buf[i:i + size], i + size
        yield key >> 3, value


def _stat(buf) -> Tuple[int, object, bool]:
    """XStat -> (metadata id, value, whether the value is a ``ref_value``:
    the id of the stat metadata whose name is the string meant)."""
    ident, value, ref = 0, None, False
    for field, v in _fields(buf):
        if field == 1:
            ident = v
        elif field == 2:
            value = struct.unpack("<d", v)[0]
        elif field in (3, 4, 7):
            value, ref = v, field == 7
        elif field == 5:
            value = bytes(v).decode("utf-8", "replace")
        elif field == 6:
            value = bytes(v)
    return ident, value, ref


def _map_entry(buf) -> Tuple[int, memoryview]:
    key, value = 0, memoryview(b"")
    for field, v in _fields(buf):
        if field == 1:
            key = v
        elif field == 2:
            value = v
    return key, value


def read_xspace(path: Path) -> List[dict]:
    """The planes of an ``.xplane.pb`` (tsl/profiler/protobuf/xplane.proto):
    ``{"name", "lines": [{"name", "events": [{"name", "start_ns",
    "duration_ns", "stats", "metadata_stats"}]}]}``, stats by name. An
    event's start is its line's ``timestamp_ns`` plus its ``offset_ps``,
    as ``ProfileData`` has it: all planes share that clock."""
    planes = []
    for field, plane_buf in _fields(memoryview(Path(path).read_bytes())):
        if field != 1:
            continue
        name, line_bufs, metadata, stat_names = "", [], {}, {}
        for f, v in _fields(plane_buf):
            if f == 2:
                name = bytes(v).decode()
            elif f == 3:
                line_bufs.append(v)
            elif f == 4:
                ident, md = _map_entry(v)
                entry = {"name": "", "stats": []}
                for mf, mv in _fields(md):
                    if mf == 2:
                        entry["name"] = bytes(mv).decode("utf-8", "replace")
                    elif mf == 5:
                        entry["stats"].append(_stat(mv))
                metadata[ident] = entry
            elif f == 5:
                ident, md = _map_entry(v)
                stat_names[ident] = next(
                    (bytes(mv).decode() for mf, mv in _fields(md)
                     if mf == 2), "")

        def named(stats):
            return {stat_names.get(i, str(i)):
                    stat_names.get(v, v) if ref else v
                    for i, v, ref in stats}

        for entry in metadata.values():
            entry["stats"] = named(entry["stats"])
        lines = []
        for line_buf in line_bufs:
            line_name, t0_ns, events = "", 0, []
            for f, v in _fields(line_buf):
                if f == 2:
                    line_name = bytes(v).decode()
                elif f == 3:
                    t0_ns = v
                elif f == 4:
                    ident = offset_ps = duration_ps = 0
                    stats = []
                    for ef, ev in _fields(v):
                        if ef == 1:
                            ident = ev
                        elif ef == 2:
                            offset_ps = ev
                        elif ef == 3:
                            duration_ps = ev
                        elif ef == 4:
                            stats.append(_stat(ev))
                    events.append((ident, offset_ps, duration_ps, stats))
            lines.append({"name": line_name, "events": [
                {"name": metadata.get(i, {"name": ""})["name"],
                 "start_ns": t0_ns + off // 1000,
                 "duration_ns": dur // 1000, "stats": named(st),
                 "metadata_stats": metadata.get(i, {"stats": {}})["stats"]}
                for i, off, dur, st in events]})
        planes.append({"name": name, "lines": lines})
    return planes


def path_of(stats: dict) -> str:
    """The scope path among an event's (metadata's) stats, without the
    colon the profiler ends it with."""
    for key in PATH_STATS:
        value = stats.get(key)
        if isinstance(value, str) and (scopes.PREFIX in value
                                       or "jit(" in value):
            return value.rstrip(":")
    for value in stats.values():  # a stat this list does not know yet
        if isinstance(value, str) and scopes.PREFIX in value:
            return value.rstrip(":")
    return ""


def flatten_with_paths(planes: List[dict]) -> dict:
    """``read_xspace``'s planes -> ``{"planes": [{"name", "lines":
    [{"name", "events": [[name, start_ns, duration_ns, path], ...]}]}]}``:
    ``benchmark/trace_reduce.flatten``'s form with a fourth field; of the
    host's events only the ``photon.*`` and ``bench.*`` spans."""
    out = []
    for plane in planes:
        device = plane["name"].startswith(DEVICE_PLANE_PREFIXES)
        lines = []
        for line in plane["lines"]:
            keep_path = device and line["name"] == OPS_LINE
            lines.append({"name": line["name"], "events": [
                [ev["name"], ev["start_ns"], ev["duration_ns"],
                 path_of({**ev["metadata_stats"], **ev["stats"]})
                 if keep_path else ""]
                for ev in line["events"]
                if device or ev["name"].startswith(HOST_SPAN_PREFIXES)]})
        out.append({"name": plane["name"], "lines": lines})
    return {"planes": out}


def pack(trace: dict) -> dict:
    """Names and paths through one string table: a job's trace in ~0.5 MB."""
    table: Dict[str, int] = {}

    def ix(s: str) -> int:
        return table.setdefault(s, len(table))

    planes = [{"name": p["name"], "lines": [
        {"name": ln["name"],
         "events": [[ix(n), s, d, ix(path)] for n, s, d, path in ln["events"]]}
        for ln in p["lines"]]} for p in trace["planes"]]
    return {"strings": list(table), "planes": planes,
            **{k: v for k, v in trace.items() if k != "planes"}}


def unpack(packed: dict) -> dict:
    if "strings" not in packed:
        return packed
    strings = packed["strings"]
    planes = [{"name": p["name"], "lines": [
        {"name": ln["name"],
         "events": [[strings[n], s, d, strings[path]]
                    for n, s, d, path in ln["events"]]}
        for ln in p["lines"]]} for p in packed["planes"]]
    return {**{k: v for k, v in packed.items() if k != "strings"},
            "planes": planes}


def cut_jobs(trace: dict, jobs: int) -> dict:
    """Only what overlaps the first ``jobs`` ``bench.job`` spans, times
    moved so the first starts at 0 (for a small recorded trace)."""
    spans = job_spans(trace)[:jobs]
    if not spans:
        return trace
    lo, hi = spans[0][0], spans[-1][1]
    planes = [{"name": p["name"], "lines": [
        {"name": ln["name"],
         "events": [[n, s - lo, d, path] for n, s, d, path in ln["events"]
                    if s + d > lo and s < hi]}
        for ln in p["lines"]]} for p in trace["planes"]]
    return {**trace, "planes": planes}


# -- intervals: union, clip and total are benchmark/trace_reduce.py's ---------

def covered_ms(intervals: Iterable[Interval], lo: int, hi: int) -> float:
    return total(clip(union(intervals), lo, hi)) / 1e6


# -- where an operation ran ----------------------------------------------------

def in_block(path: str) -> bool:
    return path.startswith(f"jit({scopes.CD_BLOCK})/")


def job_spans(trace: dict) -> List[Tuple[int, int, str]]:
    return host_spans(trace, lambda n: n == JOB_SPAN)


def host_spans(trace: dict, want) -> List[Tuple[int, int, str]]:
    out = []
    for plane in trace["planes"]:
        if plane["name"].startswith(DEVICE_PLANE_PREFIXES):
            continue
        for line in plane["lines"]:
            out += [(s, s + d, n) for n, s, d, _ in line["events"]
                    if want(n)]
    return sorted(out)


def phase_of(spans: List[Tuple[int, int, str]], lo: int, hi: int) -> str:
    """The innermost host span covering [lo, hi): the shortest of those
    that overlap at least half of it; where none does, the one that
    overlaps it most."""
    covering, most = [], (0, "outside photon and bench spans")
    for a, b, name in spans:
        cover = min(b, hi) - max(a, lo)
        if 2 * cover >= hi - lo:
            covering.append((b - a, name))
        most = max(most, (cover, name))
    return min(covering)[1] if covering else most[1]


# -- the reduction -------------------------------------------------------------

def reduce_job(events: List[list], spans, lo: int, hi: int,
               gap_ms: float) -> dict:
    by_leaf: Dict[str, list] = {}
    by_coord: Dict[str, list] = {}
    by_class: Dict[str, dict] = {}
    by_latent: Dict[str, dict] = {}
    by_cell: Dict[str, list] = {}
    by_product: Dict[str, list] = {}
    scoped, everything, loose = [], [], {}
    collectives: Dict[str, list] = {}
    for name, s, d, path in events:
        if s + d <= lo or s >= hi or d <= 0:
            continue
        iv = (s, s + d)
        everything.append(iv)
        where = place(path)
        if is_collective(name):
            collectives.setdefault(where["leaf"] or NO_SCOPE, []).append(iv)
        if not where["scoped"]:
            n = short_name(name)
            loose.setdefault(n, []).append(iv)
            continue
        scoped.append(iv)
        if where["leaf"]:
            by_leaf.setdefault(where["leaf"], []).append(iv)
        for key in (where["product"], where["part"]):
            if key:
                by_product.setdefault(key, []).append(iv)
        if where["coordinate"]:
            by_coord.setdefault(where["coordinate"], []).append(iv)
            by_cell.setdefault(
                f"{where['coordinate']}/{where['leaf'] or NO_SCOPE}",
                []).append(iv)
        if where["size_class"]:
            classes = (by_latent if where["leaf"] == scopes.MF_LATENT
                       else by_class)
            cls = classes.setdefault(where["size_class"],
                                     {"ivs": [], "kernel": False})
            cls["ivs"].append(iv)
            if short_name(name).lstrip("%").startswith(scopes.KERNEL):
                cls["kernel"] = True
    busy = clip(union(everything), lo, hi)
    busy_ms = total(busy) / 1e6
    scoped_ms = covered_ms(scoped, lo, hi)
    # Operations under no scope that run while a scoped one does (a scan's
    # ``while`` around everything) take nothing from the remainder.
    scoped_union = clip(union(scoped), lo, hi)
    loose_ops = {}
    for n, ivs in loose.items():
        mine = clip(union(ivs), lo, hi)
        loose_ops[n] = (total(mine)
                        - total(_intersect(mine, scoped_union))) / 1e6
    edges = [lo] + [t for iv in busy for t in iv] + [hi]
    gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] - edges[i] > gap_ms * 1e6]
    scope_ms = {s: covered_ms(by_leaf.get(s, []), lo, hi)
                for s in LEAF_SCOPES
                if s in scopes.DEVICE_SCOPES or s in by_leaf}

    def class_ms(classes):
        return {c: {"ms": covered_ms(v["ivs"], lo, hi),
                    "path": "kernel" if v["kernel"] else "vmapped"}
                for c, v in sorted(classes.items(),
                                   key=lambda kv: int(kv[0][1:]))}

    # What ran before the block's first operation: a cold start's initial
    # scores are built, so no scoring scope may show here (a warm start's,
    # and a coordinate's that declares no zero start, do).
    block_starts = [s for _, s, d, path in events
                    if lo <= s < hi and d > 0 and in_block(path)]
    first = min(block_starts, default=hi)
    return {
        "window_ms": (hi - lo) / 1e6, "busy_ms": busy_ms,
        "scope_ms": scope_ms,
        "before_block_ms": {s: covered_ms(by_leaf.get(s, []), lo, first)
                            for s in scopes.DEVICE_SCOPES},
        "exchange_ms": sum(scope_ms[s] for s in scopes.EXCHANGE_SCOPES),
        # of each scope's time, what is collectives (union of intervals)
        "collective_ms": {s: covered_ms(ivs, lo, hi)
                          for s, ivs in sorted(collectives.items())},
        "collectives_ms": covered_ms(
            [iv for ivs in collectives.values() for iv in ivs], lo, hi),
        "size_class_ms": class_ms(by_class),
        # the factored coordinate's latent solves, by size class
        "latent_class_ms": class_ms(by_latent),
        # every coordinate's update by leaf scope (``<coordinate>/<leaf>``)
        "cell_ms": {c: covered_ms(ivs, lo, hi)
                    for c, ivs in sorted(by_cell.items())},
        # a sparse fixed effect's products, by the scope that ran them
        "product_ms": {c: covered_ms(ivs, lo, hi)
                       for c, ivs in sorted(by_product.items())},
        "coordinate_ms": {c: covered_ms(ivs, lo, hi)
                          for c, ivs in sorted(by_coord.items())},
        "unattributed_ms": busy_ms - scoped_ms,
        "unattributed_share": (busy_ms - scoped_ms) / busy_ms
        if busy_ms else 0.0,
        "unattributed_ops": sorted(
            ([n, ms] for n, ms in loose_ops.items() if ms > 0),
            key=lambda kv: -kv[1])[:8],
        "idle_gaps": [{"ms": (b - a) / 1e6, "at_ms": (a - lo) / 1e6,
                       "phase": phase_of(spans, a, b)} for a, b in gaps],
    }


def _intersect(a: List[Interval], b: List[Interval]) -> List[Interval]:
    """Of two sorted disjoint lists."""
    out, i, j = [], 0, 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            out.append((lo, hi))
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


def reduce_scopes(trace: dict, gap_ms: float = 0.2) -> dict:
    trace = unpack(trace)
    planes = [p for p in trace["planes"]
              if p["name"].startswith(DEVICE_PLANE_PREFIXES)]
    if not planes:
        raise ValueError("the trace holds no device plane")
    chips = [[e for ln in plane["lines"] if ln["name"] == OPS_LINE
              for e in ln["events"]] for plane in planes]
    jobs = job_spans(trace)
    if not jobs:
        raise ValueError(f"the trace holds no {JOB_SPAN} span")
    spans = host_spans(trace, lambda n: n != JOB_SPAN
                       and n.startswith(HOST_SPAN_PREFIXES))
    per_job = []
    for lo, hi, _ in jobs:
        per_chip = [reduce_job(events, spans, lo, hi, gap_ms)
                    for events in chips]
        # One chip: its numbers. Several: the mean over chips; the gaps and
        # the unscoped operations listed are the first chip's.
        job = per_chip[0] if len(per_chip) == 1 else {
            **per_chip[0], **_mean(per_chip)}
        job["chip_busy_ms"] = [c["busy_ms"] for c in per_chip]
        per_job.append(job)
    mean = _mean(per_job)
    mean["chip_busy_ms"] = [
        sum(j["chip_busy_ms"][k] for j in per_job) / len(per_job)
        for k in range(len(chips))]
    return {"jobs": per_job, "mean": mean}


def _mean(per_job: List[dict]) -> dict:
    n = len(per_job)
    out = {k: sum(j[k] for j in per_job) / n
           for k in ("window_ms", "busy_ms", "exchange_ms", "collectives_ms",
                     "unattributed_ms", "unattributed_share")}
    for key in ("scope_ms", "before_block_ms", "coordinate_ms",
                "collective_ms", "product_ms", "cell_ms"):
        names = sorted({s for j in per_job for s in j[key]})
        out[key] = {s: sum(j[key].get(s, 0.0) for j in per_job) / n
                    for s in names}
    for key in ("size_class_ms", "latent_class_ms"):
        classes = sorted({c for j in per_job for c in j[key]},
                         key=lambda c: int(c[1:]))
        out[key] = {
            c: {"ms": sum(j[key].get(c, {"ms": 0.0})["ms"]
                          for j in per_job) / n,
                "path": next(j[key][c]["path"] for j in per_job
                             if c in j[key])}
            for c in classes}
    return out


# -- printing ----------------------------------------------------------------

def print_report(result: dict, out=sys.stdout) -> None:
    def table(block: dict, title: str) -> None:
        busy = block["busy_ms"]
        print(f"\n{title}: window {block['window_ms']:.3f} ms, device busy "
              f"{busy:.3f} ms", file=out)
        coll = block["collective_ms"]
        print("| scope | ms | share of busy | of it collectives, ms |",
              file=out)
        print("| --- | --- | --- | --- |", file=out)
        for s in LEAF_SCOPES:  # the table's order, not the dict's
            if s not in block["scope_ms"]:
                continue
            ms = block["scope_ms"][s]
            print(f"| `{s}` | {ms:.3f} | {100 * ms / busy:.2f}% | "
                  f"{coll.get(s, 0.0):.3f} |", file=out)
            mine = {c: v for c, v in block.get("product_ms", {}).items()
                    if c.startswith(s + "/")}
            for c, v in mine.items():
                indent = "&nbsp;&nbsp;" * c.count("/")
                print(f"| {indent}`{c.rsplit('/', 1)[1]}` | {v:.3f} | "
                      f"{100 * v / busy:.2f}% |", file=out)
            if mine:
                rest = ms - sum(v for c, v in mine.items()
                                if c.count("/") == 1)
                print(f"| &nbsp;&nbsp;the rest of `{s}` (d-space, "
                      f"n-vectors) | {rest:.3f} | {100 * rest / busy:.2f}% |",
                      file=out)
            if s in (scopes.RE_SOLVE, scopes.MF_LATENT):
                classes = block["size_class_ms" if s == scopes.RE_SOLVE
                                else "latent_class_ms"]
                for c, v in classes.items():
                    print(f"| &nbsp;&nbsp;`{c}` ({v['path']}) | "
                          f"{v['ms']:.3f} | {100 * v['ms'] / busy:.2f}% |",
                          file=out)
        print(f"| exchange (gather + margins + scatter) | "
              f"{block['exchange_ms']:.3f} | "
              f"{100 * block['exchange_ms'] / busy:.2f}% |", file=out)
        for c, ms in block["coordinate_ms"].items():
            print(f"| `{c}` (whole update) | {ms:.3f} | "
                  f"{100 * ms / busy:.2f}% |", file=out)
            if len(block["coordinate_ms"]) > 2:  # which scope, whose update
                for cell, v in block["cell_ms"].items():
                    if cell.startswith(c + "/"):
                        print(f"| &nbsp;&nbsp;`{cell.split('/', 1)[1]}` | "
                              f"{v:.3f} | {100 * v / busy:.2f}% |",
                              file=out)
        print(f"| under no `photon.*` scope | "
              f"{block['unattributed_ms']:.3f} | "
              f"{100 * block['unattributed_share']:.2f}% | "
              f"{coll.get(NO_SCOPE, 0.0):.3f} |", file=out)
        print(f"collectives (union over scopes, mean over chips): "
              f"{block['collectives_ms']:.3f} ms; device busy by chip, ms: "
              + ", ".join(f"{ms:.3f}" for ms in block["chip_busy_ms"]),
              file=out)
        early = {s: ms for s, ms in block["before_block_ms"].items() if ms}
        print("before the block's first operation: " + (", ".join(
            f"`{s}` {ms:.3f} ms" for s, ms in early.items())
            or "no scoped operation"), file=out)

    for k, job in enumerate(result["jobs"]):
        table(job, f"job {k}")
        for n, ms in job["unattributed_ops"]:
            print(f"  unattributed: {n} {ms:.3f} ms", file=out)
        for g in job["idle_gaps"]:
            print(f"  idle {g['ms']:.3f} ms at {g['at_ms']:.3f} ms: "
                  f"{g['phase']}", file=out)
    table(result["mean"], f"mean of {len(result['jobs'])} jobs")


def dump_stats(planes: List[dict], n: int, out=sys.stdout) -> None:
    """Every stat of the ``n`` longest device operations (the event's own
    and its metadata's), and the lines of every plane: what a trace of
    this chip and JAX looks like."""
    for plane in planes:
        print(f"plane {plane['name']!r}: " + ", ".join(
            f"{ln['name']!r} ({len(ln['events'])})"
            for ln in plane["lines"]), file=out)
        if not plane["name"].startswith(DEVICE_PLANE_PREFIXES):
            continue
        for line in plane["lines"]:
            if line["name"] != OPS_LINE:
                continue
            longest = sorted(line["events"],
                             key=lambda e: -e["duration_ns"])[:n]
            for ev in longest:
                print(f"  {ev['name'][:120]!r} "
                      f"{ev['duration_ns'] / 1e6:.3f} ms", file=out)
                for kind in ("stats", "metadata_stats"):
                    for key, value in ev[kind].items():
                        print(f"      {kind}.{key} = {str(value)[:300]!r}",
                              file=out)


# -- the traced run ------------------------------------------------------------

def trace_cell(workload: str, seed: int, jobs: int, rehearse_rows: int,
               trace_dir: Path, cached: bool = False):
    """The cell's job as the harness builds it, warmed up, then ``jobs``
    jobs under a profiler session; returns the ``.xplane.pb``'s path."""
    import importlib
    import shutil

    import jax

    from benchmark import harness
    from photon_ml_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()  # for the compile ledger; the cache itself off:
    # executables whose metadata is the tree's (the module's docstring).
    if not cached:
        jax.config.update("jax_enable_compilation_cache", False)
    loaded = harness.load_cell(workload)
    config, wl = loaded["config"], loaded["workload"]
    device = harness.device_block(int(loaded["cell"]["chips"]),
                                  require_chip=not rehearse_rows)
    print(f"trace_scopes: device {device}", file=sys.stderr)
    recipe = importlib.import_module(f"benchmark.recipes.{config['recipe']}")
    if rehearse_rows:
        config = recipe.scale_down(config, rehearse_rows)
    problem = recipe.make(config, seed)
    job = importlib.import_module(
        f"benchmark.jobs.{wl['job']}").build(config, wl, problem)
    job.warm_up(seed)
    job.run_job(seed + 1)  # a second: nothing of the first call is left
    shutil.rmtree(trace_dir, ignore_errors=True)
    jax.profiler.start_trace(str(trace_dir))
    for k in range(jobs):
        job.run_job(seed + 2 + k)
    jax.profiler.stop_trace()
    files = sorted(trace_dir.rglob("*.xplane.pb"))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return files[-1]


#: The script's own number for what each joined metric sums.
_JOINED = {
    "exchange_ms": lambda m: m["exchange_ms"],
    "fe_solve_job_ms": lambda m: m["scope_ms"][scopes.FE_SOLVE],
    "re_solve_job_ms": lambda m: m["scope_ms"][scopes.RE_SOLVE],
    "mf_solve_job_ms": lambda m: sum(
        m["scope_ms"].get(s, 0.0) for s in scopes.MF_SCOPES),
    "mf_kernel_ms": lambda m: sum(
        v["ms"] for v in m["latent_class_ms"].values()
        if v["path"] == "kernel"),
    "fe_matvec_job_ms": lambda m: sum(
        v for k, v in m["product_ms"].items()
        if k.endswith("/" + scopes.FE_MATVEC)),
    "fe_rmatvec_job_ms": lambda m: sum(
        v for k, v in m["product_ms"].items()
        if k.endswith("/" + scopes.FE_RMATVEC)),
    "fe_score_ms": lambda m: (m["scope_ms"][scopes.FE_SCORE]
                              + m["scope_ms"][scopes.CD_OBJECTIVE]),
    "unscoped_ms": lambda m: m["unattributed_ms"],
}


def join(xplane: Path, workload: str, mean: dict, out=sys.stdout) -> dict:
    """The cell's ``per_layer`` metrics that read the block's instruction
    table, from the same ``.xplane.pb`` through the benchmark's own
    reduction and readers, beside this script's number for the same scope
    (a latent class on the kernel also runs what surrounds the call, so
    ``mf_kernel_ms`` is below the script's kernel classes)."""
    import importlib

    from benchmark import scope_seconds, trace_reduce

    ctx = {"trace": trace_reduce.reduce(trace_reduce.load(xplane))}
    found = scope_seconds.by_scope(ctx)
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    print(f"\njoin of the benchmark's op_seconds ({ctx['trace']['traced_jobs']}"
          " jobs) with the program's instruction table: "
          + ("nothing (no table, or it covers under 95%)" if found is None
             else f"coverage {100 * found['coverage']:.3f}%, total "
                  f"{found['total']:.3f} ms a job, busy "
                  f"{1e3 * ctx['trace']['busy_s'] / ctx['trace']['traced_jobs']:.3f}"),
          file=out)
    print("| metric | benchmark's reader, ms | this script, ms |", file=out)
    print("| --- | --- | --- |", file=out)
    values = {}
    for m in bench["per_layer"]:
        if m["name"] not in _JOINED or workload not in m.get(
                "workloads", [workload]):
            continue
        value = importlib.import_module(
            f"benchmark.metrics.{m['name']}").read(ctx)
        values[m["name"]] = value
        print(f"| `{m['name']}` | "
              + ("nothing" if value is None else f"{value:.3f}")
              + f" | {_JOINED[m['name']](mean):.3f} |", file=out)
    return {"metrics": values, "by_scope": found}


def disagreements(trace: dict, top: int = 12, out=sys.stdout) -> list:
    """Where the trace's own path of an operation (the profiler's ``tf_op``)
    and the program's instruction table place the same instruction name
    under different leaf scopes: ``[name, ms a job, the trace's path, the
    table's]``, the costliest first (the first chip's events)."""
    from photon_ml_tpu.utils.compile_cache import instruction_scopes

    table = instruction_scopes()
    trace = unpack(trace)
    jobs = job_spans(trace)
    plane = next(p for p in trace["planes"]
                 if p["name"].startswith(DEVICE_PLANE_PREFIXES))
    differ: Dict[tuple, float] = {}
    for name, s, d, path in (e for ln in plane["lines"]
                             if ln["name"] == OPS_LINE for e in ln["events"]):
        if not any(lo < s + d and s < hi for lo, hi, _ in jobs):
            continue
        name = short_name(name)
        if name.startswith(CONTAINER_OPS):
            continue
        name = name.lstrip("%")
        held = table.get(name)
        if held is None or place(held)["leaf"] != place(path)["leaf"]:
            key = (name, path, held)
            differ[key] = differ.get(key, 0.0) + d / 1e6 / max(len(jobs), 1)
    rows = sorted(([n, ms, path, held] for (n, path, held), ms
                   in differ.items()), key=lambda r: -r[1])
    print(f"\n{len(rows)} instruction names whose leaf scope differs between "
          f"the trace's path and the table's, {sum(r[1] for r in rows):.3f} "
          "ms a job:", file=out)
    for n, ms, path, held in rows[:top]:
        print(f"  {n} {ms:.3f} ms: trace `{path[-110:]}` | table "
              + ("holds no such name" if held is None else f"`{held[-110:]}`"),
              file=out)
    return rows


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", default="glmix.fit")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--jobs", type=int, default=3)
    ap.add_argument("--rehearse-rows", type=int, default=0)
    ap.add_argument("--gap-ms", type=float, default=0.2)
    ap.add_argument("--from-json", type=Path)
    ap.add_argument("--from-xplane", type=Path)
    ap.add_argument("--out", type=Path,
                    default=ROOT / "chiprun_out" / "trace_scopes")
    ap.add_argument("--save-trace", action="store_true")
    ap.add_argument("--cut-jobs", type=int, default=0)
    ap.add_argument("--dump-stats", type=int, default=0)
    ap.add_argument("--join", action="store_true")
    ap.add_argument("--cached", action="store_true")
    args = ap.parse_args(argv)

    xplane = None
    if args.from_json:
        trace = json.loads(args.from_json.read_text())
    else:
        args.out.mkdir(parents=True, exist_ok=True)
        xplane = args.from_xplane or trace_cell(
            args.workload, args.seed, args.jobs, args.rehearse_rows,
            args.out / "profile", args.cached)
        planes = read_xspace(xplane)
        if args.dump_stats:
            with open(args.out / "stats.txt", "w") as f:
                dump_stats(planes, args.dump_stats, out=f)
            print((args.out / "stats.txt").read_text()[:6000])
        trace = flatten_with_paths(planes)
        trace["recorded"] = {"workload": args.workload, "seed": args.seed,
                             "rehearsal": bool(args.rehearse_rows)}
        if args.save_trace:
            kept = cut_jobs(trace, args.cut_jobs) if args.cut_jobs else trace
            (args.out / "trace_with_paths.json").write_text(
                json.dumps(pack(kept), separators=(",", ":")))
    ledger = None
    if not (args.from_json or args.from_xplane):
        from photon_ml_tpu.utils.compile_cache import compile_ledger

        ledger = compile_ledger(top=12)  # what compiling cost this process
        print("compile ledger (s): " + json.dumps(ledger["totals"]),
              file=sys.stderr)
        for name, row in ledger["functions"].items():
            print(f"  {name}: trace {row['trace_s']:.2f} lower "
                  f"{row['lower_s']:.2f} backend {row['backend_s']:.2f}",
                  file=sys.stderr)
    try:
        result = reduce_scopes(trace, args.gap_ms)
    except ValueError as e:  # a CPU rehearsal has no device plane
        print(f"trace_scopes: {e}", file=sys.stderr)
        return 1
    print_report(result)
    if args.join and xplane is not None:
        result["join"] = join(xplane, args.workload, result["mean"])
        result["join"]["disagreements"] = disagreements(trace)
    if not args.from_json:
        result["compile_ledger"] = ledger
        (args.out / "scopes.json").write_text(json.dumps(result, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
