"""Compile/device-time profiler for the serving executable population —
the per-kernel cost-accounting discipline of the TPU distributed
linear-algebra literature (PAPERS.md), applied to the bucket ladder.

The :class:`~photon_ml_tpu.serving.engine.ExecutableCache` already knows
every executable the process ever built; what it could not answer is
"where did the compile seconds go" and "what does one dispatch of bucket
r4096 cost on the device". This profiler records, per cache key:

- **lower wall time + static cost analysis** at build: one
  ``fn.lower(*args)`` pass (tracing only — it does NOT compile, does not
  touch the jit dispatch cache, and therefore changes no TracingGuard
  count) whose ``Lowered.cost_analysis()`` yields FLOPs / bytes-accessed
  estimates where the backend provides them;
- **first-call wall time**: the first invocation of a jitted executable
  runs trace + XLA compile synchronously before enqueueing, so timing it
  at the dispatch site is an honest compile-wall proxy with NO added
  synchronization (everything after the first call is enqueue-only);
- **per-bucket dispatch wall**: dispatch-to-settle seconds observed at
  the EXISTING ``block_until_ready`` boundary (the ``InFlightWindow``
  settle — never a new sync), per rows-bucket, mirrored into registry
  histograms ``serving.bucket.r<rows>.dispatch_seconds`` and kept in
  always-live local accumulators (like the engines' ``_stats``). With
  pipeline depth > 1 the settle may lag the device finishing, so the
  number is an upper bound on device time — the same caveat as the
  ``device_wait`` span, documented in docs/OBSERVABILITY.md.

``table()`` renders the per-bucket view served on ``/statusz`` and
written into metrics.json: per key, compile economics (lower/first-call
seconds, static FLOPs, bytes) next to steady-state dispatch statistics
(count, mean/min/max seconds). No rate is derived from the two: static
FLOPs over a dispatch-to-settle host wall is the wrong source for a
utilization (PERF.md §6); that number comes from a device trace.
"""

from __future__ import annotations

import importlib
import threading
import time
from typing import Dict, Optional

_reg = importlib.import_module("photon_ml_tpu.telemetry.registry")


def _cost_numbers(lowered) -> Dict[str, float]:
    """FLOPs / bytes-accessed from a ``jax.stages.Lowered``, where the
    backend provides them (CPU and TPU do; the estimate is
    pre-optimization HLO). Absent/failed analysis degrades to {}."""
    try:
        cost = lowered.cost_analysis()
    except Exception:  # noqa: BLE001 — cost analysis is best-effort
        return {}
    if isinstance(cost, (list, tuple)):
        cost = cost[0] if cost else None
    if not isinstance(cost, dict):
        return {}
    out = {}
    if "flops" in cost:
        out["flops"] = float(cost["flops"])
    if "bytes accessed" in cost:
        out["bytes_accessed"] = float(cost["bytes accessed"])
    return out


class ExecutableProfiler:
    """Per-key build economics + per-bucket dispatch timing for one
    :class:`ExecutableCache` population (shared across every engine on
    that cache, so a tenancy's whole executable population lands in one
    table). All local state is plain dicts under one lock — live even
    while telemetry is disabled, like the engines' ``_stats``; only the
    registry histogram mirrors go quiet."""

    def __init__(self):
        self._lock = threading.Lock()
        self._builds: Dict[str, dict] = {}
        self._dispatch: Dict[int, dict] = {}
        self._hists: Dict[int, object] = {}

    # -- build-time profiling ----------------------------------------------

    def profile_build(self, key, fn, args,
                      rows_bucket: Optional[int] = None) -> None:
        """Record one cache build: time ``fn.lower(*args)`` and harvest
        its cost analysis. Tracing-only (no XLA compile happens here; the
        first real call still compiles exactly once), so the per-key cost
        is one extra trace — small against the compile it annotates.
        ``rows_bucket`` is the key's rows component, passed structurally
        by the caller (who holds the real key tuple) so a reader can join
        builds onto dispatch rows without parsing key reprs."""
        entry = {"lower_s": None, "first_call_s": None,
                 "rows_bucket": (int(rows_bucket)
                                 if rows_bucket is not None else None)}
        t0 = time.perf_counter()
        try:
            lowered = fn.lower(*args)
            entry["lower_s"] = time.perf_counter() - t0
            entry.update(_cost_numbers(lowered))
        except Exception:  # noqa: BLE001 — profiling must not fail a build
            pass
        with self._lock:
            self._builds[repr(key)] = entry

    def record_first_call(self, key, seconds: float) -> None:
        """First-invocation wall time (trace + XLA compile + enqueue) —
        the compile-wall proxy, timed at the dispatch site with no added
        sync."""
        with self._lock:
            entry = self._builds.setdefault(
                repr(key), {"lower_s": None, "first_call_s": None,
                            "rows_bucket": None})
            entry["first_call_s"] = float(seconds)

    # -- dispatch-time profiling -------------------------------------------

    def record_dispatch(self, rows_bucket: int, seconds: float,
                        rows: int) -> None:
        """One dispatch-to-settle observation for ``rows_bucket``,
        measured at the existing ``InFlightWindow`` settle boundary."""
        rb = int(rows_bucket)
        s = float(seconds)
        with self._lock:
            d = self._dispatch.get(rb)
            if d is None:
                d = self._dispatch[rb] = {
                    "count": 0, "sum_s": 0.0, "min_s": s, "max_s": s,
                    "rows": 0}
                # Lazy per-bucket registry histogram (bounded by ladder
                # size; dynamic name — fragments stay lint-legal).
                self._hists[rb] = _reg.registry().histogram(
                    f"serving.bucket.r{rb}.dispatch_seconds")
            d["count"] += 1
            d["sum_s"] += s
            d["min_s"] = min(d["min_s"], s)
            d["max_s"] = max(d["max_s"], s)
            d["rows"] += int(rows)
            hist = self._hists[rb]
        hist.observe(s)

    # -- reporting ---------------------------------------------------------

    def table(self) -> dict:
        """The /statusz + metrics.json per-bucket compile/device-time
        table: ``builds`` (per cache key, with the static FLOPs and bytes
        of its lowering) and ``dispatch`` (per rows bucket:
        dispatch-to-settle wall statistics)."""
        with self._lock:
            builds = {k: dict(v) for k, v in self._builds.items()}
            dispatch = {k: dict(v) for k, v in self._dispatch.items()}
        out_dispatch = {}
        for rb, d in sorted(dispatch.items()):
            out_dispatch[f"r{rb}"] = {
                "rows_bucket": rb,
                "dispatches": d["count"],
                "rows": d["rows"],
                "mean_s": d["sum_s"] / d["count"] if d["count"] else None,
                "min_s": d["min_s"],
                "max_s": d["max_s"],
            }
        return {"builds": builds, "dispatch": out_dispatch}

    def reset(self) -> None:
        with self._lock:
            self._builds.clear()
            self._dispatch.clear()
            self._hists.clear()
