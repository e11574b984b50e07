"""One run of one cell: set-up, window, traced segment, check, result.

Driven by data: the cell, its configuration and its metrics are the
entries of ``BENCHMARK.json``; what belongs to one of them sits in a file
of its own that is found by name, so a later PR adds files and entries
and edits nothing here:

- ``workloads/<cell>.json``  the traffic mix (job kind, its parameters, limits)
- ``configs/<config>.json``  the sizes as run (``file`` in BENCHMARK.json)
- ``recipes/<recipe>.py``    ``make(config, seed) -> problem``
- ``jobs/<kind>.py``         ``build(config, workload, problem) -> job``; the
                             job kind owns its window (``job.window``)
- ``checks/<kind>.py``       ``check(problem, config, workload, window)``
- ``metrics/<metric>.py``    ``read(ctx) -> float | None``
"""

from __future__ import annotations

import importlib
import json
import shutil
import sys
import time
from pathlib import Path
from typing import Dict, List, Optional

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent
OUT = HERE / "out"
TRACED_JOBS = 3


class NoChip(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


class CompileCounts:
    """Compile requests and persistent-cache hits, from JAX's monitoring
    events (``chip_smoke.py:157``): requests - hits were compiled."""

    def __init__(self):
        import jax.monitoring

        self.requests = self.hits = 0
        jax.monitoring.register_event_listener(self._on_event)

    def _on_event(self, event: str, **_) -> None:
        if event == "/jax/compilation_cache/compile_requests_use_cache":
            self.requests += 1
        elif event == "/jax/compilation_cache/cache_hits":
            self.hits += 1

    def take(self) -> dict:
        out = {"requests": self.requests, "cache_hits": self.hits,
               "compiled": self.requests - self.hits}
        self.requests = self.hits = 0
        return out


def load_cell(name: str, bench_path: Path = ROOT / "BENCHMARK.json") -> dict:
    bench = json.loads(bench_path.read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"no workload {name!r} in {bench_path.name}: "
                         f"{sorted(cells)}")
    cell = cells[name]
    cfg_entry = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    config = json.loads((ROOT / cfg_entry["file"]).read_text())
    workload = json.loads((HERE / "workloads" / f"{name}.json").read_text())
    if workload["config"] != cell["config"]:
        raise SystemExit(f"{name}: workload file names config "
                         f"{workload['config']!r}, BENCHMARK.json "
                         f"{cell['config']!r}")
    return {"bench": bench, "cell": cell, "config": config,
            "workload": workload}


def metrics_for(bench: dict, cell_name: str, group: str) -> List[dict]:
    return [m for m in bench[group]
            if "workloads" not in m or cell_name in m["workloads"]]


def device_block(chips: int, require_chip: bool) -> dict:
    import jax

    devs = jax.local_devices()
    platform = devs[0].platform
    if require_chip and (platform == "cpu" or len(devs) < chips):
        raise NoChip(f"the cell asks for {chips} chip(s); JAX found "
                     f"{len(devs)} device(s) of platform {platform!r}")
    return {"platform": platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def memory_peak_bytes() -> Optional[int]:
    import jax

    peaks = []
    for dev in jax.local_devices():
        stats = dev.memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            peaks.append(int(stats["peak_bytes_in_use"]))
    return max(peaks) if peaks else None


def run_cell(name: str, seed: int, seconds: float, trace: bool, t0: float,
             require_chip: bool = True, rehearse_rows: int = 0) -> dict:
    """Everything one run does; returns the result line as a dict."""
    import jax

    from photon_ml_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    loaded = load_cell(name)
    bench, cell = loaded["bench"], loaded["cell"]
    config, workload = loaded["config"], loaded["workload"]
    device = device_block(int(cell["chips"]), require_chip)
    recipe = importlib.import_module(f"benchmark.recipes.{config['recipe']}")
    if rehearse_rows:
        config = recipe.scale_down(config, rehearse_rows)
    counts = CompileCounts()

    # -- set-up: data from the seed, the program's objects, one warm job ----
    phases = {"start": time.perf_counter() - t0}
    problem = recipe.make(config, seed)
    phases["data"] = time.perf_counter() - t0 - sum(phases.values())
    job = importlib.import_module(
        f"benchmark.jobs.{workload['job']}").build(config, workload, problem)
    phases["build"] = time.perf_counter() - t0 - sum(phases.values())
    job.warm_up(seed)
    phases["warm_up"] = time.perf_counter() - t0 - sum(phases.values())
    setup_compiles = counts.take()
    setup_s = time.perf_counter() - t0

    # -- the window: the job kind's own loop over the workload's traffic ------
    window = job.window(seconds, seed)
    window_compiles = counts.take()
    device["memory_peak_bytes"] = memory_peak_bytes()
    print(f"benchmark: set-up {setup_s:.3f} s; window {window['seconds']:.4f}"
          f" s, {window['attempted']} attempted", file=sys.stderr, flush=True)

    ctx = {"config": config, "workload": workload, "device": device,
           "setup_s": setup_s, "window": window,
           "steady_bytes": problem.steady_bytes(),
           "peaks": json.loads((HERE / "peaks.json").read_text()),
           "counters": None, "probes": None, "trace": None}
    breakdown = None
    if trace:
        ctx["counters"] = job.counters(window)
        traced_seed = seed + 1 + window["attempted"]
        if device["platform"] == "cpu":  # a CPU run has no device plane
            ctx["probes"] = job.traced(traced_seed, 1)
        else:
            from benchmark import trace_reduce

            job.warm_traced()
            trace_dir = OUT / f"trace-{name}"
            shutil.rmtree(trace_dir, ignore_errors=True)
            jax.profiler.start_trace(str(trace_dir))
            ctx["probes"] = job.traced(traced_seed, TRACED_JOBS)
            jax.profiler.stop_trace()
            ctx["trace"] = trace_reduce.reduce_dir(trace_dir)
            shutil.rmtree(trace_dir, ignore_errors=True)
            device["busy_s"] = ctx["trace"]["busy_s"]
            device["window_s"] = ctx["trace"]["window_s"]
            breakdown = ctx["trace"]["breakdown"]

    # -- the check: after the window, the peak read, the program freed -------
    job.after_window(window)
    routing = job.kernel_routing()
    job.release()
    del job
    check = importlib.import_module(f"benchmark.checks.{workload['job']}")
    t_chk = time.perf_counter()
    compared = check.check(problem, config, workload, window)
    check_s = time.perf_counter() - t_chk
    # Nothing may compile inside the measured window: an exact comparison.
    compared["window_compiles"] = {
        "value": float(window_compiles["compiled"]), "limit": 0.0}
    correct = all(v["value"] <= v["limit"] for v in compared.values())

    group = "per_layer" if trace else "end_to_end"
    metrics: Dict[str, dict] = {}
    if device["platform"] != "cpu":
        for m in metrics_for(bench, name, group):
            reader = importlib.import_module(f"benchmark.metrics.{m['name']}")
            value = reader.read(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": float(value),
                                      "unit": m["unit"]}
    attempted = int(window["attempted"])
    result = {"correct": bool(correct), "attempted": attempted,
              "failed": 0 if correct else attempted, "metrics": metrics,
              "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["notes"] = {
        "workload": name, "seed": int(seed), "rehearsal": bool(rehearse_rows),
        "n_rows": config["n_rows"], "window_s": window["seconds"],
        "setup_phases_s": phases,
        "setup_compiles": setup_compiles, "window_compiles": window_compiles,
        "steady_bytes": ctx["steady_bytes"], "check_s": check_s,
        "routing": routing, "counters": ctx["counters"],
        "probes": ctx["probes"],
        "probe_busy_s": (ctx["trace"] or {}).get("probe_busy_s")}
    if ctx["trace"]:  # summed seconds of the operations the workload names
        from benchmark import trace_reduce

        result["notes"]["trace_sums"] = {
            "traced_jobs": ctx["trace"]["traced_jobs"],
            **{key: trace_reduce.op_sum(ctx["trace"], prefix)
               for key, prefix in workload.get("trace_sums", {}).items()}}
    result["compared"] = compared
    return result


def print_result(result: dict) -> None:
    """Earlier lines for the reader, the compared numbers last on standard
    error, the result as the last line of standard output."""
    for name, v in result["compared"].items():
        print(f"compared {name} = {v['value']:.6g} (limit {v['limit']:.6g})",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
