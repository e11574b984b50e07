"""Where the persistent XLA compilation cache lives, and what compiling cost.

One rule for every entry point (the three drivers, ``chip_smoke.py``,
``benchmark/harness.py``): ``JAX_COMPILATION_CACHE_DIR``
decides when it is set — JAX reads it itself and no directory is set in
code — and otherwise the cache is ``<checkout>/.jax_cache``. The path is
part of the cache key's lookup, so it is never derived from a temporary
name, a pid or the time: a directory that moves never hits.

The same call turns on the **compile ledger**: listeners on JAX's own
monitoring events, kept per jitted function name, read by
``compile_ledger()``. It answers "which function was traced, lowered or
compiled, and for how long" from inside the program, in set-up and
(should one happen) in a measured window. It costs a dict update per
compile event and nothing in steady state.
"""

from __future__ import annotations

import os
import threading
from pathlib import Path
from typing import Dict, Optional

_DEFAULT_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"

# JAX's event -> the ledger's (seconds, count) fields. These three carry
# ``fun_name``.
_DURATION_FIELDS = {
    "/jax/core/compile/jaxpr_trace_duration": ("trace_s", "traces"),
    "/jax/core/compile/jaxpr_to_mlir_module_duration":
        ("lower_s", "lowerings"),
    "/jax/core/compile/backend_compile_duration": ("backend_s", "compiles"),
}
_RETRIEVAL_EVENT = "/jax/compilation_cache/cache_retrieval_time_sec"
_COUNT_FIELDS = {
    "/jax/compilation_cache/compile_requests_use_cache": "cache_requests",
    "/jax/compilation_cache/cache_hits": "cache_hits",
}


def _new_totals() -> Dict[str, float]:
    return {"trace_s": 0.0, "lower_s": 0.0, "backend_s": 0.0,
            "retrieval_s": 0.0, "cache_requests": 0, "cache_hits": 0}


_lock = threading.Lock()
_listening = False
_functions: Dict[str, Dict[str, float]] = {}
_totals = _new_totals()
# Retrieval seconds and hits seen since the last backend-compile event: JAX
# reports them without a function name, inside that function's
# backend-compile span, which ends (and names the function) after them.
_unclaimed = {"retrieval_s": 0.0, "cache_hits": 0}
# Devices a function's program is partitioned over, where its owner said so
# (``note_partitions``): JAX's events carry a function's name and no more.
_partitions: Dict[str, int] = {}
_layouts: Dict[str, dict] = {}


def _function_name(fun_name) -> str:
    """JAX names the trace event ``cd_block`` and the lowering and compile
    events ``jit(cd_block)``: one row for both."""
    name = str(fun_name) if fun_name else "?"
    if name.endswith(")") and "(" in name:
        name = name[name.index("(") + 1:-1]
    return name


def _on_duration(event: str, duration: float, **kw) -> None:
    fields = _DURATION_FIELDS.get(event)
    if fields is None and event != _RETRIEVAL_EVENT:
        return
    with _lock:
        if fields is None:
            _totals["retrieval_s"] += duration
            _unclaimed["retrieval_s"] += duration
            return
        field, count = fields
        _totals[field] += duration
        row = _functions.setdefault(_function_name(kw.get("fun_name")), {
            "trace_s": 0.0, "lower_s": 0.0, "backend_s": 0.0,
            "retrieval_s": 0.0, "traces": 0, "lowerings": 0,
            "compiles": 0, "cache_hits": 0})
        row[field] += duration
        row[count] += 1
        if field == "backend_s":
            row["retrieval_s"] += _unclaimed["retrieval_s"]
            row["cache_hits"] += _unclaimed["cache_hits"]
            _unclaimed.update(retrieval_s=0.0, cache_hits=0)


def _on_event(event: str, **_kw) -> None:
    field = _COUNT_FIELDS.get(event)
    if field is None:
        return
    with _lock:
        _totals[field] += 1
        if field == "cache_hits":
            _unclaimed["cache_hits"] += 1


def note_partitions(fun_name: str, partitions: int) -> None:
    """The owner of a jitted function whose arguments lie over a device
    mesh says over how many devices: the ledger's row of that name reads
    ``partitions`` (1 for every function nobody spoke for)."""
    with _lock:
        _partitions[str(fun_name)] = int(partitions)


def note_layout(fun_name: str, layout: str, coded_slots: int = 0,
                coded_entries: int = 0) -> None:
    """The owner of a jitted function that runs a sparse fixed effect says
    in which layout the chooser put its matrix, how many of a row's slots
    that layout reads by code and how many entries their padded tables
    hold: the ledger's row of that name reads ``fe_layout``,
    ``fe_coded_slots`` and ``fe_coded_entries`` (no such keys where nobody
    spoke)."""
    with _lock:
        _layouts[str(fun_name)] = dict(
            fe_layout=str(layout), fe_coded_slots=int(coded_slots),
            fe_coded_entries=int(coded_entries))


def compile_ledger(top: Optional[int] = None) -> dict:
    """``{"functions": {name: row}, "totals": {...}}`` since the process
    began listening (``enable_compile_cache``), or since ``reset``; with
    ``top``, only that many functions, those that took longest.

    A row: ``trace_s`` (Python to jaxpr; a function traced inside another
    is in both rows), ``lower_s`` (jaxpr to MLIR module), ``backend_s``
    (XLA compile, or the persistent cache's lookup and executable load
    where it hit: ``retrieval_s`` and ``cache_hits`` are that part), and
    how often each happened (``traces``, ``lowerings``, ``compiles``),
    and ``partitions``, the devices the program was lowered for
    (``note_partitions``; 1 where nobody said); ``fe_layout``,
    ``fe_coded_slots`` and ``fe_coded_entries`` where the function runs a
    sparse fixed effect (``note_layout``).
    Names are the jitted functions' (``cd_block``), as JAX reports them.
    Totals add ``cache_requests``; requests minus hits were compiled."""
    with _lock:
        rows = {k: dict(v, partitions=_partitions.get(k, 1))
                for k, v in _functions.items()}
        for k, layout in _layouts.items():
            if k in rows:
                rows[k].update(layout)
        totals = dict(_totals)
    if top is not None:
        cost = lambda r: r["trace_s"] + r["lower_s"] + r["backend_s"]
        keep = sorted(rows, key=lambda k: -cost(rows[k]))[:top]
        rows = {k: rows[k] for k in keep}
    return {"functions": rows, "totals": totals}


def reset_compile_ledger() -> None:
    with _lock:
        _functions.clear()
        _partitions.clear()
        _layouts.clear()
        _totals.update(_new_totals())
        _unclaimed.update(retrieval_s=0.0, cache_hits=0)


def _listen() -> None:
    global _listening
    with _lock:
        if _listening:
            return
        _listening = True
    import jax.monitoring

    jax.monitoring.register_event_duration_secs_listener(_on_duration)
    jax.monitoring.register_event_listener(_on_event)


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory.

    Every executable is cached, however quickly it compiled, so a second
    run of the same command in the same checkout compiles nothing it
    compiled before. Also starts the compile ledger (idempotent)."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(_DEFAULT_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    _listen()
    return path
