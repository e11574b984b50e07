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

Beside the ledger sits the **instruction table** of a jitted function whose
owner handed over its executable (``note_instructions``): which scope path
(the ``op_name`` of the HLO metadata, which ``jax.named_scope`` writes) each
compiled instruction carries, read ONCE from the optimized HLO text of the
executable that runs, as plain strings. A profiler's device events carry an
instruction's name (``%fusion.262``); the table says whose it is
(``instruction_scopes``), so per-operation seconds summed by name resolve
to the program's own names (``telemetry.scopes.place``).
"""

from __future__ import annotations

import os
import re
import threading
import time
from pathlib import Path
from typing import Dict, List, Optional

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
# One record a kernel body traced (``note_kernel_body``), in trace order.
_kernel_bodies: List[dict] = []
# The instruction table of the executable a function's owner handed over
# last (``note_instructions``): strings and three numbers, nothing of JAX.
_instructions: Dict[str, dict] = {}


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


def note_kernel_body(kernel: str, variant: str, rows: int, width: int,
                     eqns: int) -> None:
    """The owner of a Pallas kernel says, each time it traces the kernel's
    body, the body's static shape and how many equations its jaxpr holds
    (those inside its loops included): the ledger's ``kernel_bodies``
    lists one ``{kernel, variant, rows, width, eqns}`` a trace. Tracing
    time goes with the equations, so this says what a body costs to trace
    and whether that grows with its rows."""
    with _lock:
        _kernel_bodies.append(dict(kernel=str(kernel), variant=str(variant),
                                   rows=int(rows), width=int(width),
                                   eqns=int(eqns)))


# Instructions that run the computations they name as device events of
# their own (a ``fusion``'s ``calls=`` and a reducer's ``to_apply=`` are
# insides: they never show as events).
_CONTROL_FLOW = ("while", "conditional", "call", "async-start")
_CALLED = re.compile(
    r"\b(?:body|condition|to_apply|calls|true_computation|"
    r"false_computation)=%?([\w.\-]+)")
_BRANCHES = re.compile(r"\bbranch_computations=\{([^}]*)\}")
_OP_NAME = re.compile(r'op_name="((?:[^"\\]|\\.)*)"')
# an instruction's attributes end where its ``backend_config`` begins: a
# kernel's ``custom-call`` carries its whole module there, megabytes that
# are never searched
_BACKEND_CONFIG = ", backend_config="


def _opcode_at(line: str, i: int) -> str:
    """The opcode of an instruction's text from ``i``, just behind its
    `` = ``: what follows the shape (one word, or a tuple in balanced
    parentheses) up to the operands' parenthesis."""
    if line.startswith("(", i):
        depth = 0
        for j in range(i, len(line)):
            c = line[j]
            if c == "(":
                depth += 1
            elif c == ")":
                depth -= 1
                if not depth:
                    i = j + 1
                    break
    else:
        i = line.find(" ", i)
    return line[i + 1:line.find("(", i + 1)]


def parse_instructions(hlo_text: str) -> Dict[str, tuple]:
    """``{instruction name: (opcode, op_name path)}`` of an optimized HLO
    module's text, for the instructions that can show as device events:
    those of the entry computation and of every computation a ``while``,
    ``conditional`` or ``call`` reaches from it, not the insides of fused
    computations. Names without the ``%``; where the compiler kept no path
    on an instruction, its caller's (the ``while`` whose body it sits in),
    ``""`` where that has none either."""
    computations: Dict[str, list] = {}
    entry = current = None
    for line in hlo_text.splitlines():
        if not line:
            continue
        if line[0] != " ":
            if line.endswith("{") and (line[0] == "%"
                                       or line.startswith("ENTRY")):
                is_entry = line.startswith("ENTRY")
                head = line[6:] if is_entry else line
                name = head.split(" ", 1)[0].lstrip("%")
                current = computations.setdefault(name, [])
                if is_entry:
                    entry = name
            else:
                current = None
            continue
        if current is None:
            continue
        eq = line.find(" = ")
        if eq < 0:
            continue
        name = line[:eq].split()[-1].lstrip("%")
        opcode = _opcode_at(line, eq + 3)
        end = line.find(_BACKEND_CONFIG, eq)
        path = _OP_NAME.search(line, eq, end if end >= 0 else len(line))
        called = ()
        if opcode in _CONTROL_FLOW:
            called = tuple(_CALLED.findall(line, eq))
            for group in _BRANCHES.findall(line, eq):
                called += tuple(c.strip().lstrip("%")
                                for c in group.split(","))
        current.append((name, opcode, path.group(1) if path else "", called))
    # An instruction the compiler made with no path of its own (a copy into
    # the fast memory space, its ``copy-start`` / ``copy-done``) inside a
    # loop's body belongs to whatever scope the loop does: it takes the path
    # of the instruction that runs its computation.
    table: Dict[str, tuple] = {}
    seen, stack = set(), [(entry, "")] if entry else []
    while stack:
        comp, callers_path = stack.pop()
        if comp in seen:
            continue
        seen.add(comp)
        for name, opcode, path, called in computations.get(comp, ()):
            path = path or callers_path
            table[name] = (opcode, path)
            stack.extend((c, path) for c in called)
    return table


def dispatched_executable(fun_name: str, fn, args):
    """The executable a jitted function's call with ``args`` has just
    dispatched: ``fn.lower(*args).compile()`` with THE SAME arrays, where
    tracing, lowering and compiling are each a hit in JAX's own caches
    (0.5 ms; with shapes in place of the arrays it would all run again).
    JAX still fires one zero-length trace event for the lookup: the
    ledger's row of ``fun_name`` keeps the ``traces`` and ``trace_s`` it
    had, unless a lowering happened too (then the arguments did not match
    the call's, it was a real retrace, and the row says so)."""
    name = str(fun_name)
    with _lock:
        before = dict(_functions.get(name, ()))
    compiled = fn.lower(*args).compile()
    with _lock:
        row = _functions.get(name)
        if (before and row is not None
                and row["lowerings"] == before["lowerings"]):
            _totals["trace_s"] -= row["trace_s"] - before["trace_s"]
            row.update(trace_s=before["trace_s"], traces=before["traces"])
    return compiled


def note_instructions(fun_name: str, compiled,
                      since: Optional[float] = None) -> None:
    """The owner of a jitted function hands over the executable it just
    dispatched (``fn.lower(*the call's arguments).compile()``: every step a
    cache hit): its optimized HLO text is parsed ONCE into the function's
    instruction table, ``{instruction name: op_name path}`` with each
    one's opcode beside it, and nothing else is kept: no ``Compiled``, no
    argument, no closure. The ledger's row of the name gains the counts
    ``instructions`` and ``scoped_instructions`` (those under a
    ``photon.*`` name) and ``instructions_s``, the seconds the text and its
    parse took (from ``since``, a ``time.perf_counter()`` reading, where the
    owner began earlier: the executable's lookup). A later executable under
    the same name replaces the table."""
    from photon_ml_tpu.telemetry.scopes import PREFIX

    t0 = time.perf_counter() if since is None else since
    parsed = parse_instructions(compiled.as_text())
    table = {
        "scopes": {name: path for name, (_, path) in parsed.items()},
        "opcodes": {name: opcode for name, (opcode, _) in parsed.items()},
        "scoped": sum(1 for _, path in parsed.values()
                      if PREFIX in path),
    }
    table["seconds"] = time.perf_counter() - t0
    with _lock:
        _instructions[str(fun_name)] = table


def _table_field(fun_name: str, field: str) -> Dict[str, str]:
    with _lock:
        table = _instructions.get(str(fun_name))
        return dict(table[field]) if table else {}


def instruction_scopes(fun_name: str = "cd_block") -> Dict[str, str]:
    """``{instruction name: op_name path}`` of the executable dispatched
    last under this function name (``note_instructions``), names as a
    profiler's device events carry them less the ``%`` (``fusion.262``);
    ``{}`` where none was. ``telemetry.scopes.place(path)`` resolves a
    path to the table's scopes."""
    return _table_field(fun_name, "scopes")


def instruction_opcodes(fun_name: str = "cd_block") -> Dict[str, str]:
    """``{instruction name: opcode}`` of the same table (``fusion``,
    ``custom-call``, ``all-reduce``, ...): a collective by its opcode,
    whatever JAX named the instruction."""
    return _table_field(fun_name, "opcodes")


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
    sparse fixed effect (``note_layout``); ``instructions``,
    ``scoped_instructions`` and ``instructions_s`` where its owner handed
    over its executable (``note_instructions``).
    Names are the jitted functions' (``cd_block``), as JAX reports them.
    Totals add ``cache_requests``; requests minus hits were compiled.
    ``kernel_bodies``: a record a Pallas kernel body traced
    (``note_kernel_body``), in trace order."""
    with _lock:
        rows = {k: dict(v, partitions=_partitions.get(k, 1))
                for k, v in _functions.items()}
        for k, layout in _layouts.items():
            if k in rows:
                rows[k].update(layout)
        for k, table in _instructions.items():
            if k in rows:
                rows[k].update(instructions=len(table["scopes"]),
                               scoped_instructions=table["scoped"],
                               instructions_s=table["seconds"])
        totals = dict(_totals)
        bodies = [dict(b) for b in _kernel_bodies]
    if top is not None:
        cost = lambda r: r["trace_s"] + r["lower_s"] + r["backend_s"]
        keep = sorted(rows, key=lambda k: -cost(rows[k]))[:top]
        rows = {k: rows[k] for k in keep}
    return {"functions": rows, "totals": totals, "kernel_bodies": bodies}


def reset_compile_ledger() -> None:
    with _lock:
        _functions.clear()
        _partitions.clear()
        _layouts.clear()
        _instructions.clear()
        _kernel_bodies.clear()
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
