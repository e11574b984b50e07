"""Profiler integration (SURVEY §5: the reference relies on the Spark UI;
the TPU build's counterpart is jax.profiler traces viewable in
XProf/TensorBoard, plus the per-phase wall timers in utils/timer.py)."""

from __future__ import annotations

import contextlib
import json
from pathlib import Path
from typing import Optional

#: Written beside the trace's ``.xplane.pb``: the fused block's instruction
#: table, to look a device event's name up in.
INSTRUCTION_SCOPES_FILE = "instruction_scopes.json"


def maybe_trace(trace_dir: Optional[str]):
    """Context manager: a jax.profiler trace written to ``trace_dir`` when
    set, a no-op otherwise. Drivers wrap their train phase with this.

    Where a fit's fused block ran by the time the trace ends, the directory
    the profiler wrote (``<trace_dir>/plugins/profile/<time>/``) also gets
    ``instruction_scopes.json``: ``{"function": "cd_block", "scopes":
    {instruction name: op_name path}, "opcodes": {...}}`` of the block
    dispatched last (``utils.compile_cache.instruction_scopes``), so that
    ``fusion.29`` in a viewer can be looked up as
    ``.../photon.cd.perUser/.../photon.re.scatter/...``."""
    if not trace_dir:
        return contextlib.nullcontext()
    return _trace_with_scopes(Path(trace_dir))


@contextlib.contextmanager
def _trace_with_scopes(trace_dir: Path):
    import jax

    try:
        with jax.profiler.trace(str(trace_dir)):
            yield
    finally:
        write_instruction_scopes(trace_dir)


def write_instruction_scopes(trace_dir: Path) -> Optional[Path]:
    """The block's instruction table as ``instruction_scopes.json`` in the
    newest run directory under ``trace_dir`` (``trace_dir`` itself where the
    profiler made none); nothing where no block was dispatched."""
    from photon_ml_tpu.telemetry import scopes
    from photon_ml_tpu.utils import compile_cache

    table = compile_cache.instruction_scopes(scopes.CD_BLOCK)
    if not table:
        return None
    runs = sorted(p for p in Path(trace_dir).glob("plugins/profile/*")
                  if p.is_dir())
    out = (runs[-1] if runs else Path(trace_dir)) / INSTRUCTION_SCOPES_FILE
    out.parent.mkdir(parents=True, exist_ok=True)
    out.write_text(json.dumps({
        "function": scopes.CD_BLOCK, "scopes": table,
        "opcodes": compile_cache.instruction_opcodes(scopes.CD_BLOCK)}))
    return out
