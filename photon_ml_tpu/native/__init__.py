"""Native (C) runtime components, compiled on first use and cached.

The only native-ish dependency of the reference is BLAS-under-Breeze plus
PalDB (SURVEY §2 preamble) — its decode hot path runs on the JVM. Here the
device math is XLA; the host-side ingest is where native code pays, so the
Avro datum decoder is a C extension (_avro_native.c). If it cannot be
built or loaded the pure-python codec is used, and a warning says so once
per process.

Set PHOTON_ML_TPU_NO_NATIVE=1 to force the pure-python paths.
"""

from __future__ import annotations

import importlib.util
import logging
import os
import subprocess
import sysconfig
import tempfile
from pathlib import Path
from typing import Optional

logger = logging.getLogger(__name__)

_NATIVE_DIR = Path(__file__).resolve().parent
_loaded = False
_module = None


def _compile(src: Path, out: Path) -> bool:
    cc = sysconfig.get_config_var("CC") or "cc"
    include = sysconfig.get_paths()["include"]
    out.parent.mkdir(parents=True, exist_ok=True)
    # Each builder writes a file of its own and renames it into place:
    # processes that find no .so at the same moment (test workers, decoder
    # workers after chip_smoke.py removed _build/) race harmlessly.
    fd, tmp = tempfile.mkstemp(dir=out.parent, suffix=out.suffix + ".tmp")
    os.close(fd)
    cmd = [cc.split()[0], "-O2", "-shared", "-fPIC", f"-I{include}",
           str(src), "-o", tmp]
    try:
        res = subprocess.run(cmd, capture_output=True, text=True,
                             timeout=120)
        if res.returncode != 0:
            logger.warning("native build failed:\n%s", res.stderr)
            return False
        os.replace(tmp, out)
        return True
    except (OSError, subprocess.TimeoutExpired) as e:
        logger.warning("native build failed to launch: %s", e)
        return False
    finally:
        if os.path.exists(tmp):
            os.remove(tmp)


def load_avro_native() -> Optional[object]:
    """The compiled _avro_native module, or None when unavailable."""
    global _loaded, _module
    if _loaded:
        return _module
    _loaded = True
    if os.environ.get("PHOTON_ML_TPU_NO_NATIVE") == "1":
        return None
    src = _NATIVE_DIR / "_avro_native.c"
    suffix = sysconfig.get_config_var("EXT_SUFFIX") or ".so"
    so = _NATIVE_DIR / "_build" / f"_avro_native{suffix}"
    try:
        if (not so.exists()
                or so.stat().st_mtime < src.stat().st_mtime):
            if not _compile(src, so):
                return None
        spec = importlib.util.spec_from_file_location(
            "photon_ml_tpu.native._avro_native", so)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        _module = mod
        logger.debug("native avro decoder loaded from %s", so)
    except Exception as e:  # noqa: BLE001 — fall back to pure python
        logger.warning("native avro decoder unavailable, decoding in pure "
                       "python: %s", e)
        _module = None
    return _module
