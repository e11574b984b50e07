"""The benchmark's own tests run on the CPU at tiny sizes, in float32 as
the chip computes (no x64: the cells are float32 configurations)."""

import os
import sys
from pathlib import Path

os.environ["JAX_PLATFORMS"] = "cpu"

ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_enable_x64", False)
