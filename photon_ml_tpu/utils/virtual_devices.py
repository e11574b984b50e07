"""Child-process environments with a forced virtual CPU device count.

Device-count behavior (``--mesh-devices`` on an N-chip host) can only be
exercised by a jax whose TOTAL device count is N, and
``--xla_force_host_platform_device_count`` must land in XLA_FLAGS before
jax initializes — so the ``multi_device`` pytest fixture
(tests/conftest.py) and tests/test_mesh_fold.py spawn subprocesses with
this environment. One builder keeps the scrub-and-append rules from
drifting between them.
"""

from __future__ import annotations

import os
import re
from typing import Dict, Optional

_FORCE_COUNT_RE = re.compile(
    r"--xla_force_host_platform_device_count=\d+")


def forced_cpu_device_env(n_devices: int,
                          base_env: Optional[Dict[str, str]] = None
                          ) -> Dict[str, str]:
    """A copy of ``base_env`` (default: a snapshot of os.environ) whose
    child jax will see EXACTLY ``n_devices`` virtual CPU devices: any
    inherited device-count force is scrubbed from XLA_FLAGS (the test
    harness pins 8), the new count appended, and the platform pinned
    to cpu."""
    env = dict(os.environ if base_env is None else base_env)
    flags = _FORCE_COUNT_RE.sub("", env.get("XLA_FLAGS", "")).strip()
    env["XLA_FLAGS"] = (
        f"{flags} --xla_force_host_platform_device_count="
        f"{int(n_devices)}").strip()
    env["JAX_PLATFORMS"] = "cpu"
    return env
