"""Two-process jax.distributed test on localhost.

The reference proves its multi-node paths with Spark local[4]
(photon-test-utils/.../SparkTestUtils.scala:55-70) — threads standing in
for executors. The analog here is stronger: two REAL processes, each with
2 virtual CPU devices, joined through jax.distributed's coordination
service into one 4-device mesh, exercising initialize_multihost's
coordinator path, cross-process array assembly, and a cross-host psum.
"""

import os
import socket
import subprocess
import sys
from pathlib import Path

WORKER = Path(__file__).parent / "multihost_worker.py"


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def test_two_process_multihost():
    port = _free_port()
    procs = []
    for pid in (0, 1):
        env = dict(
            os.environ,
            COORDINATOR_ADDRESS=f"127.0.0.1:{port}",
            NUM_PROCESSES="2",
            PROCESS_ID=str(pid),
            PYTHONPATH=str(WORKER.parent.parent),
        )
        # The conftest's own env (single-process 8-device) must not leak in.
        env.pop("XLA_FLAGS", None)
        env.pop("PHOTON_ML_TPU_TEST_F32", None)
        procs.append(subprocess.Popen(
            [sys.executable, str(WORKER)], env=env,
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True))

    outputs = []
    try:
        for p in procs:
            out, _ = p.communicate(timeout=240)
            outputs.append(out)
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()

    for pid, (p, out) in enumerate(zip(procs, outputs)):
        assert p.returncode == 0, (
            f"worker {pid} failed (rc={p.returncode}):\n{out}")
        assert f"MULTIHOST_OK process={pid} total=28.0" in out, out
