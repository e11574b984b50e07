"""Test harness: emulate an 8-device TPU mesh on CPU.

The analog of the reference's Spark `local[4]` integration harness
(photon-test-utils/.../SparkTestUtils.scala:191): the same sharding /
collective code paths run on 8 virtual CPU devices, so multi-chip logic is
exercised without TPU hardware. Must run before jax initializes — hence the
env mutation at import time of this conftest.

f64 is enabled so golden-value tests can run at Breeze-like precision; device
code paths stay dtype-polymorphic and run f32/bf16 on real TPU.
"""

import os

# Force CPU for tests even on a machine whose JAX defaults to a TPU:
# unit/integration tiers need f64 and 8 virtual devices.
os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()

# Second CI configuration (SURVEY hard-part 3): PHOTON_ML_TPU_TEST_F32=1
# runs the suite WITHOUT x64 — every array stays f32, the dtype the real
# TPU executes. tests/test_f32_parity.py asserts f32-vs-f64 agreement of
# optimizer outcomes regardless of mode.
_F32_MODE = os.environ.get("PHOTON_ML_TPU_TEST_F32") == "1"
if not _F32_MODE:
    os.environ.setdefault("JAX_ENABLE_X64", "1")

# Plugins (flax/chex) may have imported jax before this conftest ran, in which
# case the env vars above were read too late — re-apply through jax.config
# (safe while the backend is uninitialized).
import jax

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_enable_x64", not _F32_MODE)

import numpy as np
import pytest

assert jax.device_count() == 8, (
    f"test harness expected 8 virtual CPU devices, got {jax.device_count()}"
)

F32_MODE = _F32_MODE

# dtype-aware golden tolerances: f32 carries ~7 significant digits, so
# equality/closed-form assertions that demand 1e-12 in the f64 config get
# a calibrated bound in the f32 config instead of a false failure.
GOLD_RTOL = 1e-5 if F32_MODE else 1e-12
SOLVE_RTOL = 2e-3 if F32_MODE else 1e-5  # optimizer-vs-optimum agreement


def gold(rtol: float, f32_floor: float = None) -> float:
    """A test's f64-calibrated tolerance, floored at the f32 bound when the
    suite runs in the PHOTON_ML_TPU_TEST_F32=1 config."""
    if not F32_MODE:
        return rtol
    return max(rtol, f32_floor if f32_floor is not None else GOLD_RTOL)


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "needs_f64: test depends on double precision (finite differences, "
        "sub-1e-8 golden values) and is skipped in the f32 CI config")
    config.addinivalue_line(
        "markers",
        "native_decoder: test exercises the native C Avro decoder "
        "(photon_ml_tpu/native/_avro_native.c) and is skipped cleanly "
        "when the extension is unbuilt (no C compiler) or disabled via "
        "PHOTON_ML_TPU_NO_NATIVE=1")
    config.addinivalue_line(
        "markers",
        "slow: heavyweight test — forced-device subprocess suites "
        "(full jax-init training-driver children) and the longest "
        "solver-parity sweeps whose cheaper siblings keep the "
        "coverage; excluded from the tier-1 `-m 'not slow'` budget "
        "run, still runs in full CI (ROADMAP.md §verify)")


def _native_decoder_available() -> bool:
    from photon_ml_tpu.native import load_avro_native

    native = load_avro_native()
    return native is not None and hasattr(native, "decode_training_block")


def pytest_collection_modifyitems(config, items):
    if any("native_decoder" in item.keywords for item in items) \
            and not _native_decoder_available():
        skip_native = pytest.mark.skip(
            reason="native C avro decoder unavailable (extension unbuilt "
                   "or PHOTON_ML_TPU_NO_NATIVE=1)")
        for item in items:
            if "native_decoder" in item.keywords:
                item.add_marker(skip_native)
    if not F32_MODE:
        return
    skip = pytest.mark.skip(
        reason="requires f64 (PHOTON_ML_TPU_TEST_F32=1 config)")
    for item in items:
        if "needs_f64" in item.keywords:
            item.add_marker(skip)


@pytest.fixture
def rng():
    return np.random.default_rng(20260729)


@pytest.fixture
def multi_device():
    """Run a python snippet under a jax that sees EXACTLY ``n_devices``
    virtual CPU devices, in a fresh subprocess (``XLA_FLAGS
    --xla_force_host_platform_device_count`` must land before jax
    initializes — the tests/multihost_worker.py pattern). This harness
    process is pinned to 8 virtual devices, so total-device-count
    behavior (``--mesh-devices`` on an N-chip host) is only testable in
    a child; the fixture SKIPS (never fails) when a child cannot be
    spawned at all — constrained sandboxes — and raises with the
    child's output on a genuine in-child failure.

    Usage::

        def test_x(multi_device):
            proc = multi_device(2, "import jax; print(jax.device_count())")
            assert proc.stdout.strip() == "2"
    """
    import subprocess
    import sys

    from photon_ml_tpu.utils.virtual_devices import forced_cpu_device_env

    repo_root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

    def run(n_devices: int, code: str, timeout: float = 600.0,
            env: dict = None) -> "subprocess.CompletedProcess":
        child_env = forced_cpu_device_env(n_devices, os.environ)
        child_env.update(env or {})
        try:
            proc = subprocess.run(
                [sys.executable, "-c", code], env=child_env,
                capture_output=True, text=True, timeout=timeout,
                cwd=repo_root)
        except subprocess.TimeoutExpired as exc:
            raise AssertionError(
                f"{n_devices}-device subprocess hung past {timeout}s:\n"
                f"STDOUT:\n{exc.stdout}\nSTDERR:\n{exc.stderr}") from exc
        except (OSError, subprocess.SubprocessError) as exc:
            pytest.skip(
                f"cannot spawn a {n_devices}-device subprocess: {exc!r}")
        if proc.returncode != 0:
            raise AssertionError(
                f"{n_devices}-device subprocess failed "
                f"(rc={proc.returncode}):\nSTDOUT:\n{proc.stdout}\n"
                f"STDERR:\n{proc.stderr}")
        return proc

    return run


@pytest.fixture
def tracing_guard():
    """Shared retrace-guard fixture (utils/tracing_guard.py): yields a
    fresh TracingGuard; budgets a test declares (track(..., max_traces=)
    or set_budget(total)) are verified at teardown, so a compile-count
    regression fails the test even without an explicit assert."""
    from photon_ml_tpu.utils.tracing_guard import TracingGuard

    guard = TracingGuard()
    yield guard
    guard.verify()
