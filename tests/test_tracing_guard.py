"""utils/tracing_guard.py: trace counting against real jax.jit cache
sizes, budget assertions, generation-preserving tracking, and the
coordinate-descent adoption (run() asserts per-executable trace
invariants through the guard)."""

import jax
import jax.numpy as jnp
import pytest

from photon_ml_tpu.utils.tracing_guard import (
    RetraceError,
    TracingGuard,
    assert_max_retraces,
    trace_count,
)


def test_trace_count_reads_jit_cache():
    f = jax.jit(lambda x: x * 2)
    assert trace_count(f) == 0
    f(jnp.ones(3))
    assert trace_count(f) == 1
    f(jnp.ones(3))  # same shape: cached
    assert trace_count(f) == 1
    f(jnp.ones(4))  # new shape: retrace
    assert trace_count(f) == 2


def test_trace_count_rejects_plain_callables_unless_defaulted():
    with pytest.raises(TypeError, match="cache introspection"):
        trace_count(lambda x: x)
    assert trace_count(lambda x: x, default=0) == 0


def test_assert_max_retraces_single_fn():
    f = jax.jit(lambda x: x + 1)
    for n in (3, 4, 5):
        f(jnp.ones(n))
    assert_max_retraces(f, 3)
    with pytest.raises(RetraceError, match="traced 3 times, budget 2"):
        assert_max_retraces(f, 2, name="step")


def test_guard_totals_and_per_fn_budgets():
    guard = TracingGuard()
    f = guard.track("f", jax.jit(lambda x: x * 2))
    g = guard.track("g", jax.jit(lambda x: x + 1))
    f(jnp.ones(2))
    g(jnp.ones(2))
    g(jnp.ones(3))
    assert guard.counts() == {"f": 1, "g": 2}
    assert guard.total_traces() == 3
    guard.assert_max_retraces(max_total=3)
    guard.assert_max_retraces(per_fn=2)
    with pytest.raises(RetraceError, match="exceed budget"):
        guard.assert_max_retraces(max_total=2)
    with pytest.raises(RetraceError, match="per-fn trace budget"):
        guard.assert_max_retraces(per_fn=1)


def test_guard_tracking_is_cumulative_across_generations():
    """Re-tracking a name keeps the old callable's traces in the totals —
    the property that makes evict-and-rebuild regressions visible."""
    guard = TracingGuard()
    for _ in range(3):
        fn = guard.track("bucket", jax.jit(lambda x: x * 2))
        fn(jnp.ones(2))  # fresh object every time: traces once each
    assert len(guard) == 3
    assert sorted(guard.counts()) == ["bucket", "bucket#2", "bucket#3"]
    assert guard.total_traces() == 3


def test_verify_checks_declared_budgets_only():
    guard = TracingGuard()
    guard.verify()  # no budgets: no-op
    f = guard.track("f", jax.jit(lambda x: x + 1), max_traces=1)
    f(jnp.ones(2))
    guard.verify()
    f(jnp.ones(5))
    with pytest.raises(RetraceError, match="declared trace budgets"):
        guard.verify()
    guard2 = TracingGuard()
    g = guard2.track("g", jax.jit(lambda x: x + 1))
    g(jnp.ones(2))
    guard2.set_budget(1)
    guard2.verify()
    g(jnp.ones(3))
    with pytest.raises(RetraceError):
        guard2.verify()


def test_fixture_yields_fresh_guard(tracing_guard):
    assert isinstance(tracing_guard, TracingGuard)
    assert len(tracing_guard) == 0 and tracing_guard.total_traces() == 0


def test_coordinate_descent_asserts_trace_invariant_through_guard(rng):
    """The fused hot loop registers every executable with the instance's
    guard, and run() asserts each traced exactly once (shared
    infrastructure, not ad-hoc counting)."""
    import scipy.sparse as sp

    from photon_ml_tpu.algorithm.coordinates import FixedEffectCoordinate
    from photon_ml_tpu.algorithm.coordinate_descent import CoordinateDescent
    from photon_ml_tpu.data.game_data import GameDataset
    from photon_ml_tpu.optimization.config import (
        GLMOptimizationConfiguration,
    )
    from photon_ml_tpu.types import TaskType

    n, d = 40, 4
    x = rng.normal(0, 1, (n, d))
    y = (rng.random(n) < 0.5).astype(float)
    data = GameDataset.build(responses=y,
                             feature_shards={"global": sp.csr_matrix(x)},
                             ids={})
    coord = FixedEffectCoordinate(
        name="fixed", data=data, feature_shard_id="global",
        task_type=TaskType.LOGISTIC_REGRESSION,
        config=GLMOptimizationConfiguration(max_iterations=5))
    cd = CoordinateDescent({"fixed": coord},
                           TaskType.LOGISTIC_REGRESSION)
    result = cd.run(num_iterations=3, seed=0)
    assert result.model is not None
    # run() already asserted per_fn=1 internally; confirm the guard saw
    # the executable (the 3-iteration block dispatch, which traced once)
    # and the invariant holds externally.
    counts = cd.tracing_guard.counts()
    assert counts and counts["block:3"] == 1
    assert all(v <= 1 for v in counts.values())
    cd.tracing_guard.assert_max_retraces(per_fn=1)
    # A second identical run reuses every executable: no new traces.
    cd.run(num_iterations=3, seed=0)
    assert cd.tracing_guard.counts() == counts
