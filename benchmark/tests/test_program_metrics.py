"""The two metrics read from inside the program (PR 29), on hand-made
``ctx``s: the kernel's events by their stable name, and the fused block's
tracing and lowering seconds from the compile ledger."""

import pytest

from benchmark.metrics import block_trace_lower_s, re_kernel_ms


def _trace(op_seconds, traced_jobs=3):
    return {"op_seconds": op_seconds, "traced_jobs": traced_jobs}


@pytest.mark.parametrize("ctx, expected", [
    # four buckets' kernels over three jobs, beside other operations
    ({"trace": _trace({"%pallas_entity_lbfgs.5": 0.006,
                       "%pallas_entity_lbfgs.7": 0.0102,
                       "%pallas_entity_lbfgs_tron.2": 0.009,
                       "%fusion.45": 0.0435,
                       "%not_pallas_entity_lbfgs.1": 1.0})}, 8.4),
    ({"trace": _trace({"%pallas_entity_lbfgs.1": 0.002}, traced_jobs=1)},
     2.0),
    # no kernel ran (a parent without it, every bucket on the fallback)
    ({"trace": _trace({"%fusion.45": 0.0435})}, None),
    ({"trace": _trace({"%pallas_entity_lbfgs.1": 0.002}, traced_jobs=0)},
     None),
    ({"trace": None}, None),  # a CPU run, or --trace 0
    ({}, None),
])
def test_re_kernel_ms(ctx, expected):
    got = re_kernel_ms.read(ctx)
    assert got == (None if expected is None else pytest.approx(expected))


@pytest.mark.parametrize("ledger, expected", [
    ({"functions": {"cd_block": {"trace_s": 21.5, "lower_s": 9.25,
                                 "backend_s": 7.0},
                    "_solve_block": {"trace_s": 3.0, "lower_s": 0.0,
                                     "backend_s": 0.0}},
      "totals": {}}, 30.75),
    ({"functions": {"_solve_block": {"trace_s": 3.0, "lower_s": 0.0}},
      "totals": {}}, None),  # the block never ran in this process
    ({"functions": {}, "totals": {}}, None),
])
def test_block_trace_lower_s_from_ctx(ledger, expected):
    got = block_trace_lower_s.read({"compile_ledger": ledger})
    assert got == (None if expected is None else pytest.approx(expected))


def test_block_trace_lower_s_reads_the_live_ledger():
    """Without a ledger in ``ctx`` the reader asks the program: nothing
    before a block was traced, its seconds after one."""
    import jax
    import jax.numpy as jnp

    from photon_ml_tpu.utils import compile_cache

    compile_cache._listen()
    compile_cache.reset_compile_ledger()
    assert block_trace_lower_s.read({}) is None

    @jax.jit
    def cd_block(x):
        return jnp.sum(x * 2.0)

    cd_block(jnp.ones(8)).block_until_ready()
    first = block_trace_lower_s.read({})
    assert first is not None and first > 0
    cd_block(jnp.ones(8)).block_until_ready()  # cached: nothing more
    assert block_trace_lower_s.read({}) == first


def test_block_trace_lower_s_without_a_ledger(monkeypatch):
    """A program from before the ledger: the reader returns nothing and
    does not raise."""
    from photon_ml_tpu.utils import compile_cache

    monkeypatch.delattr(compile_cache, "compile_ledger")
    assert block_trace_lower_s.read({}) is None
