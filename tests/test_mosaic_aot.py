"""Real-compiler tests: the main path's programs, at the shapes
``chip_smoke.py`` runs them, compiled for a described (not attached) TPU
v5e by the libtpu installed here. Interpret-mode parity cannot see what
these do: Mosaic legalization (KERNEL.md constraint #6), the scoped-VMEM
limit, device memory, and whether a kernel survives ``shard_map``.

This is THE file for such tests: only one process may load libtpu, the
suite runs under several xdist workers with ``--dist loadfile``, and a
second file would land on a worker whose fixture can only skip. The
topology is described inside the module-scoped fixture and nowhere at
import, so every worker collects the same tests.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from chip_smoke import D_FIXED, D_USER, KERNEL_MARKER, N_USERS, ROWS
from photon_ml_tpu.ops.losses import loss_for_task
from photon_ml_tpu.ops.pallas_entity_solver import (
    VMEM_GUARD_BYTES,
    entity_solver_vmem_bytes,
    pallas_entity_lbfgs,
)
from photon_ml_tpu.types import TaskType

V5E_HBM_BYTES = 16 * 10**9

# What chip_smoke.py prints at seed 0: 200k rows over 5k users with 25
# per-user features bucket to (entities, r, d) =
SMOKE_BUCKETS = [(580, 32, 32), (4419, 64, 32), (1, 128, 32)]


@pytest.fixture(scope="module")
def topo():
    from photon_ml_tpu.utils.aot import v5e_topology

    try:
        return v5e_topology("v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no libtpu here, or it is held
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def tpu_lowering():
    """The suite's x64 recurses without end when JAX lowers for the
    described chip, and the chip runs f32 anyway: x64 is off while these
    tests lower. So is the persistent compile cache, which a driver test
    earlier in this worker may have turned on: an executable compiled
    for a described chip is written to it but cannot be read back
    without one."""
    from jax.experimental.compilation_cache import compilation_cache

    was_enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        with jax.enable_x64(False):
            yield
    finally:
        jax.config.update("jax_enable_compilation_cache", was_enabled)
        compilation_cache.reset_cache()


def _struct(sharding):
    return lambda shape, dt=jnp.float32: jax.ShapeDtypeStruct(
        shape, dt, sharding=sharding)


def _kernel_args(arg, e, r, d, norm_bounds):
    args = (arg((e, r, d)), arg((e, r)), arg((e, r)), arg((e, r)),
            arg((e, d)), arg(()), arg(()))
    extra = {k: arg((e, d)) for k in
             ("factors", "shifts", "lower", "upper")} if norm_bounds else {}
    return args, extra


def _guard_extreme(which: str, norm_bounds: bool):
    """The largest (r, d) the routing guard admits, three ways: most
    rows, widest, and the highest estimate. r is a power of two (bucket
    size classes); d is a power of two >= 8 or a factored coordinate's
    latent width 4."""
    est = lambda rd: entity_solver_vmem_bytes(
        *rd, 4, normalized=norm_bounds, bounded=norm_bounds)
    admitted = [(r, d) for r in (1 << p for p in range(2, 13))
                for d in [4] + [1 << p for p in range(3, 11)]
                if est((r, d)) < VMEM_GUARD_BYTES]
    key = {"max_r": lambda rd: (rd[0], est(rd)),
           "max_d": lambda rd: (rd[1], est(rd)),
           "max_estimate": est}[which]
    return max(admitted, key=key)


def _compile_kernel(one_chip, mode, e, r, d, norm_bounds):
    args, extra = _kernel_args(_struct(one_chip), e, r, d, norm_bounds)
    fn = functools.partial(
        pallas_entity_lbfgs, loss_for_task(TaskType.LOGISTIC_REGRESSION),
        max_iter=20, tol=1e-6, mode=mode)
    return jax.jit(fn).lower(*args, **extra).compile()


def _kernel_at_smoke_bucket(one_chip, mode, norm_bounds, bucket):
    return _compile_kernel(one_chip, mode, *bucket, norm_bounds)


def _kernel_at_guard_extreme(one_chip, mode, norm_bounds, which):
    r, d = _guard_extreme(which, norm_bounds)
    return _compile_kernel(one_chip, mode, 128, r, d, norm_bounds)


def _fixed_effect_value_and_grad(one_chip):
    """The dense fixed-effect pass at 200,000 x 200."""
    from photon_ml_tpu.ops.features import DenseFeatures
    from photon_ml_tpu.ops.glm_objective import GLMBatch, GLMObjective

    arg = _struct(one_chip)
    batch = GLMBatch(DenseFeatures(arg((ROWS, D_FIXED))), arg((ROWS,)),
                     arg((ROWS,)), arg((ROWS,)))
    objective = GLMObjective(loss_for_task(TaskType.LOGISTIC_REGRESSION))
    return jax.jit(objective.value_and_grad).lower(
        arg((D_FIXED,)), batch, arg(())).compile()


def _serving_top_bucket(one_chip):
    """The serving engine's scoring kernel for the GLMix model at the top
    of its bucket ladder."""
    import scipy.sparse as sp

    from photon_ml_tpu.io.model_io import RandomEffectModelSnapshot
    from photon_ml_tpu.models import (
        Coefficients,
        FixedEffectModel,
        GameModel,
        LogisticRegressionModel,
    )
    from photon_ml_tpu.serving import StreamingGameScorer

    # The model as the scoring driver loads it from disk.
    engine = StreamingGameScorer(GameModel({
        "fixed": FixedEffectModel(LogisticRegressionModel(Coefficients(
            jnp.zeros(D_FIXED, jnp.float32))), "global"),
        "perUser": RandomEffectModelSnapshot(
            "userId", "user",
            sp.csr_matrix(np.ones((N_USERS, D_USER), np.float32)),
            np.asarray([f"user{i:05d}" for i in range(N_USERS)])),
    }, TaskType.LOGISTIC_REGRESSION))
    rows = engine.ladder.max_rows
    nnz = tuple(engine.ladder.nnz_bucket(rows * engine._shards[sid], rows)
                for sid in engine._shard_order)
    arg = _struct(one_chip)
    shard_args = tuple((arg((z,)), arg((z,), jnp.int32),
                        arg((z,), jnp.int32)) for z in nnz)
    code_args = ((), (arg((rows,), jnp.int32),))
    params = tuple(arg(p.shape, p.dtype) for p in engine._params)
    return engine._build_fn(rows, nnz).lower(
        shard_args, code_args, params).compile()


def _kernel_under_shard_map(topo):
    """The entity-sharded bucket solve (coordinates.py
    _shard_mapped_pallas_solver) on a four-device mesh: one kernel per
    device over its shard of the smoke's largest bucket."""
    from photon_ml_tpu.algorithm import coordinates
    from photon_ml_tpu.ops.glm_objective import GLMObjective
    from photon_ml_tpu.optimization.config import GLMOptimizationConfiguration

    mesh = Mesh(np.asarray(topo.devices), ("data",))
    e, r, d = 4420, 64, 32  # 4419 entities padded to the mesh extent
    s2 = _struct(NamedSharding(mesh, P("data", None)))
    s3 = _struct(NamedSharding(mesh, P("data", None, None)))
    objective = GLMObjective(loss_for_task(TaskType.LOGISTIC_REGRESSION))
    config = GLMOptimizationConfiguration.parse("20,1e-6,1.0,1.0,LBFGS,L2")
    solve = functools.partial(coordinates._shard_mapped_pallas_solver,
                              objective, config, mesh)
    return jax.jit(solve).lower(
        s3((e, r, d)), s2((e, r)), s2((e, r)), s2((e, r)),
        s2((e, d))).compile()


PLAIN, NORM_BOUNDS = False, True

CASES = [
    pytest.param(_kernel_at_smoke_bucket, (mode, nb, bucket), True,
                 id=f"kernel-{mode}{'+norm+bounds' if nb else ''}-"
                    f"{'x'.join(map(str, bucket))}")
    for mode, nb in (("lbfgs", PLAIN), ("owlqn", PLAIN), ("tron", PLAIN),
                     ("lbfgs", NORM_BOUNDS))
    for bucket in SMOKE_BUCKETS
] + [
    # The variants the compiler refused at its default 16 MiB scoped
    # VMEM, at the guard's three extremes (ops/pallas_entity_solver.py
    # VMEM_LIMIT_BYTES).
    pytest.param(_kernel_at_guard_extreme, (mode, nb, which), True,
                 id=f"kernel-{mode}{'+norm+bounds' if nb else ''}-"
                    f"guard-{which}")
    for mode, nb, which in (("lbfgs", PLAIN, "max_r"),
                            ("lbfgs", NORM_BOUNDS, "max_d"),
                            ("lbfgs", PLAIN, "max_estimate"),
                            ("owlqn", PLAIN, "max_estimate"),
                            ("lbfgs", NORM_BOUNDS, "max_estimate"))
] + [
    pytest.param(_fixed_effect_value_and_grad, (), False,
                 id="fixed-effect-value-and-grad-200000x200"),
    pytest.param(_serving_top_bucket, (), False,
                 id="serving-top-bucket"),
]


@pytest.mark.parametrize("build,args,has_kernel", CASES)
def test_compiles_for_v5e(one_chip, tpu_lowering, build, args, has_kernel):
    compiled = build(one_chip, *args)
    memory = compiled.memory_analysis()
    resident = (memory.argument_size_in_bytes + memory.output_size_in_bytes
                + memory.temp_size_in_bytes)
    assert 0 < resident < V5E_HBM_BYTES
    assert (KERNEL_MARKER in compiled.as_text()) == has_kernel


# A bucket over several grid steps, so that the windows are double-buffered
# as in a real bucket's solve (at 128 entities, one step, the compiler places
# whole operands in VMEM outside the scoped limit).
VMEM_NEED_ENTITIES = 1024


@pytest.mark.parametrize("mode,nb,which", [
    ("lbfgs", PLAIN, "max_r"), ("lbfgs", NORM_BOUNDS, "max_d"),
    ("lbfgs", PLAIN, "max_estimate")])
def test_guard_extreme_vmem_need(one_chip, tpu_lowering, monkeypatch,
                                 record_property, mode, nb, which):
    """The scoped VMEM the compiler needs for the kernel at each of the
    guard's extremes, against ``entity_solver_vmem_bytes``: the least limit,
    in eighths of the estimate, at which it compiles (recorded as
    ``vmem_need_over_estimate``). With its row loops rolled the kernel
    compiles from 1.75x (max_r), 1.5x (max_d) and 1.25x (max_estimate);
    with them unrolled it needed 3.99x, 1.72x and 1.95x. Twice the
    estimate must do, well inside ``VMEM_LIMIT_BYTES``."""
    from photon_ml_tpu.ops import pallas_entity_solver as K

    r, d = _guard_extreme(which, nb)
    estimate = entity_solver_vmem_bytes(r, d, 4, normalized=nb, bounded=nb)
    assert 2 * estimate < K.VMEM_LIMIT_BYTES

    def compiles(eighths):
        # the limit is read when the kernel is traced
        monkeypatch.setattr(K, "VMEM_LIMIT_BYTES", estimate * eighths // 8)
        K.pallas_entity_lbfgs.clear_cache()
        try:
            _compile_kernel(one_chip, mode, VMEM_NEED_ENTITIES, r, d, nb)
        except Exception as e:  # noqa: BLE001 — the compiler's refusal
            if "vmem" not in str(e).lower():
                raise
            return False
        return True

    try:
        lo, hi = 0, 16
        assert compiles(hi)
        while hi - lo > 1:
            mid = (lo + hi) // 2
            lo, hi = (lo, mid) if compiles(mid) else (mid, hi)
    finally:
        monkeypatch.undo()
        K.pallas_entity_lbfgs.clear_cache()
    record_property("vmem_need_over_estimate", hi / 8)
    print(f"r={r} d={d}: compiles from {hi / 8:.3f}x the estimate "
          f"({estimate * hi / 8 / 2**20:.2f} of {estimate / 2**20:.2f} MiB)")


def test_kernel_compiles_under_shard_map(topo, tpu_lowering):
    compiled = _kernel_under_shard_map(topo)
    assert KERNEL_MARKER in compiled.as_text()
    memory = compiled.memory_analysis()  # bytes per device
    assert 0 < memory.argument_size_in_bytes < V5E_HBM_BYTES


@pytest.fixture
def lookup_on_the_chip(monkeypatch):
    """``ops.features._lookup`` asks the attached backend (the CPU, here)
    whether to interpret its kernel: these tests lower it for the chip."""
    from photon_ml_tpu.ops import features as F

    monkeypatch.setattr(F, "_off_tpu", lambda: False)
    return F


def _cell_shape():
    """``sparse-lr.fit``'s rows, slots and columns, and the distinct
    columns each slot names at that size (a field draws ranks 1 .. card - 1;
    the last slot is the intercept)."""
    import json
    import pathlib

    config = json.loads((pathlib.Path(__file__).resolve().parent.parent
                         / "benchmark/configs/sparse-lr-criteo.json"
                         ).read_text())
    fields = config["fixed"]["fields"]
    return (config["n_rows"], len(fields) + 1, config["fixed"]["d"],
            [card - 1 for card in fields] + [1])


@pytest.mark.parametrize("width", [128, 1024, 16384, 65536])
def test_the_lookup_kernel_compiles_at_every_class(one_chip, tpu_lowering,
                                                   lookup_on_the_chip, width):
    """``_lookup`` (PR 39) over one slot of the cell's 9,168,123 rows, read
    in place from the codes of three: the lane gather, the dynamic row of
    the table and the ``uint16`` blocks pass Mosaic, the table (64 KB at
    the top class, 256 KB at the widest a ``uint16`` code names, which the
    probe reads) fits VMEM, and nothing is copied around the call."""
    F = lookup_on_the_chip
    n = _cell_shape()[0]
    s = _struct(one_chip)
    compiled = jax.jit(lambda codes, table: F._lookup(
        codes, table, jnp.ones((1,), jnp.int32))).lower(
        s((3, F._code_stride(n) // 128, 128), F._CODE_DTYPE),
        s((width,))).compile()
    assert "coded_slot_lookup" in compiled.as_text()
    assert compiled.memory_analysis().temp_size_in_bytes == 0


def test_the_coded_matvec_compiles_under_vmap(one_chip, tpu_lowering,
                                              lookup_on_the_chip):
    """A batch of vectors over one matrix (a grid of regularisation
    weights solved at once): the kernel call takes the batch as one more
    grid axis over tables, the codes and the scalar that places the slot
    unbatched, and Mosaic takes that too."""
    F = lookup_on_the_chip
    n, d, coded, classes = 300000, 50000, (0, 2, 3), (128, 2048, 16384)
    s = _struct(one_chip)
    feats = F.SlotMajorEllFeatures(
        s((4 * n,), jnp.int32), s((4 * n,)), n, d, None,
        s((len(coded), F._code_stride(n) // 128, 128), F._CODE_DTYPE),
        s((sum(classes),), jnp.int32), coded, classes)
    compiled = jax.jit(lambda f, vs: jax.vmap(f.matvec)(vs)).lower(
        feats, s((5, d))).compile()
    assert compiled.as_text().count("tpu_custom_call") >= len(coded)
    assert compiled.memory_analysis().temp_size_in_bytes < 2 ** 28


def test_the_coded_matvec_compiles_at_the_sparse_cells_shape(
        one_chip, tpu_lowering, lookup_on_the_chip):
    """``sparse-lr.fit``'s matvec: 9,168,123 rows x 40 slots, the 32 slots
    whose field has at most ``CODED_SLOT_TOP_CLASS`` values read by code
    (PR 36; by the lane-gather kernel in width classes since PR 39), each in
    its class. The compiled program's temporaries are n-vectors, its
    gathers sit in plain loops (a conditional's branch is out of the
    memory-space assignment's reach: PERF.md section 6), the gather is gone
    from the coded slots, and each of those is one kernel call."""
    import re

    F = lookup_on_the_chip
    n, k, d, named = _cell_shape()
    coded = tuple(f for f, count in enumerate(named)
                  if count <= F.CODED_SLOT_TOP_CLASS)
    classes = tuple(F._slot_class(named[f]) for f in coded)
    assert len(coded) == 32 and sum(classes) == 64768
    assert sorted(set(classes)) == [128, 512, 1024, 2048, 4096, 8192, 16384]
    s = _struct(one_chip)
    feats = F.SlotMajorEllFeatures(
        s((k * n,), jnp.int32), s((k * n,)), n, d, None,
        s((len(coded), F._code_stride(n) // 128, 128), F._CODE_DTYPE),
        s((sum(classes),), jnp.int32), coded, classes)
    compiled = jax.jit(lambda f, v: f.matvec(v)).lower(
        feats, s((d,))).compile()
    memory = compiled.memory_analysis()
    assert memory.temp_size_in_bytes < 2 ** 30
    text = compiled.as_text()
    assert " conditional(" not in text
    assert len(re.findall(r"custom_call_target=\"tpu_custom_call\"",
                          text)) == len(coded)
    gathered = sum(1 for _, _, at in F._runs(coded, k) if at is None)
    # one gather of n a run of gathered slots (the loop's body), and the
    # dictionaries' own 64,768 entries
    assert len(re.findall(rf"= f32\[{n}\][^ ]* fusion\([^)]*\), "
                          r"kind=kCustom", text)) == gathered
