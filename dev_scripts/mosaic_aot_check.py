"""Deviceless Mosaic compile check: every Pallas kernel variant is
AOT-compiled for TPU v5e with the LOCAL libtpu compiler — no chip, no
interpret-mode proxy.

`jax.experimental.topologies.get_topology_desc("v5e:2x2")` builds a
compile-only PJRT client from the libtpu bundled in this image, and
`jax.jit(...).lower(...).compile()` against its abstract devices runs
the REAL Mosaic lowering + TPU backend compile. Interpret-mode parity
proves semantics, not
that Mosaic legalizes the kernel (it immediately caught a real one:
vector-valued `scf.if` from the line-search tail's `lax.cond` fails to
legalize — now KERNEL.md constraint #6, fixed as a 0/1-trip
while_loop).

Run after any kernel change (and in CI-like gates):
    python dev_scripts/mosaic_aot_check.py            # all variants
    python dev_scripts/mosaic_aot_check.py lbfgs owlqn # name filter

Exit 0 iff every selected variant compiles. This does NOT execute
anything (abstract devices) — chip_validation.py remains the on-chip
run gate; this is the compile gate.
"""

from __future__ import annotations

import functools
import os
import sys
import time


def main() -> int:
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import jax
    import numpy as np

    jax.config.update("jax_platforms", "cpu")
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec

    from photon_ml_tpu.ops.losses import loss_for_task
    from photon_ml_tpu.ops.pallas_entity_solver import pallas_entity_lbfgs
    from photon_ml_tpu.types import TaskType
    from photon_ml_tpu.utils.aot import v5e_topology

    topo = v5e_topology()
    sh = NamedSharding(Mesh(np.array(topo.devices[:1]), ("x",)),
                       PartitionSpec())

    def arg(shape, dt=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dt, sharding=sh)

    log_loss = loss_for_task(TaskType.LOGISTIC_REGRESSION)
    poi_loss = loss_for_task(TaskType.POISSON_REGRESSION)
    e, r, d = 256, 8, 6
    base = (arg((e, r, d)), arg((e, r)), arg((e, r)), arg((e, r)),
            arg((e, d)), arg(()), arg(()))
    norm = dict(factors=arg((e, d)), shifts=arg((e, d)))
    bnds = dict(lower=arg((e, d)), upper=arg((e, d)))

    variants = [
        ("lbfgs", log_loss, "lbfgs", {}),
        ("owlqn", log_loss, "owlqn", {}),
        ("tron", poi_loss, "tron", {}),
        ("lbfgs+norm", log_loss, "lbfgs", dict(norm)),
        ("lbfgs+bounds", log_loss, "lbfgs", dict(bnds)),
        ("lbfgs+norm+bounds", log_loss, "lbfgs", dict(**norm, **bnds)),
        ("owlqn+norm", log_loss, "owlqn", dict(norm)),
        ("tron+norm", poi_loss, "tron", dict(norm)),
        ("tron+bounds", poi_loss, "tron", dict(bnds)),
        ("tron+norm+bounds", poi_loss, "tron", dict(**norm, **bnds)),
    ]
    selected = sys.argv[1:]
    failures = []
    ran = [0]

    def run_group(checks):
        """Shared check runner: time each (name, thunk), print one line,
        record failures (exit-code accounting happens at the end)."""
        for name, thunk in checks:
            ran[0] += 1
            t0 = time.perf_counter()
            try:
                thunk()
                print(f"{name:28s}: MOSAIC COMPILE OK "
                      f"({time.perf_counter() - t0:.1f}s)", flush=True)
            except Exception as ex:  # noqa: BLE001
                failures.append(name)
                first = str(ex).strip().splitlines()
                print(f"{name:28s}: FAILED — "
                      f"{first[0][:160] if first else ex}", flush=True)
    def variant_checks():
        for name, loss, mode, kw in variants:
            if selected and not any(s in name for s in selected):
                continue
            fn = functools.partial(pallas_entity_lbfgs, loss, max_iter=15,
                                   tol=1e-6, mode=mode)
            yield name, functools.partial(
                lambda fn_, kw_: jax.jit(fn_).lower(*base, **kw_).compile(),
                fn, kw)

    run_group(variant_checks())
    # Multi-chip compiles: the SAME paths the virtual-CPU dryrun executes,
    # but compiled for a real v5e 2x2 slice — XLA lowers the sharding
    # annotations to actual ICI collectives, something no CPU mesh can
    # certify.
    def shard_checks():
        from photon_ml_tpu.ops.features import DenseFeatures
        from photon_ml_tpu.ops.glm_objective import GLMBatch, GLMObjective
        from photon_ml_tpu.optimization.convergence import OptimizerResult
        from photon_ml_tpu.optimization.glm_lbfgs import minimize_lbfgs_glm

        mesh4 = Mesh(np.array(topo.devices), ("data",))

        def marg(shape, spec, dt=jnp.float32):
            return jax.ShapeDtypeStruct(
                shape, dt, sharding=NamedSharding(mesh4, spec))

        s2, s3 = PartitionSpec("data", None), PartitionSpec("data", None,
                                                            None)
        out_specs = OptimizerResult(
            x=s2, value=PartitionSpec("data"),
            grad_norm=PartitionSpec("data"),
            iterations=PartitionSpec("data"), reason=PartitionSpec("data"),
            value_history=None, grad_norm_history=None, coef_history=None)
        kfn = functools.partial(pallas_entity_lbfgs, log_loss, max_iter=15,
                                tol=1e-6, mode="lbfgs")
        sharded_kernel = jax.shard_map(
            lambda x, y, o, w, c0: kfn(x, y, o, w, c0, 1.0), mesh=mesh4,
            in_specs=(s3, s2, s2, s2, s2),
            out_specs=out_specs, check_vma=False)
        ep = 4 * 256
        yield "kernel@shard_map(4 chips)", lambda: jax.jit(
            sharded_kernel).lower(
                marg((ep, r, d), s3), marg((ep, r), s2), marg((ep, r), s2),
                marg((ep, r), s2), marg((ep, d), s2)).compile()

        obj = GLMObjective(log_loss)
        n, dfe = 1024, 64
        dp = PartitionSpec("data")
        batch = GLMBatch(
            DenseFeatures(marg((n, dfe), s2)), marg((n,), dp),
            marg((n,), dp), marg((n,), dp))
        fe_fn = functools.partial(minimize_lbfgs_glm, obj, l2_weight=1.0,
                                  max_iter=20, tol=0.0)
        yield "fe_lbfgs@dp(4 chips)", lambda: jax.jit(
            lambda b, x0: fe_fn(b, x0)).lower(
                batch, marg((dfe,), PartitionSpec())).compile()

        # Feature-dimension ("model") sharding on a 2x2 (data x model)
        # mesh: coefficient columns sharded, margins all-reduced over ICI.
        mesh22 = Mesh(np.array(topo.devices).reshape(2, 2),
                      ("data", "model"))

        def marg22(shape, spec, dt=jnp.float32):
            return jax.ShapeDtypeStruct(
                shape, dt, sharding=NamedSharding(mesh22, spec))

        batch22 = GLMBatch(
            DenseFeatures(marg22((n, dfe), PartitionSpec("data", "model"))),
            marg22((n,), PartitionSpec("data")),
            marg22((n,), PartitionSpec("data")),
            marg22((n,), PartitionSpec("data")))
        yield "fe_lbfgs@dpxmp(2x2 chips)", lambda: jax.jit(
            lambda b, x0: fe_fn(b, x0)).lower(
                batch22, marg22((dfe,), PartitionSpec("model"))).compile()

        # A full v5e-16 slice (4x4): the composed data x model mesh at
        # the largest single-host v5e topology — collectives lower for
        # a 16-chip ICI ring, not just the 4-chip square. Topology
        # creation happens INSIDE the thunk so a libtpu that rejects
        # the name records as this one check failing, not a gate crash.
        def check_4x4():
            topo16 = v5e_topology("v5e:4x4")
            mesh44 = Mesh(np.array(topo16.devices).reshape(4, 4),
                          ("data", "model"))

            def marg44(shape, spec, dt=jnp.float32):
                return jax.ShapeDtypeStruct(
                    shape, dt, sharding=NamedSharding(mesh44, spec))

            batch44 = GLMBatch(
                DenseFeatures(marg44((n, dfe),
                                     PartitionSpec("data", "model"))),
                marg44((n,), PartitionSpec("data")),
                marg44((n,), PartitionSpec("data")),
                marg44((n,), PartitionSpec("data")))
            return jax.jit(lambda b, x0: fe_fn(b, x0)).lower(
                batch44, marg44((dfe,), PartitionSpec("model"))).compile()

        yield "fe_lbfgs@dpxmp(4x4 chips)", check_4x4

    # Gather-wall candidates (docs/SCALE.md): the two Pallas candidates
    # and the XLA one-hot scan, compiled at the d=2M bench geometry.
    # Compile certainty here; the integrate-or-close decision still needs
    # chip TIMING (chip_validation.py runs them).
    def gather_checks():
        sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
        from gather_experiments import (
            BLOCK,
            build_onehot_call,
            build_residue_call,
        )

        d_g, m_g = 2_000_000, 12_000_000
        kb = -(-d_g // BLOCK)
        e_g = -(-m_g // kb)  # balanced per-block count
        f_oh, ep, kbp = build_onehot_call(kb, e_g)
        yield "gather:pallas_onehot", lambda: jax.jit(
            lambda l, m_, wp: f_oh(l, m_, wp)).lower(
                arg((kbp, ep), jnp.int32), arg((kbp, ep)),
                arg((kbp, BLOCK))).compile()

        # The residue dynamic_gather candidate is compiler-capped: the
        # gather dim must fit ONE source vreg (8 f32 sublanes -> tables
        # of <=1024 elements), so it can only compile at tiny d. Verify
        # the cap from both sides: a=8 must compile, the d=2M geometry
        # must fail with 'Multiple source vregs'.
        f_small = build_residue_call(4, 8, 128, jnp.float32)
        yield "gather:residue(d=1024 cap)", lambda: jax.jit(
            lambda wt, i: f_small(wt, i)).lower(
                arg((8, 128)), arg((4, 8, 128), jnp.int32)).compile()

        def residue_big_must_fail():
            a_g = -(-d_g // 128)
            chunks = -(-(m_g // 128) // a_g)
            f_rg = build_residue_call(chunks, a_g, 128, jnp.float32)
            try:
                jax.jit(lambda wt, i: f_rg(wt, i)).lower(
                    arg((a_g, 128)), arg((chunks, a_g, 128),
                                         jnp.int32)).compile()
            except Exception as ex:  # noqa: BLE001
                if "Multiple source vregs" in str(ex):
                    return  # the documented architectural cap holds
                raise
            raise AssertionError(
                "residue gather at d=2M unexpectedly compiled — revisit "
                "SCALE.md's impossibility note")

        yield "gather:residue(d=2M is capped)", residue_big_must_fail

    if not selected or any("gather".startswith(s) for s in selected):
        run_group(gather_checks())

    # Sort-permutation sparse layout (docs/SCALE.md §Attacking the
    # gather wall): both products compile for v5e at the d=2M bench
    # geometry — a ~12M-element (i32, f32) lax.sort per pass plus the
    # broadcast expansions and fixed-width reductions. Compile certainty
    # here; the integrate-or-close decision needs the chip sort RATE
    # (dev_scripts/sort_primitives.py).
    def sortperm_checks():
        from photon_ml_tpu.ops.features import SortPermuteEllFeatures

        n_r, d_c, w_r = 250_000, 2_000_000, 48
        col_groups = [(1_500_000, 7), (500_000, 4)]
        p = max(n_r * w_r, sum(ng * wg for ng, wg in col_groups))
        feats = SortPermuteEllFeatures(
            row_vals=(arg((n_r, w_r)),),
            row_owner=(arg((n_r,), jnp.int32),),
            row_inv=arg((n_r,), jnp.int32),
            col_vals=tuple(arg((ng, wg)) for ng, wg in col_groups),
            col_owner=tuple(arg((ng,), jnp.int32) for ng, _ in col_groups),
            col_inv=arg((d_c,), jnp.int32),
            keys_c2r=arg((p,), jnp.int32),
            keys_r2c=arg((p,), jnp.int32),
            n_rows=n_r, n_features=d_c)
        yield "sortperm:matvec(d=2M)", lambda: jax.jit(
            lambda f, v: f.matvec(v)).lower(feats, arg((d_c,))).compile()
        yield "sortperm:rmatvec(d=2M)", lambda: jax.jit(
            lambda f, u: f.rmatvec(u)).lower(feats, arg((n_r,))).compile()

    # Prefix match, not reversed substring membership: `any(s in "sortperm")`
    # would let selectors like "t" or "o" silently enable unrelated groups.
    if not selected or any("sortperm".startswith(s) for s in selected):
        run_group(sortperm_checks())

    if not selected or any("sharded".startswith(s) for s in selected):
        run_group(shard_checks())

    if failures:
        print(f"FAILED VARIANTS: {failures}")
        return 1
    if selected and not ran[0]:
        # A selector that matches nothing must fail loudly, not certify
        # zero compiles as green (group selectors PREFIX-match 'gather'/
        # 'sortperm'/'sharded'; variant selectors substring-match names).
        print(f"NO CHECKS MATCHED SELECTORS {selected!r}")
        return 2
    print("ALL SELECTED VARIANTS COMPILE ON MOSAIC (v5e, deviceless AOT)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
