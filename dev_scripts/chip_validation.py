"""Chip validation: all ten Pallas entity-solver variants (3 modes x
normalization/bounds folds) run and are timed on real TPU, then the
gather-wall candidates. Run on the chip after any kernel change, before
trusting TPU results:
    python dev_scripts/chip_validation.py
Compile-only certification without a chip: dev_scripts/mosaic_aot_check.py
"""
def main():
    import time
    import numpy as np, jax, jax.numpy as jnp

    from photon_ml_tpu.ops.losses import loss_for_task
    from photon_ml_tpu.ops.pallas_entity_solver import pallas_entity_lbfgs
    from photon_ml_tpu.types import TaskType

    rng = np.random.default_rng(3)
    e, r, d = 5000, 40, 25
    x = rng.normal(0, 1, (e, r, d)).astype(np.float32); x[:, :, 0] = 1.0
    wt = rng.normal(0, 0.4, (e, d))
    z = np.einsum("erd,ed->er", x, wt)
    y = (rng.random((e, r)) < 1/(1+np.exp(-z))).astype(np.float32)
    yp = rng.poisson(2.0, (e, r)).astype(np.float32)
    off = np.zeros((e, r), np.float32); w = np.ones((e, r), np.float32)

    def sync(v): np.asarray(jax.device_get(jax.tree.leaves(v)[0].ravel()[0]))

    def timed(fn, reps=8):
        out = fn(); sync(out)
        t0 = time.perf_counter()
        for _ in range(reps): out = fn()
        sync(out)
        return (time.perf_counter() - t0) / reps * 1e3, out

    log_loss = loss_for_task(TaskType.LOGISTIC_REGRESSION)
    poi_loss = loss_for_task(TaskType.POISSON_REGRESSION)
    xa, ya, ypa = jnp.asarray(x), jnp.asarray(y), jnp.asarray(yp)
    offa, wa = jnp.asarray(off), jnp.asarray(w)
    c0 = jnp.zeros((e, d), np.float32)

    # Per-entity normalization arrays (STANDARDIZATION-like) and box
    # bounds — the round-4 kernel folds; each variant must COMPILE on
    # real Mosaic (interpret-mode parity does not prove that).
    fac = np.tile(1.0 / np.maximum(x.std(axis=(0, 1)), 0.2), (e, 1))
    fac[:, 0] = 1.0
    shf = np.tile(x.mean(axis=(0, 1)), (e, 1))
    shf[:, 0] = 0.0
    faca = jnp.asarray(fac, np.float32)
    shfa = jnp.asarray(shf, np.float32)
    lba = jnp.full((e, d), -0.3, np.float32)
    uba = jnp.full((e, d), 0.3, np.float32)

    for name, mode, loss, yy, l1, l2, kw in [
        ("lbfgs", "lbfgs", log_loss, ya, 0.0, 1.0, {}),
        ("owlqn", "owlqn", log_loss, ya, 0.5, 0.5, {}),
        ("tron", "tron", poi_loss, ypa, 0.0, 1.0, {}),
        ("lbfgs+norm", "lbfgs", log_loss, ya, 0.0, 1.0,
         dict(factors=faca, shifts=shfa)),
        ("lbfgs+bounds", "lbfgs", log_loss, ya, 0.0, 1.0,
         dict(lower=lba, upper=uba)),
        ("lbfgs+norm+bounds", "lbfgs", log_loss, ya, 0.0, 1.0,
         dict(factors=faca, shifts=shfa, lower=lba, upper=uba)),
        ("owlqn+norm", "owlqn", log_loss, ya, 0.5, 0.5,
         dict(factors=faca, shifts=shfa)),
        ("tron+norm", "tron", poi_loss, ypa, 0.0, 1.0,
         dict(factors=faca, shifts=shfa)),
        ("tron+bounds", "tron", poi_loss, ypa, 0.0, 1.0,
         dict(lower=lba, upper=uba)),
        ("tron+norm+bounds", "tron", poi_loss, ypa, 0.0, 1.0,
         dict(factors=faca, shifts=shfa, lower=lba, upper=uba)),
    ]:
        ms, res = timed(lambda: pallas_entity_lbfgs(
            loss, xa, yy, offa, wa, c0, l2, l1,
            max_iter=15, tol=1e-6, mode=mode, **kw))
        xs = np.asarray(jax.device_get(res.x))
        assert np.isfinite(xs).all(), name
        print(f"{name:18s}: {ms:7.2f} ms  mean_iters="
              f"{float(np.asarray(res.iterations).mean()):.1f}  finite OK",
              flush=True)
    print("ALL KERNEL VARIANTS COMPILE+RUN ON CHIP", flush=True)

    # Sparse gather candidates (docs/SCALE.md wall): measured rates.
    import subprocess
    import sys
    from pathlib import Path

    subprocess.run(
        [sys.executable,
         str(Path(__file__).with_name("gather_experiments.py"))],
        check=False)


if __name__ == "__main__":
    main()
