#!/usr/bin/env python3
"""Time candidate forms of a sparse fixed effect's two products on the chip,
on the cell's own data at its own size (PR 35; PERF.md section 5's per-index
table came from this):

    chiprun -- python dev_scripts/sparse_products_probe.py [rows] [csr]

Each form of ``X.w`` / ``X^T.u`` (over ``[n, k]`` arrays, over ``[k, n]``,
one flat operation, and the slot-major loops ``SlotMajorEllFeatures`` runs)
is compiled, run twice, and reported as ns a stored slot; the loops again
over columns spread evenly and over ONE address (what collisions cost).
Writes ``chiprun_out/probe.json``. A later layout is ranked by adding its
two functions here before it is added to the program. With ``csr`` only
the program's two layouts are timed, side by side on the same data: the
flat triplet ``choose_layout`` weighs the slot-major ELL against
(``CSRFeatures``: a gather and a segment-sum a non-zero) and the ELL
itself, into ``chiprun_out/probe_csr.json``.
"""
import functools
import json
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax import lax  # noqa: E402

from benchmark.recipes import sparse_glm  # noqa: E402

def V_mv_nk(cols, vals, w):
    return jnp.sum(vals * w[cols], axis=1)
def V_rmv_nk(cols, vals, u, d):
    return jnp.zeros((d,), jnp.float32).at[cols].add(vals * u[:, None])
def V_mv_kn(cols, vals, w):
    return jnp.sum(vals * w[cols], axis=0)
def V_rmv_kn(cols, vals, u, d):
    return jnp.zeros((d,), jnp.float32).at[cols].add(vals * u[None, :])
def V_mv_loop(cols, vals, w, n):
    k = cols.shape[0] // n
    def body(f, acc):
        c = lax.dynamic_slice(cols, (f * n,), (n,))
        v = lax.dynamic_slice(vals, (f * n,), (n,))
        return acc + v * w.at[c].get(mode="promise_in_bounds")
    return lax.fori_loop(0, k, body, jnp.zeros((n,), jnp.float32))
def V_rmv_loop(cols, vals, u, d):
    n = u.shape[0]
    k = cols.shape[0] // n
    def body(f, acc):
        c = lax.dynamic_slice(cols, (f * n,), (n,))
        v = lax.dynamic_slice(vals, (f * n,), (n,))
        return acc.at[c].add(v * u, mode="promise_in_bounds")
    return lax.fori_loop(0, k, body, jnp.zeros((d,), jnp.float32))
def V_mv_flat(cols, vals, w, n):
    k = cols.shape[0] // n
    p = vals * w.at[cols].get(mode="promise_in_bounds")
    out = lax.slice(p, (0,), (n,))
    for f in range(1, k):
        out = out + lax.slice(p, (f * n,), ((f + 1) * n,))
    return out
def V_rmv_flat(cols, vals, u, d):
    n = u.shape[0]
    k = cols.shape[0] // n
    return jnp.zeros((d,), jnp.float32).at[cols].add(
        vals * jnp.tile(u, k), mode="promise_in_bounds")


_transpose = jax.jit(lambda a: a.T)
_flatten = jax.jit(lambda a: a.reshape(-1))
_rows_of = jax.jit(
    lambda n, k: jnp.repeat(jnp.arange(n, dtype=jnp.int32), k),
    static_argnums=(0, 1))


def timed(out, name, fn, args, n_slots, ref=None):
    """Compile and run ``fn`` (a jitted function) once, then twice more."""
    t = time.perf_counter()
    r = jax.block_until_ready(fn(*args))
    first = time.perf_counter() - t
    ts = []
    for _ in range(2):
        t = time.perf_counter()
        r = jax.block_until_ready(fn(*args))
        ts.append(time.perf_counter() - t)
    out[name] = {"first_s": first, "s": ts,
                 "ns_per_nnz": 1e9 * min(ts) / n_slots}
    if ref is not None:
        out[name]["err"] = float(jnp.max(jnp.abs(r - ref))
                                 / jnp.max(jnp.abs(ref)))
    print(name, json.dumps(out[name]), flush=True)
    return r


def program_layouts(rows: int) -> dict:
    """``CSRFeatures`` against ``SlotMajorEllFeatures``, both products, on
    the cell's data. Every slot of this data is a non-zero, so the triplet
    the chooser's ``csr`` side would build is the rows' entries end to end,
    which is built here directly (row-sorted, nothing padded)."""
    from photon_ml_tpu.ops import features as F

    config = json.loads(
        (ROOT / "benchmark/configs/sparse-lr-criteo.json").read_text())
    p = sparse_glm.make(sparse_glm.scale_down(config, rows), 2147486401)
    n, k, d = p.n_rows, p.cols.shape[1], p.n_features
    out = {"n": n, "k": k, "d": d}
    w = jax.random.normal(jax.random.PRNGKey(1), (d,), jnp.float32)
    u = jax.random.normal(jax.random.PRNGKey(2), (n,), jnp.float32)
    matvec = jax.jit(lambda f, v: f.matvec(v))
    rmatvec = jax.jit(lambda f, v: f.rmatvec(v))
    ell = F.sparse_rows_to_device(p.cols, p.vals, d)
    out["chosen"] = F.layout_counts(ell).layout
    ref_mv = timed(out, "ell_matvec", matvec, (ell, w), n * k)
    ref_rmv = timed(out, "ell_rmatvec", rmatvec, (ell, u), n * k)
    del ell
    csr = F.CSRFeatures(_flatten(p.vals), _flatten(p.cols), _rows_of(n, k),
                        n, d)
    p.cols = p.vals = None
    timed(out, "csr_matvec", matvec, (csr, w), n * k, ref_mv)
    timed(out, "csr_rmatvec", rmatvec, (csr, u), n * k, ref_rmv)
    out["device"] = str(jax.local_devices()[0].device_kind)
    (ROOT / "chiprun_out").mkdir(exist_ok=True)
    (ROOT / "chiprun_out/probe_csr.json").write_text(
        json.dumps(out, indent=1))
    return out


def main(rows: int) -> dict:
    config = json.loads(
        (ROOT / "benchmark/configs/sparse-lr-criteo.json").read_text())
    config = sparse_glm.scale_down(config, rows)
    t0 = time.perf_counter()
    p = sparse_glm.make(config, 2147486401)
    n, k, d = p.n_rows, p.cols.shape[1], p.n_features
    out = {"n": n, "make_s": time.perf_counter() - t0, "notes": p.notes}
    w = jax.random.normal(jax.random.PRNGKey(1), (d,), jnp.float32)
    u = jax.random.normal(jax.random.PRNGKey(2), (n,), jnp.float32)
    by_row = {name: jax.jit(functools.partial(fn, n=n))
              for name, fn in (("mv_loop", V_mv_loop), ("mv_flat", V_mv_flat))}
    by_col = {name: jax.jit(functools.partial(fn, d=d)) for name, fn in (
        ("rmv_nk", V_rmv_nk), ("rmv_kn", V_rmv_kn), ("rmv_loop", V_rmv_loop),
        ("rmv_flat", V_rmv_flat))}
    mv_nk, mv_kn = jax.jit(V_mv_nk), jax.jit(V_mv_kn)
    slots = n * k

    ref_mv = timed(out, "mv_nk", mv_nk, (p.cols, p.vals, w), slots)
    ref_rmv = timed(out, "rmv_nk", by_col["rmv_nk"], (p.cols, p.vals, u),
                    slots)
    cols_kn = jax.block_until_ready(_transpose(p.cols))
    vals_kn = jax.block_until_ready(_transpose(p.vals))
    p.cols = p.vals = None  # the [n, k] arrays go: a second copy follows
    timed(out, "mv_kn", mv_kn, (cols_kn, vals_kn, w), slots, ref_mv)
    timed(out, "rmv_kn", by_col["rmv_kn"], (cols_kn, vals_kn, u), slots,
          ref_rmv)
    cols_f = jax.block_until_ready(_flatten(cols_kn))
    vals_f = jax.block_until_ready(_flatten(vals_kn))
    del cols_kn, vals_kn
    for name in ("mv_loop", "mv_flat"):
        timed(out, name, by_row[name], (cols_f, vals_f, w), slots, ref_mv)
    for name in ("rmv_loop", "rmv_flat"):
        timed(out, name, by_col[name], (cols_f, vals_f, u), slots, ref_rmv)
    # what collisions cost: columns spread evenly, then every index on one
    spread = jax.block_until_ready(jax.random.randint(
        jax.random.PRNGKey(3), (slots,), 0, d, jnp.int32))
    one = jnp.full((slots,), d - 1, jnp.int32)
    for tag, cols in (("uniform_cols", spread), ("one_address", one)):
        timed(out, f"rmv_loop_{tag}", by_col["rmv_loop"], (cols, vals_f, u),
              slots)
        timed(out, f"mv_loop_{tag}", by_row["mv_loop"], (cols, vals_f, w),
              slots)
    out["device"] = str(jax.local_devices()[0].device_kind)
    (ROOT / "chiprun_out").mkdir(exist_ok=True)
    (ROOT / "chiprun_out/probe.json").write_text(json.dumps(out, indent=1))
    return out


if __name__ == "__main__":
    rows = int(sys.argv[1]) if len(sys.argv) > 1 else 9168123
    print(json.dumps(program_layouts(rows) if "csr" in sys.argv[2:]
                     else main(rows)))
