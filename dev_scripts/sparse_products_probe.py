#!/usr/bin/env python3
"""Time candidate forms of a sparse fixed effect's two products on the chip,
on the cell's own data at its own size (PR 35; PERF.md section 5's per-index
table came from this):

    chiprun -- python dev_scripts/sparse_products_probe.py [rows] [csr]
    chiprun -- python dev_scripts/sparse_products_probe.py [rows] codes [widths]

Each form of ``X.w`` / ``X^T.u`` (over ``[n, k]`` arrays, over ``[k, n]``,
one flat operation, and the slot-major loops ``SlotMajorEllFeatures`` runs)
is compiled, run twice, and reported as ns a stored slot; the loops again
over columns spread evenly and over ONE address (what collisions cost).
Writes ``chiprun_out/probe.json``. A later layout is ranked by adding its
two functions here before it is added to the program. With ``csr`` only
the program's two layouts are timed, side by side on the same data: the
flat triplet ``choose_layout`` weighs the slot-major ELL against
(``CSRFeatures``: a gather and a segment-sum a non-zero) and the ELL
itself (with every slot's distinct columns and the coded slots' classes),
into ``chiprun_out/probe_csr.json``. With ``codes`` (PR 36) ONE
slot of n rows is read by gather from ``f32[d]`` and, through a code a row
and a table of V entries, by every candidate form of ``code_forms``, for V
in ``CODE_WIDTHS``: the table ``ops.features.CODED_SLOT_TOP_CLASS`` was set
from (``chiprun_out/probe_codes.json``; docs/SCALE.md has the law). Since
PR 39 ``program`` is the program's own ``_lookup`` (the lane gather in a
Pallas kernel), and ``program_b<block>_g<groups>`` the same kernel at other
sizes than the ones it ships with (rows of codes a grid step, groups of the
table a loop step).
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



# -- one slot read through codes (PR 36) ---------------------------------------
# Every form is ``fn(out, x, idx, table) -> out + x * table[idx]``: what one
# step of ``SlotMajorEllFeatures._by_row`` adds. ``idx`` is the slot's column
# ids (``gather_d``: the table is all of w) or its codes (the table is the
# slot's V dictionary entries of w).
CODE_WIDTHS = (1, 3, 64, 128, 256, 512, 1024, 2048, 4096, 8192, 16384,
               32768, 65536)
LANES = 128
# rows of codes a grid step and groups a loop step, beside the program's own
KERNEL_SIZES = ((512, 4), (512, 16), (1024, 8), (256, 8))


def C_gather(out, x, idx, table):
    return out + x * table.at[idx].get(mode="promise_in_bounds")


def C_chain(out, x, code, table):
    """One compare and one select an entry, unrolled: one elementwise
    fusion, nothing of size [V, n]."""
    t = jnp.zeros(out.shape, table.dtype)
    for v in range(table.shape[0]):
        t = jnp.where(code == v, table[v], t)
    return out + x * t


def C_chain_chunked(out, x, code, table):
    """The chain in a loop of 32 unrolled entries a step (64 a step is
    slower): the text stays small at any V, at one more pass over an
    n-vector a step."""
    def body(i, t):
        for j in range(32):
            v = i * 32 + j
            t = jnp.where(code == v.astype(code.dtype), table[v], t)
        return t
    t = lax.fori_loop(0, table.shape[0] // 32, body,
                      jnp.zeros(out.shape, table.dtype))
    return out + x * t


def C_program(out, x, codes, table):
    """The form the program runs (``ops.features._lookup`` and
    ``_add_term``): the table along the 128 lanes (one row a group of 128
    entries), the codes ``[1, rows, 128]``; one lane-local dynamic gather
    and one select a group."""
    from photon_ml_tpu.ops import features as F

    found = F._lookup(codes, table, jnp.zeros((1,), jnp.int32))
    return F._add_term(out, x, found, fenced=F._off_tpu())


def program_at(block: int, groups: int):
    """``C_program`` with the kernel's rows of codes a grid step and its
    groups a loop step set otherwise: the two are static arguments of the
    program's ``_lookup_call``, so every pair is traced and compiled on its
    own."""
    from photon_ml_tpu.ops import features as F

    def form(out, x, codes, table):
        found = F._lookup_call(jnp.zeros((1,), jnp.int32), codes, table,
                               interpret=F._off_tpu(), block=block,
                               groups=groups)
        return F._add_term(out, x, found, fenced=F._off_tpu())

    return form


def C_tree(out, x, code, table):
    """V - 1 selects on the code's bits, lowest first."""
    level = [table[v] for v in range(table.shape[0])]
    bit = 0
    while len(level) > 1:
        m = ((code >> bit) & 1) != 0
        level = [jnp.where(m, level[i + 1], level[i])
                 for i in range(0, len(level), 2)]
        bit += 1
    return out + x * level[0]


def C_reduce(out, x, code, table):
    """``sum_v where(code == v, table[v], 0)``: XLA's own reduce over V."""
    v = jnp.arange(table.shape[0], dtype=code.dtype)
    t = jnp.sum(jnp.where(code[None, :] == v[:, None], table[:, None], 0.0),
                axis=0)
    return out + x * t


def C_onehot(out, x, code, table):
    """A one-hot row times the table on the MXU, exact at ``highest``."""
    hot = jax.nn.one_hot(code, table.shape[0], dtype=table.dtype)
    return out + x * jnp.dot(hot, table, precision="highest")


def code_forms(v: int) -> dict:
    """``{name: (function, the codes' dtype)}``: the candidates at width
    ``v`` (the unrolled forms only where their text stays compilable, the
    MXU form only where its ``[n, V]`` operand could fit)."""
    narrow = jnp.uint8 if v <= 256 else jnp.uint16
    forms = {"program": (C_program, jnp.uint16)}
    for block, groups in KERNEL_SIZES:
        forms[f"program_b{block}_g{groups}"] = (program_at(block, groups),
                                                jnp.uint16)
    if v <= 4096:  # past it only the kernel is a candidate
        forms["gather_v_i32"] = (C_gather, jnp.int32)
    if 64 <= v <= 4096:
        forms["chain_chunked_i32"] = (C_chain_chunked, jnp.int32)
        forms["chain_chunked_narrow"] = (C_chain_chunked, narrow)
    if v <= 256:  # at 1,024 the unrolled text compiles for 13-22 s
        forms["chain_narrow"] = (C_chain, narrow)
        forms["chain_i32"] = (C_chain, jnp.int32)
        forms["reduce_i32"] = (C_reduce, jnp.int32)
        forms["reduce_narrow"] = (C_reduce, narrow)
    if 2 <= v <= 256 and v & (v - 1) == 0:
        forms["tree_i32"] = (C_tree, jnp.int32)
    if v <= 128:
        forms["onehot_i32"] = (C_onehot, jnp.int32)
    return forms


def code_form_shapes(name, n, v, code_dt, shape_of):
    """The arguments of ``code_forms(v)[name]`` as shapes (``shape_of(shape,
    dtype)``: a ``ShapeDtypeStruct`` for a described chip, or an array). The
    program's kernel takes a slot's codes as the program stores them, and a
    table of whole groups of 128."""
    from photon_ml_tpu.ops.features import _code_stride, _slot_class

    kernel = name.startswith("program")
    step = max(block for block, _ in KERNEL_SIZES)  # whole steps of each
    rows = -(-_code_stride(n) // (step * LANES)) * step
    idx = (1, rows, LANES) if kernel else (n,)
    return (shape_of((n,), jnp.float32), shape_of((n,), jnp.float32),
            shape_of(idx, code_dt),
            shape_of((_slot_class(v) if kernel else v,), jnp.float32))


def coded_slot(rows: int, widths=CODE_WIDTHS) -> dict:
    """The gather-against-code table: one slot of ``rows`` rows, ms a slot
    and ns a row for every form and width, and whether the result is
    bitwise the gather's."""
    n, d = rows, 1000001
    out = {"n": n, "d": d, "widths": {}}
    key = jax.random.PRNGKey(2147486601)
    w = jax.random.normal(jax.random.fold_in(key, 1), (d,), jnp.float32)
    x = jax.random.normal(jax.random.fold_in(key, 2), (n,), jnp.float32)
    acc = jax.random.normal(jax.random.fold_in(key, 3), (n,), jnp.float32)
    for v in widths:
        row = out["widths"][str(v)] = {}
        dictionary = jnp.sort(jax.random.choice(
            jax.random.fold_in(key, 10 + v), d, (v,), replace=False)
        ).astype(jnp.int32)
        code = jax.random.randint(jax.random.fold_in(key, 20 + v), (n,), 0, v,
                                  jnp.int32)
        cols = dictionary[code]
        table = w[dictionary]
        ref = timed(row, "gather_d", jax.jit(C_gather), (acc, x, cols, w), n)
        for name, (fn, code_dt) in code_forms(v).items():
            idx, entries = code.astype(code_dt), table
            if name.startswith("program"):
                shapes = code_form_shapes(name, n, v, code_dt,
                                          lambda s, _: s)
                idx = jnp.pad(idx, (0, shapes[2][1] * LANES - n)).reshape(
                    shapes[2])
                entries = jnp.pad(table, (0, shapes[3][0] - v))
            try:
                timed(row, name, jax.jit(fn), (acc, x, idx, entries), n, ref)
            except Exception as e:  # a form the compiler refuses: recorded
                row[name] = {"failed": f"{type(e).__name__}: {e}"[:400]}
                print(name, json.dumps(row[name]), flush=True)
    out["device"] = str(jax.local_devices()[0].device_kind)
    (ROOT / "chiprun_out").mkdir(exist_ok=True)
    (ROOT / "chiprun_out/probe_codes.json").write_text(
        json.dumps(out, indent=1))
    return out


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
    # what the coded side was built from: every slot's distinct columns, the
    # slots read by code and each one's class (PR 39)
    out["distinct"] = [int(v) for v in jax.device_get(F._slot_dictionaries(
        ell.cols, n_rows=n, n_features=d)[0])]
    out["coded"], out["classes"] = list(ell.coded), list(ell.classes)
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
    mode = sys.argv[2] if len(sys.argv) > 2 else ""
    if mode == "codes" and len(sys.argv) > 3:  # ... codes 128,1024
        coded_slot(rows, tuple(int(v) for v in sys.argv[3].split(",")))
    else:
        print(json.dumps({"csr": program_layouts, "codes": coded_slot}.get(
            mode, main)(rows)))
