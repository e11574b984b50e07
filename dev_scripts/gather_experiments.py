#!/usr/bin/env python3
"""Sparse gather-wall experiments.

The d=2M sparse fixed-effect iteration is gather-bound: XLA random
access runs at a FLAT ~148M lookups/s on v5e (docs/SCALE.md), ~0.07% of
HBM bandwidth, making the sparse path ~440x slower per iteration than
the dense one. Before accepting that wall, this script measures every
alternative implementation of the core primitive

    out[i] = w[idx[i]]   (w: f32[d] table, idx: i32[m], m ~ 12M, d ~ 2M)

on the current backend and prints one JSON line per candidate:

  xla_gather          baseline w[idx] (the 148M/s wall)
  xla_onehot_scan     indices pre-grouped into 2048-wide column blocks;
                      per block, a fused iota-compare one-hot (bf16)
                      contracted against the block's w slice on the MXU.
                      Arithmetic bound: 197e12 MAC/s / 2048 ≈ 48G
                      lookups/s IF XLA fuses the one-hot into the dot
                      without materializing it in HBM.
  pallas_onehot       the same contraction written explicitly as a
                      Pallas kernel (one-hot built in VREGs, jnp.dot on
                      the MXU, f32 accumulation).
  pallas_residue_gather  Pallas kernel holding the whole table in VMEM
                      as [d/128, 128] and issuing LANE-LOCAL
                      dynamic_gathers over residue-class-packed indices
                      (lane l gathers only elements with j%128 == l) —
                      the only arbitrary-gather formulation Mosaic's
                      gather lowering supports; a flat table[idx]
                      raises 'Only 2D gather is supported'.

Run on a real chip:  python dev_scripts/gather_experiments.py
CPU correctness check (tiny shapes + interpret mode):
                     python dev_scripts/gather_experiments.py --check
"""

from __future__ import annotations

import argparse
import json
import os
import time

import numpy as np

BLOCK = 2048


def _prep_blocks(idx: np.ndarray, d: int, block: int = BLOCK):
    """Group indices by `block`-wide column block, padded per block to
    the max per-block count (value 0 -> gathers w[block_start], masked
    by weight 0). Returns (block_local i32[kb, e], mask f32[kb, e],
    perm i32[m] mapping packed order back to original order)."""
    kb = -(-d // block)
    owner = idx // block
    order = np.argsort(owner, kind="stable")
    counts = np.bincount(owner, minlength=kb)
    e = max(1, int(counts.max()))
    local = np.zeros((kb, e), np.int32)
    mask = np.zeros((kb, e), np.float32)
    starts = np.concatenate([[0], np.cumsum(counts)[:-1]])
    pos = np.arange(len(idx)) - np.repeat(starts, counts)
    local[owner[order], pos] = (idx[order] - owner[order] * block)
    mask[owner[order], pos] = 1.0
    packed_of = (owner[order] * e + pos)  # position in [kb*e] layout
    slot = np.empty(len(idx), np.int64)
    slot[order] = packed_of
    return local, mask, slot


def make_xla_gather(w, idx):
    """Returns (jitted f, args). Timed over rolled index variants."""
    import jax

    @jax.jit
    def f(w, idx):
        return w[idx]

    return f, (w, idx)


def make_xla_onehot_scan(w, local, mask, block: int = BLOCK):
    """Returns (jitted f, args). Timed over rolled (local, mask)."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    kb, e = local.shape
    d_pad = kb * block

    @jax.jit
    def f(w, local, mask):
        wb = jnp.pad(w, (0, d_pad - w.shape[0])).reshape(kb, block)

        def step(_, args):
            loc, msk, wslice = args
            onehot = (loc[:, None] ==
                      jnp.arange(block, dtype=jnp.int32)[None, :]
                      ).astype(jnp.bfloat16)
            out = jnp.dot(onehot, wslice.astype(jnp.bfloat16),
                          preferred_element_type=jnp.float32)
            return None, out * msk

        _, outs = lax.scan(step, None, (local, mask, wb))
        return outs.reshape(-1)  # packed [kb * e]

    return f, (w, local, mask)


def build_onehot_call(kb, e, interpret=False):
    """The raw pallas_call for the one-hot MXU gather candidate —
    separated from the data prep so the deviceless Mosaic compile gate
    (mosaic_aot_check.py) can AOT-compile it from abstract shapes.

    Two Mosaic constraints found by the AOT gate shape the geometry:
    the block shape's second-to-last dim must divide by 8 (a (1, ep)
    block fails to lower), and the materialized one-hot intermediate
    must FIT VMEM — so the grid is 2-D: 8 column-blocks per step along
    kb, ECOLS=512 entities per step along ep (one-hot tile
    [512, 2048] bf16 = 2 MB in VREGs, reused across the 8 static-loop
    2-D dots; no 3-D contraction). kb pads to a multiple of 8, e to a
    multiple of 512 (pad rows/cols gather w[.] masked to 0)."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    rows = 8  # second-to-last block dim must divide by 8
    ecols = 512  # entities per grid step: bounds the one-hot VMEM tile
    kbp = -(-kb // rows) * rows
    ep = -(-e // ecols) * ecols

    def kernel(loc_ref, msk_ref, w_ref, out_ref):
        iota = jax.lax.broadcasted_iota(jnp.int32, (ecols, BLOCK), 1)
        for i in range(rows):
            loc = loc_ref[i].reshape(ecols, 1)
            onehot = (loc == iota).astype(jnp.bfloat16)
            wv = w_ref[i].reshape(BLOCK, 1).astype(jnp.bfloat16)
            out = jnp.dot(onehot, wv, preferred_element_type=jnp.float32)
            out_ref[i] = out.reshape(ecols) * msk_ref[i]

    f = pl.pallas_call(
        kernel,
        grid=(kbp // rows, ep // ecols),
        in_specs=[
            pl.BlockSpec((rows, ecols), lambda b, c: (b, c),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((rows, ecols), lambda b, c: (b, c),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((rows, BLOCK), lambda b, c: (b, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((rows, ecols), lambda b, c: (b, c),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((kbp, ep), jnp.float32),
        interpret=interpret,
    )
    return f, ep, kbp


def make_pallas_onehot(w, local, mask, interpret=False):
    """Returns (jitted f, args). Timed over rolled (local, mask)."""
    import jax
    import jax.numpy as jnp

    kb, e = local.shape
    d_pad = kb * BLOCK
    w_pad = jnp.pad(w, (0, d_pad - w.shape[0])).reshape(kb, BLOCK)
    f, ep, kbp = build_onehot_call(kb, e, interpret=interpret)
    w_pad = jnp.pad(w_pad, ((0, kbp - kb), (0, 0)))
    local_p = jnp.pad(local, ((0, kbp - kb), (0, ep - e)))
    mask_p = jnp.pad(mask, ((0, kbp - kb), (0, ep - e)))
    jf = jax.jit(lambda l, m, wp: f(l, m, wp)[:kb, :e].reshape(-1))
    return jf, (local_p, mask_p, w_pad)


def _prep_residue(idx: np.ndarray, d: int):
    """Residue-class packing for Mosaic's lane-local dynamic_gather:
    the table reshapes to T[d/128, 128] (element j at sublane j//128,
    lane j%128) and tpu.dynamic_gather(T, C, [0]) lets lane l gather
    only from ITS OWN column T[:, l] — i.e. elements with j%128 == l.
    So indices are bucketed by residue j%128 (one stream per lane),
    each stream padded to a multiple of the table's sublane count A,
    giving C chunks of exactly the table's [A, 128] shape (the lowering
    requires x.shape == idx.shape). Returns (sub i32[chunks, A, 128],
    slot i64[m] mapping each original index to its packed position)."""
    assert d % 128 == 0
    a = d // 128
    lane = idx % 128
    sub = idx // 128
    order = np.argsort(lane, kind="stable")
    counts = np.bincount(lane, minlength=128)
    per_lane = -(-max(1, int(counts.max())) // a) * a  # pad to A-multiple
    chunks = per_lane // a
    packed = np.zeros((128, per_lane), np.int32)
    starts = np.concatenate([[0], np.cumsum(counts)[:-1]])
    pos = np.arange(len(idx)) - np.repeat(starts, counts)
    packed[lane[order], pos] = sub[order]
    # [128, per_lane] -> [chunks, A, 128]
    packed = packed.reshape(128, chunks, a).transpose(1, 2, 0)
    slot = np.empty(len(idx), np.int64)
    # packed position (lane l, stream index p) -> flat slot in the
    # [chunks, A, 128] output: chunk = p // a, sublane = p % a, lane l.
    slot[order] = ((pos // a) * a * 128 + (pos % a) * 128
                   + lane[order])
    return packed, slot


def build_residue_call(chunks, a, lanes, dtype, interpret=False):
    """The raw pallas_call for the lane-local dynamic_gather candidate
    (separated from data prep for the deviceless Mosaic compile gate)."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    def kernel(w_ref, idx_ref, out_ref):
        out_ref[0] = jnp.take_along_axis(w_ref[:], idx_ref[0], axis=0)

    return pl.pallas_call(
        kernel,
        grid=(chunks,),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.VMEM),  # whole table
            pl.BlockSpec((1, a, lanes), lambda t: (t, 0, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((1, a, lanes), lambda t: (t, 0, 0),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((chunks, a, lanes), dtype),
        interpret=interpret,
    )


def make_pallas_residue_gather(w, sub_chunks, interpret=False):
    """Whole table in VMEM as [d/128, 128]; one lane-local
    dynamic_gather per same-shape index chunk — the ONLY arbitrary-
    gather formulation Mosaic's gather lowering supports (jax pallas
    mosaic lowering.py:2464-2525: batched 2-D take_along_axis with
    slice_sizes (1,1); flat 1-D gathers raise 'Only 2D gather').
    Returns (jitted f, args)."""
    import jax
    import jax.numpy as jnp

    chunks, a, lanes = sub_chunks.shape
    w2 = jnp.asarray(w).reshape(a, lanes)
    f = build_residue_call(chunks, a, lanes, w.dtype, interpret=interpret)
    jf = jax.jit(lambda wt, i: f(wt, i).reshape(-1))
    sc = jnp.asarray(sub_chunks)
    return jf, (w2, sc)


REPS = 5  # distinct-arg timed reps per candidate

# Per-process nonce folded into every roll shift: two processes timing
# the same candidate never enqueue byte-identical dispatches.
_NONCE = os.getpid() % 997 + 1


def _variant_args(args, roll_axes, i):
    """Roll the arrays named by ``roll_axes`` (index -> axis) by a
    variant- and process-specific shift; arrays not named stay shared
    (e.g. the coefficient table). Rolled index/mask pairs shift
    TOGETHER so they stay aligned (paired arrays share an axis length,
    so the per-axis-length reduction below gives them the same
    effective shift), and a rolled workload has identical cost shape.

    The effective shift is forced NONZERO per rolled axis: a raw shift
    that happens to be a multiple of the axis length would make the
    roll an identity, and the "distinct" rep a repeat of the warm-up."""
    import jax.numpy as jnp

    shift = (1009 + _NONCE) * i

    def roll(a, axis):
        eff = shift % a.shape[axis] or 1
        return jnp.roll(a, eff, axis=axis)

    return tuple(roll(a, roll_axes[j]) if j in roll_axes else a
                 for j, a in enumerate(args))


def _time_distinct(f, args, roll_axes):
    """args warms (and is the verify variant — never re-timed); each
    timed rep uses a distinct rolled variant, so no timed call repeats
    an earlier one byte for byte (docs/SCALE.md §methodology)."""
    import jax

    variants = [_variant_args(args, roll_axes, i + 1) for i in range(REPS)]
    jax.block_until_ready(f(*args))
    # The rolls above are async device work (~48 MB each at candidate
    # shapes); drain them BEFORE the clock starts or the timed window
    # absorbs roll cost.
    jax.block_until_ready(variants)
    t0 = time.perf_counter()
    outs = [f(*a) for a in variants]
    jax.block_until_ready(outs)
    return (time.perf_counter() - t0) / len(variants)


def run(m, d, check=False):
    import jax
    import jax.numpy as jnp

    interpret = check and jax.default_backend() != "tpu"
    rng = np.random.default_rng(0)
    idx_np = rng.integers(0, d, m).astype(np.int32)
    w_np = rng.normal(0, 1, d).astype(np.float32)
    w = jnp.asarray(w_np)
    idx = jnp.asarray(idx_np)
    local, mask, slot = _prep_blocks(idx_np, d)
    local_j, mask_j = jnp.asarray(local), jnp.asarray(mask)
    res_chunks, res_slot = _prep_residue(idx_np, d)
    expect = w_np[idx_np]

    def verify(f, args, slot_map):
        out = np.asarray(f(*args))
        got = out[slot_map] if slot_map is not None else out
        np.testing.assert_allclose(got, expect, atol=2e-2)
        return True

    # candidate -> ((f, args), {arg index -> roll axis}, slot map)
    candidates = {
        "xla_gather": (make_xla_gather(w, idx), {1: 0}, None),
        "xla_onehot_scan": (make_xla_onehot_scan(w, local_j, mask_j),
                            {1: 1, 2: 1}, slot),
        "pallas_onehot": (make_pallas_onehot(w, local_j, mask_j,
                                             interpret=interpret),
                          {0: 1, 1: 1}, slot),
        "pallas_residue_gather": (
            make_pallas_residue_gather(w, res_chunks, interpret=interpret),
            {1: 1}, res_slot),
    }
    results = {}
    for name, ((f, args), roll_axes, slot_map) in candidates.items():
        try:
            verify(f, args, slot_map)
            dt = (_time_distinct(f, args, roll_axes) if not check
                  else float("nan"))
            results[name] = {"ok": True,
                             "mlookups_per_sec": (round(m / dt / 1e6, 1)
                                                  if dt == dt else None)}
        except Exception as e:  # noqa: BLE001 — report per-candidate
            results[name] = {"ok": False,
                             "error": f"{type(e).__name__}: {e}"[:300]}
        print(json.dumps({"candidate": name, "m": m, "d": d,
                          **results[name]}), flush=True)
    return results


def sweep(m, d, blocks=(256, 512, 1024, 2048, 4096)):
    """Block-width sweep of xla_onehot_scan (round 5). The 2048-wide
    rate (293.6 M/s on chip) matches an MXU-GEMV bound — 770 G MAC/s
    (1/128 of peak, matrix-vector) / block MACs-per-lookup — so rate
    should scale ~1/block until the VPU one-hot generation or per-step
    scan overhead takes over. The sweep locates the knee."""
    import jax
    import jax.numpy as jnp

    rng = np.random.default_rng(0)
    idx_np = rng.integers(0, d, m).astype(np.int32)
    w_np = rng.normal(0, 1, d).astype(np.float32)
    w = jnp.asarray(w_np)
    expect = w_np[idx_np]
    # Baseline closed through a reduction AND timed over distinct index
    # arrays per rep: an un-reduced same-args loop can be dead-code
    # eliminated into an impossible rate (the §methodology rule in
    # docs/SCALE.md).
    f_base = jax.jit(lambda w, i: w[i].sum())
    base = _time_distinct(f_base, (w, jnp.asarray(idx_np)), {1: 0})
    print(json.dumps({"candidate": "xla_gather_reduced", "m": m, "d": d,
                      "ok": True,
                      "mlookups_per_sec": round(m / base / 1e6, 1)}),
          flush=True)
    for block in blocks:
        try:
            local, mask, slot = _prep_blocks(idx_np, d, block=block)
            f, args = make_xla_onehot_scan(
                w, jnp.asarray(local), jnp.asarray(mask), block=block)
            out = np.asarray(f(*args))
            np.testing.assert_allclose(out[slot], expect, atol=2e-2)
            dt = _time_distinct(f, args, {1: 1, 2: 1})
            res = {"ok": True,
                   "mlookups_per_sec": round(m / dt / 1e6, 1),
                   "pad_ratio": round(local.size / m, 3)}
        except Exception as e:  # noqa: BLE001 — report per-width
            res = {"ok": False, "error": f"{type(e).__name__}: {e}"[:200]}
        print(json.dumps({"candidate": f"xla_onehot_scan_b{block}",
                          "m": m, "d": d, **res}), flush=True)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--check", action="store_true",
                    help="small-shape correctness check (CPU/interpret)")
    ap.add_argument("--sweep", action="store_true",
                    help="block-width sweep of the one-hot scan")
    ap.add_argument("--m", type=int, default=None)
    ap.add_argument("--d", type=int, default=None)
    args = ap.parse_args()
    if args.check:
        run(args.m or 3_000, args.d or 4_096, check=True)
    elif args.sweep:
        sweep(args.m or 12_000_000, args.d or 2_000_000)
    else:
        run(args.m or 12_000_000, args.d or 2_000_000)


if __name__ == "__main__":
    main()
