"""Chip probe: can sort-based data movement beat the random-access wall?

The sparse iteration's irreducible cost is applying a FIXED permutation
(packed order <-> ELL order) and a FIXED one-to-many expansion
(coefficient space -> slot space). Both are gathers today (~115-148 M
lookups/s flat). Alternatives measured here, all sequential-access
(one JSON line per op; docs/SCALE.md section "Attacking the gather
wall" has the cost model these rates plug into):

  sort12M_kv        lax.sort of (i32 key, f32 payload) at m=12M — the
                    cost of applying a known permutation via sort.
  sort12M_keyonly   key alone (lower bound for the sort machinery).
  cumsum12M         prefix scan at 12M — run-length copy-forward cost.
  max_scan12M       associative max-scan (segmented-propagate shape).
  scatter2M_into_12M  scatter of 2M run heads into a 12M vector.
  gather12M_reduced the baseline wall, reduction-closed against DCE.

Timing uses gather_experiments._time_distinct: every timed rep gets a
distinct per-process rolled input, so DCE cannot fake a rate
(docs/SCALE.md §methodology).

Usage: python dev_scripts/sort_primitives.py [--m 12000000] [--d 2000000]
"""
import argparse
import json

import numpy as np

from gather_experiments import _time_distinct


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--m", type=int, default=12_000_000)
    ap.add_argument("--d", type=int, default=2_000_000)
    args = ap.parse_args()
    m, d = args.m, args.d

    import jax
    import jax.numpy as jnp
    from jax import lax

    rng = np.random.default_rng(11)
    # a rolled permutation is still a permutation, so the shared
    # roll-variant harness keeps every op's input valid
    keys = jnp.asarray(rng.permutation(m).astype(np.int32))
    vals = jnp.asarray(rng.normal(0, 1, m).astype(np.float32))
    idx = jnp.asarray(rng.integers(0, d, m).astype(np.int32))
    w = jnp.asarray(rng.normal(0, 1, d).astype(np.float32))
    heads = jnp.asarray(
        np.sort(rng.choice(m, d, replace=False)).astype(np.int32))
    hv = jnp.asarray(rng.normal(0, 1, d).astype(np.float32))

    @jax.jit
    def f_sort(k, v):
        sk, sv = lax.sort((k, v), num_keys=1)
        return sv.sum(), sk[-1]

    @jax.jit
    def f_sortk(k):
        return lax.sort(k)[-1]

    @jax.jit
    def f_cumsum(v):
        return jnp.cumsum(v).sum()

    @jax.jit
    def f_max_scan(v):
        # copy-forward of run heads is a segmented scan; the plain
        # associative max-scan over the values bounds its cost shape.
        return lax.associative_scan(jnp.maximum, v).sum()

    @jax.jit
    def f_scatter(hv):
        z = jnp.zeros(m, jnp.float32)
        return z.at[heads].add(hv).sum()

    @jax.jit
    def f_gather(w, idx):
        return w[idx].sum()

    # op -> (jitted f, args, {arg index -> roll axis})
    suites = [
        ("gather12M_reduced", f_gather, (w, idx), {1: 0}),
        ("sort12M_kv", f_sort, (keys, vals), {0: 0}),
        ("sort12M_keyonly", f_sortk, (keys,), {0: 0}),
        ("cumsum12M", f_cumsum, (vals,), {0: 0}),
        ("max_scan12M", f_max_scan, (vals,), {0: 0}),
        ("scatter2M_into_12M", f_scatter, (hv,), {0: 0}),
    ]
    for name, f, fargs, roll_axes in suites:
        try:
            ms = _time_distinct(f, fargs, roll_axes) * 1e3
            print(json.dumps({"op": name, "m": m, "d": d,
                              "ms": round(ms, 2),
                              "melem_per_sec": round(m / ms / 1e3, 1)}),
                  flush=True)
        except Exception as e:  # noqa: BLE001 — report per-op
            print(json.dumps({"op": name, "m": m, "d": d,
                              "error": str(e)[:200]}), flush=True)


if __name__ == "__main__":
    main()
