#!/usr/bin/env python3
"""``readings.py`` for a ``cd_fit_sparse`` cell: read the numbers that
decide ``correct``, over many seeds, in one process: the program as the
configuration states it, the lower-precision control (the values handed to
the program in bfloat16) and the planted faults of ``faults_sparse.py``.

    python3 benchmark/readings_sparse.py --workload sparse-lr.fit \
        --seeds 1,2,3 --variants program,control,half_batch,tail_dropped

One JSON line per (seed, variant). The limits in the workload file were set
from these readings (``PERF.md`` gives them); the benchmark's own runs
never run this. ``--witness`` adds, per seed, the reference's L-BFGS once
more in float64 on the host (numpy; the same method and constants, exact
sums for this purpose) and a line that says how far the program's and the
float32 reference's coefficients each lie from it, and which columns carry
the difference between the two: what a sound ``coef_gap`` is made of. (``readings.py`` names its reference and its faults module;
a ``benchmark`` PR should make it take both from the cell, ``PERF.md``
section 7.)
"""

import argparse
import contextlib
import gc
import importlib
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
WITNESS_BLOCK = 1 << 20  # rows whose gathered values the witness holds


def witness_fit(problem, config: dict):
    """``reference/sparse_glm.fit`` in float64 on the host: the same
    two-loop recursion, first step, Armijo constant and halving, for the
    same cap from zero. Returns the iterate (numpy float64)."""
    import numpy as np

    from benchmark.reference import sparse_glm as ref

    if config["link"] != "logistic":
        raise ValueError("the witness covers the logistic link")
    opt = ref.optimizer_of(config["fixed"]["optimizer"])
    cols = np.asarray(problem.cols)
    vals = np.asarray(problem.vals)
    y, off, wts = (np.asarray(a, np.float64) for a in (
        problem.labels, problem.offsets, problem.weights))
    n, d, l2 = cols.shape[0], problem.n_features, opt["l2"]

    def evaluate(w, grad: bool):
        value, g = 0.5 * l2 * float(w @ w), l2 * w if grad else None
        for lo in range(0, n, WITNESS_BLOCK):
            rows = slice(lo, lo + WITNESS_BLOCK)
            cb, vb = cols[rows], vals[rows].astype(np.float64)
            z = (vb * w[cb]).sum(axis=1) + off[rows]
            value += float(wts[rows] @ (np.logaddexp(0.0, z) - y[rows] * z))
            if grad:
                u = wts[rows] * (1.0 / (1.0 + np.exp(-z)) - y[rows])
                g = g + np.bincount(cb.ravel(),
                                    weights=(vb * u[:, None]).ravel(),
                                    minlength=d)
        return value, g

    def direction(g, pairs):
        q, alphas = g.copy(), []
        for s, yv in reversed(pairs):
            a = (s @ q) / (yv @ s)
            q -= a * yv
            alphas.append(a)
        if pairs:
            s, yv = pairs[-1]
            q *= (s @ yv) / (yv @ yv)
        for (s, yv), a in zip(pairs, reversed(alphas)):
            q += s * (a - (yv @ q) / (yv @ s))
        return -q

    w = np.zeros(d)
    f, g = evaluate(w, True)
    pairs = []
    for _ in range(opt["cap"]):
        p = direction(g, pairs)
        slope = float(p @ g)
        if slope >= 0:
            p, slope = -g, -float(g @ g)
        t = 1.0 if pairs else 1.0 / max(float(np.linalg.norm(p)), 1.0)
        for _trial in range(ref.MAX_LINE_SEARCH + 1):
            f_t, _ = evaluate(w + t * p, False)
            if np.isfinite(f_t) and f_t <= f + ref.C1 * t * slope:
                break
            t *= ref.SHRINK
        else:
            raise RuntimeError("the witness' line search failed")
        w_t = w + t * p
        f_new, g_new = evaluate(w_t, True)
        s, yv = w_t - w, g_new - g
        if s @ yv > ref.CAUTIOUS_EPS * np.linalg.norm(s) * np.linalg.norm(yv):
            pairs = (pairs + [(s, yv)])[-ref.HISTORY:]
        w, f, g = w_t, f_new, g_new
    return w


def witness_line(problem, w_program, w_reference, w_witness) -> dict:
    """How far the program's and the float32 reference's coefficients each
    lie from the float64 witness, relative to its norm, and the share of
    the squared difference between the two that sits on the ten fullest
    columns (the intercept's first)."""
    import numpy as np

    w_p, w_r = (np.asarray(w, np.float64) for w in (w_program, w_reference))
    norm = np.linalg.norm(w_witness)
    diff = w_p - w_r
    hot = np.argsort(-np.asarray(problem.col_degree))[:10]
    return {"program_vs_witness": float(np.linalg.norm(w_p - w_witness)
                                        / norm),
            "reference_vs_witness": float(np.linalg.norm(w_r - w_witness)
                                          / norm),
            "program_vs_reference": float(np.linalg.norm(diff) / norm),
            "share_on_hottest": float((diff[hot] ** 2).sum()
                                      / max((diff ** 2).sum(), 1e-300)),
            "share_on_fullest": float(diff[hot[0]] ** 2
                                      / max((diff ** 2).sum(), 1e-300))}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--variants", default="program,control")
    ap.add_argument("--rehearse-rows", type=int, default=0)
    ap.add_argument("--witness", action="store_true")
    args = ap.parse_args(argv)
    sys.path.insert(0, str(ROOT))
    import jax

    from benchmark import faults_sparse, harness
    from benchmark.reference import sparse_glm
    from photon_ml_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    loaded = harness.load_cell(args.workload)
    config, workload = loaded["config"], loaded["workload"]
    device = harness.device_block(1, require_chip=not args.rehearse_rows)
    recipe = importlib.import_module(f"benchmark.recipes.{config['recipe']}")
    if args.rehearse_rows:
        config = recipe.scale_down(config, args.rehearse_rows)
    jobs = importlib.import_module(f"benchmark.jobs.{workload['job']}")
    check = importlib.import_module(f"benchmark.checks.{workload['job']}")
    for seed in (int(s) for s in args.seeds.split(",")):
        problem = recipe.make(config, seed)
        ref = sparse_glm.fit(problem, config)
        print(json.dumps({
            "seed": seed, "variant": "reference",
            "values": ref["values"].tolist(),
            "line_search_trials": ref["line_search_trials"],
            "stopped": ref["stopped"], "degrees": problem.notes}),
            flush=True)
        for variant in args.variants.split(","):
            t0 = time.perf_counter()
            storage = "bfloat16" if variant == "control" else "float32"
            planted = (faults_sparse.FAULTS[variant](problem)
                       if variant in faults_sparse.FAULTS
                       else contextlib.nullcontext())
            with planted:
                job = jobs.build(config, workload, problem, storage=storage)
                job.warm_up(seed)
                window = job.window(0.0, seed)  # one job
                job.after_window(window)
            counters = job.counters(window)
            job.release()
            del job
            values = check.numbers(problem, config, window, ref)
            line = {"workload": args.workload, "seed": seed,
                    "variant": variant, "platform": device["platform"],
                    "n_rows": config["n_rows"], "job_s": window["seconds"],
                    "total_s": time.perf_counter() - t0,
                    "counters": counters,
                    "history": window["histories"][0].tolist(),
                    "numbers": values}
            if args.witness and variant == "program":
                fixed = config["fixed"]["name"]
                t1 = time.perf_counter()
                gaps = witness_line(
                    problem, window["kept"]["last"]["coefs"][fixed],
                    ref["coefs"][fixed], witness_fit(problem, config))
                print(json.dumps(dict(
                    gaps, seed=seed, variant="witness",
                    total_s=time.perf_counter() - t1)), flush=True)
            del window
            jax.clear_caches()  # the next variant traces its own programs
            gc.collect()
            print(json.dumps(line), flush=True)
        del problem, ref
    return 0


if __name__ == "__main__":
    sys.exit(main())
