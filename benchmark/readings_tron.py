#!/usr/bin/env python3
"""``readings.py`` for a ``cd_fit_tron`` cell: read the numbers that decide
``correct``, over many seeds, in one process: the program as the
configuration states it, the lower-precision control (X stored in
bfloat16 through the program's own ``DenseFeatures.bf16``) and the planted
faults of ``faults_tron.py``.

    python3 benchmark/readings_tron.py --workload tron-lr.fit \\
        --seeds 1,2,3 --variants program,control,cg_step_short [--data-seed 1]

One JSON line per seed for the reference (its values, its CG steps an
outer step and how near each CG stop came to its residual exit and to the
trust region's boundary, which outer steps it accepted, its passes over
X, its seconds) and one per (seed,
variant) with the program's counts, one job's seconds and the compared
numbers. The limits in the workload file were set from these readings
(``PERF.md`` gives them); the benchmark's own runs never run this.
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


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--variants", default="program,control")
    ap.add_argument("--rehearse-rows", type=int, default=0)
    ap.add_argument("--data-seed", type=int, default=None,
                    help="another draw of the data set than the "
                         "configuration's (its rows, truth and labels)")
    args = ap.parse_args(argv)
    sys.path.insert(0, str(ROOT))
    import jax

    from benchmark import faults_tron, harness
    from benchmark.reference import tron_glm
    from photon_ml_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    loaded = harness.load_cell(args.workload)
    config, workload = loaded["config"], loaded["workload"]
    device = harness.device_block(1, require_chip=not args.rehearse_rows)
    recipe = importlib.import_module(f"benchmark.recipes.{config['recipe']}")
    if args.rehearse_rows:
        config = recipe.scale_down(config, args.rehearse_rows)
    if args.data_seed is not None:
        config = {**config, "fixed": {**config["fixed"],
                                      "data_seed": args.data_seed}}
    jobs = importlib.import_module(f"benchmark.jobs.{workload['job']}")
    check = importlib.import_module(f"benchmark.checks.{workload['job']}")
    for seed in (int(s) for s in args.seeds.split(",")):
        problem = recipe.make(config, seed)
        t0 = time.perf_counter()
        ref = tron_glm.fit(problem, config)
        print(json.dumps({
            "seed": seed, "variant": "reference",
            "data_seed": config["fixed"]["data_seed"],
            "values": ref["values"].tolist(),
            "cg_per_step": ref["cg_per_step"],
            "cg_residual_margins": ref["cg_residual_margins"],
            "cg_boundary_margins": ref["cg_boundary_margins"],
            "accepted_steps": ref["accepted_steps"],
            "passes": ref["passes"], "stopped": ref["stopped"],
            "total_s": time.perf_counter() - t0}), flush=True)
        for variant in args.variants.split(","):
            t0 = time.perf_counter()
            storage = "bfloat16" if variant == "control" else "float32"
            planted = (faults_tron.FAULTS[variant](problem)
                       if variant in faults_tron.FAULTS
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
            print(json.dumps({
                "workload": args.workload, "seed": seed, "variant": variant,
                "platform": device["platform"], "n_rows": config["n_rows"],
                "job_s": window["seconds"],
                "total_s": time.perf_counter() - t0, "counters": counters,
                "history": window["histories"][0].tolist(),
                "numbers": values}), flush=True)
            del window
            jax.clear_caches()  # the next variant traces its own programs
            gc.collect()
        del problem, ref
    return 0


if __name__ == "__main__":
    sys.exit(main())
