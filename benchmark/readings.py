#!/usr/bin/env python3
"""Read the numbers that decide ``correct``, over many seeds, in one
process: the program as the configuration states it, the lower-precision
control (the program's own bfloat16 storage of X), and the planted faults.

    python3 benchmark/readings.py --workload glmix.fit --seeds 1,2,3 \
        --variants program,control,half_batch,exchange_left_out

One JSON line per (seed, variant). The limits in the workload files were
set from these readings (``PERF.md`` gives them); the benchmark's own runs
never run this.
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
    ap.add_argument("--set", action="append", default=[],
                    metavar="COORD.optimizer=STRING",
                    help="read under another optimizer string than the "
                         "configuration's (fixed.optimizer=50,1e-6,...)")
    args = ap.parse_args(argv)
    sys.path.insert(0, str(ROOT))
    import jax

    from benchmark import faults, harness
    from photon_ml_tpu.utils.compile_cache import enable_compile_cache

    def drop_programs():
        # A loaded program keeps its temporaries reserved on the chip
        # (6.9 GiB for the GLMix block): drop it before the next loads.
        jax.clear_caches()
        gc.collect()

    enable_compile_cache()
    loaded = harness.load_cell(args.workload)
    config, workload = loaded["config"], loaded["workload"]
    device = harness.device_block(1, require_chip=not args.rehearse_rows)
    recipe = importlib.import_module(f"benchmark.recipes.{config['recipe']}")
    if args.rehearse_rows:
        config = recipe.scale_down(config, args.rehearse_rows)
    for item in args.set:
        target, value = item.split("=", 1)
        coord, key = target.split(".")
        for c in [config["fixed"]] + config.get("random", []):
            if c["name"] == coord:
                c[key] = value
    jobs = importlib.import_module(f"benchmark.jobs.{workload['job']}")
    check = importlib.import_module(f"benchmark.checks.{workload['job']}")
    seeds = [int(s) for s in args.seeds.split(",")]
    from benchmark.reference import glm_cd

    for seed in seeds:
        problem = recipe.make(config, seed)
        ref = glm_cd.fit(problem, config)
        print(json.dumps({"seed": seed, "variant": "reference",
                          "history": ref["history"].tolist()}), flush=True)
        for variant in args.variants.split(","):
            t0 = time.perf_counter()
            storage = "bfloat16" if variant == "control" else "float32"
            planted = (faults.FAULTS[variant]() if variant in faults.FAULTS
                       else contextlib.nullcontext())
            with planted:
                job = jobs.build(config, workload, problem, storage=storage)
                job.warm_up(seed)
                window = job.window(0.0, seed)  # one job
                job.after_window(window)
            counters = job.counters(window)
            job.release()
            del job
            drop_programs()
            values = check.numbers(problem, config, window, ref)
            job_s = window["seconds"]
            history = window["histories"][0].tolist()
            del window
            drop_programs()
            print(json.dumps({
                "workload": args.workload, "seed": seed, "variant": variant,
                "platform": device["platform"], "n_rows": config["n_rows"],
                "job_s": job_s, "total_s": time.perf_counter() - t0,
                "fe_iterations": counters["fe_iterations_per_update"],
                "fe_stop_margins": counters["fe_stop_margins"],
                "re_iterations": counters["re_iterations"],
                "history": history,
                "numbers": values}), flush=True)
        del problem
    return 0


if __name__ == "__main__":
    sys.exit(main())
