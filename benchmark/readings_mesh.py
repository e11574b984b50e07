#!/usr/bin/env python3
"""``readings.py`` for a cell whose check brings its own reference and
whose job needs more than one chip.

    python3 benchmark/readings_mesh.py --workload glmix-20m.fit4 \
        --seeds 1,2 --variants program,control,exchange_left_out

The same readings in the same form (one JSON line per seed and variant:
the program as the configuration states it, the bfloat16-X control, the
planted faults of ``faults.py``), with two differences from
``readings.py``, which this file leaves as it is: the device block asks
for the cell's own number of chips, and the reference fit is the check
module's ``reference_fit`` (``glm_cd`` would gather all of X onto every
chip, ``checks/cd_fit_mesh.py``). The benchmark's own runs never run this.
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
    args = ap.parse_args(argv)
    sys.path.insert(0, str(ROOT))
    import jax

    from benchmark import faults, harness
    from photon_ml_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    loaded = harness.load_cell(args.workload)
    config, workload = loaded["config"], loaded["workload"]
    device = harness.device_block(int(loaded["cell"]["chips"]),
                                  require_chip=not args.rehearse_rows)
    recipe = importlib.import_module(f"benchmark.recipes.{config['recipe']}")
    if args.rehearse_rows:
        config = recipe.scale_down(config, args.rehearse_rows)
    jobs = importlib.import_module(f"benchmark.jobs.{workload['job']}")
    check = importlib.import_module(f"benchmark.checks.{workload['job']}")
    for seed in (int(s) for s in args.seeds.split(",")):
        problem = recipe.make(config, seed)
        ref = check.reference_fit(problem, config)
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
            jax.clear_caches()  # a loaded program keeps its temporaries
            gc.collect()
            values = check.numbers(problem, config, window, ref)
            print(json.dumps({
                "workload": args.workload, "seed": seed, "variant": variant,
                "platform": device["platform"], "chips": device["count"],
                "n_rows": config["n_rows"], "job_s": window["seconds"],
                "total_s": time.perf_counter() - t0,
                "fe_iterations": counters["fe_iterations_per_update"],
                "fe_stop_margins": counters["fe_stop_margins"],
                "history": window["histories"][0].tolist(),
                "numbers": values}), flush=True)
            del window
        del problem, ref
    return 0


if __name__ == "__main__":
    sys.exit(main())
