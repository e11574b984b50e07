#!/usr/bin/env python3
"""``readings.py`` for a ``cd_fit_game`` cell: read the numbers that decide
``correct``, over many seeds, in one process: the program as the
configuration states it, the lower-precision control (the program's own
bfloat16 storage of X) and the planted faults of ``faults_game.py``.

    python3 benchmark/readings_game.py --workload game-mf.fit \
        --read program=1,2,3,4,5,6,7,8 --read user_altered=1,2 \
        --read control=1,2,3 --read refit_left_out=1,2

Each ``--read VARIANT=SEEDS`` is one pass, in the order given. ``a+b``
plants two faults in one job, for faults that touch different numbers.
``mxu_default`` is a second control that only a chip can read: the factored
path's six true matrix products at the MXU's default precision (bfloat16
multiplies), where the program asks for exact float32 products.

A trace of the block costs minutes on the chip's host, and every seed deals
the same shapes, so a pass traces ONCE: its first seed's job traces and
compiles ``cd_block``, and every later seed's job, built from that seed's
problem as the cell builds it, runs that same compiled function on its own
arrays (a retrace would show in the line's ``block_traces``). A
pass of faults that only alter the model after the fit
(``faults_game.AFTER_FIT``) runs the sound pass's function when it follows
it directly.

One JSON line per (seed, variant), with the counters that say whether every
seed did the same work, ``fit_s`` over ``--seconds`` of back-to-back jobs,
and the worst-entity numbers under other scales than the check's
(``other_scales``: why the check's scale was chosen). The limits in the
workload file were set from these readings (``PERF.md`` gives them); the
benchmark's own runs never run this.
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


def block_memory(job) -> dict:
    """``memory_analysis()`` of the job's ``cd_block`` as compiled for the
    arguments ``run()`` gives it (a second compile of the warm-up's
    program: the compile cache has it)."""
    cd, seen = job.cd, {}
    fn = cd._fused_block_fn(job.iterations)

    def recorder(*block_args):
        seen["args"] = block_args
        return fn(*block_args)

    cd._block_fns[job.iterations] = recorder
    job.run_job(0)
    cd._block_fns[job.iterations] = fn
    ma = fn.lower(*seen["args"]).compile().memory_analysis()
    return {"arguments": ma.argument_size_in_bytes,
            "outputs": ma.output_size_in_bytes,
            "temporaries": ma.temp_size_in_bytes,
            "aliased": ma.alias_size_in_bytes,
            "code": ma.generated_code_size_in_bytes}


def disown(job) -> None:
    """Drop what ``job``'s coordinates hold, arrays and all: the traced
    block's closure keeps the coordinate OBJECTS alive (it read their
    settings while it traced, and never runs again), and with them a seed's
    7 GB unless they are emptied."""
    for coord in job.coords.values():
        vars(coord).clear()


@contextlib.contextmanager
def mxu_default():
    """The factored path's matrix products without their precision word."""
    from photon_ml_tpu.ops.features import KroneckerFeatures

    asked = KroneckerFeatures.PRECISION
    KroneckerFeatures.PRECISION = None
    try:
        yield
    finally:
        KroneckerFeatures.PRECISION = asked


def other_scales(config: dict, window: dict, ref: dict) -> dict:
    """The worst entity of every group coordinate under the scales the
    check does NOT use: ``median`` (``cd_fit``'s ``max(own norm, the median
    entity's)`` over the whole group) and ``quarter`` (a quarter of that
    median as the floor), over the kept answers."""
    import numpy as np

    from benchmark.checks import cd_fit_game

    out = {}
    for name in config["updating_sequence"]:
        if name == config["fixed"]["name"]:
            continue
        worst = {"median": 0.0, "quarter": 0.0}
        for answer in window["kept"].values():
            diff, norm = (np.concatenate(v) for v in zip(
                *cd_fit_game.entity_gaps(answer["coefs"][name],
                                         ref["coefs"][name])))
            for key, share in (("median", 1.0), ("quarter", 0.25)):
                worst[key] = max(worst[key], float(np.max(
                    diff / np.maximum(norm, share * np.median(norm)))))
        out[name] = worst
    return out


def refit_gradients(config: dict, problem, window: dict) -> dict:
    """A number the check does NOT use, read beside the ones it does: the
    norm of the reference's refit gradient at the program's kept
    coefficients over its norm at B0 with the same factors, the worst kept
    answer's. It depends on where the capped solver stopped, which
    ``refit_obj_gap`` does not (``PERF.md`` section 4 compares the two)."""
    import jax.numpy as jnp

    from benchmark.reference import game_cd

    out = {}
    for name, b0 in window["b0"].items():
        worst = 0.0
        for answer in window["kept"].values():
            _, g = game_cd.refit_objective(problem, config, name,
                                           answer["coefs"])
            _, g0 = game_cd.refit_objective(problem, config, name,
                                            answer["coefs"], b=b0)
            worst = max(worst, float(jnp.linalg.norm(g)
                                     / jnp.linalg.norm(g0)))
        out[name] = worst
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--read", action="append", required=True,
                    metavar="VARIANT=SEED,SEED,...")
    ap.add_argument("--seconds", type=float, default=0.0,
                    help="the window of every sound job's fit_s (0: one job)")
    ap.add_argument("--rehearse-rows", type=int, default=0)
    ap.add_argument("--refit-optimizer", default=None,
                    help="read under another refit string than the "
                         "configuration's (evidence for its cap)")
    ap.add_argument("--block-memory", action="store_true",
                    help="the compiled block's memory_analysis() of the "
                         "first sound job (the sizing rule's numbers)")
    args = ap.parse_args(argv)
    sys.path.insert(0, str(ROOT))
    import jax

    from benchmark import faults_game, harness
    from benchmark.reference import game_cd
    from photon_ml_tpu.utils.compile_cache import enable_compile_cache

    def drop_programs():
        # a loaded program keeps its temporaries reserved on the chip
        jax.clear_caches()
        gc.collect()

    enable_compile_cache()
    loaded = harness.load_cell(args.workload)
    config, workload = loaded["config"], loaded["workload"]
    device = harness.device_block(1, require_chip=not args.rehearse_rows)
    recipe = importlib.import_module(f"benchmark.recipes.{config['recipe']}")
    if args.rehearse_rows:
        config = recipe.scale_down(config, args.rehearse_rows)
    if args.refit_optimizer:
        for f in config["factored"]:
            f["refit_optimizer"] = args.refit_optimizer
    jobs = importlib.import_module(f"benchmark.jobs.{workload['job']}")
    check = importlib.import_module(f"benchmark.checks.{workload['job']}")
    refs = {}  # seed -> the reference's fit, on the host
    sound_fns = None  # the sound pass's compiled blocks
    for item in args.read:
        variant, seeds = item.split("=")
        planted_by = dict(faults_game.FAULTS, mxu_default=mxu_default)
        faults = [f for f in variant.split("+")
                  if f not in ("program", "control")]
        for fault in faults:
            if fault not in planted_by:
                raise KeyError(f"no fault named {fault!r}")
        after_fit = bool(faults) and all(
            f in faults_game.AFTER_FIT for f in faults)
        block_fns = sound_fns if after_fit else None
        if not after_fit:
            sound_fns = None
            drop_programs()
        storage = "bfloat16" if "control" in variant else "float32"
        for seed in (int(s) for s in seeds.split(",")):
            t0 = time.perf_counter()
            problem = recipe.make(config, seed)
            if seed not in refs:
                t_ref = time.perf_counter()
                refs[seed] = jax.device_get(game_cd.fit(problem, config))
                print(json.dumps({
                    "seed": seed, "variant": "reference",
                    "reference_s": time.perf_counter() - t_ref,
                    "history": refs[seed]["history"].tolist()}), flush=True)
            with contextlib.ExitStack() as planted:
                for fault in faults:
                    planted.enter_context(planted_by[fault]())
                job = jobs.build(config, workload, problem, storage=storage)
                if block_fns is None:
                    job.warm_up(seed)  # traces, compiles
                    block_fns = job.cd._block_fns
                    if args.block_memory and variant == "program":
                        print(json.dumps({"seed": seed, "variant": "block",
                                          **block_memory(job)}), flush=True)
                else:
                    # this job's CoordinateDescent looks its block up among
                    # the first job's compiled ones, and finds it there
                    job.cd._block_fns = block_fns
                    # what a CoordinateDescent derives once an object (its
                    # cold start, its training rows) stays out of fit_s
                    job.run_job(seed)
                window = job.window(0.0 if faults else args.seconds, seed)
                job.after_window(window)
            counters = job.counters(window)
            routing = job.kernel_routing()
            traces = {str(k): fn._cache_size()
                      for k, fn in block_fns.items()}
            disown(job)
            job.release()
            del job
            values = check.numbers(problem, config, window, refs[seed])
            gradients = refit_gradients(config, problem, window)
            line = {
                "workload": args.workload, "seed": seed, "variant": variant,
                "platform": device["platform"], "n_rows": config["n_rows"],
                "refit_optimizer": config["factored"][0]["refit_optimizer"],
                "fit_s": window["seconds"] / window["attempted"],
                "jobs": window["attempted"], "block_traces": traces,
                "total_s": time.perf_counter() - t0,
                "memory_peak_bytes": harness.memory_peak_bytes(),
                "fe_iterations": counters["fe_iterations_per_update"],
                "fe_stop_margins": counters["fe_stop_margins"],
                "re": counters["re"], "mf": counters["mf"],
                "flops": counters["flops"],
                "paths": {name: [[c["rows"], c["entities"], c["path"]]
                                 for c in classes]
                          for name, classes in routing["classes"].items()},
                "history": window["histories"][0].tolist(),
                "numbers": values, "refit_grad_gap": gradients,
                "other_scales": other_scales(config, window, refs[seed])}
            del window, problem
            gc.collect()
            print(json.dumps(line), flush=True)
        if variant == "program":
            sound_fns = block_fns
    return 0


if __name__ == "__main__":
    sys.exit(main())
