"""Job kind ``cd_fit``: one GLM / GLMix fit through the program's entry.

One job is ``CoordinateDescent(coords, task).run(iterations, seed)`` from
zero models over the coordinates ``GameEstimator.fit`` would build
(``photon_ml_tpu/estimators/game_estimator.py:148-179``), ended by
``block_until_ready`` on the final parameters with the objective history
materialised. The coordinates and the ``CoordinateDescent`` object are
built once; every job of a window runs on that one object, so only the
first compiles. The window is this job kind's own: jobs back to back from
zero until the seconds have passed, ended on a job boundary. What the
check needs besides (the kept models' scores on the training rows, by the
program's ``Coordinate.score``) is computed when the window has closed and
is in no metric.

The problem's plain arrays (``recipes/``) are wrapped in the program's
own containers here and nowhere else: ``StreamedFixedEffectData`` over a
``GLMBatch(DenseFeatures(x), ...)``, and ``RandomEffectDataset`` over
``EntityBlock``s. ``storage="bfloat16"`` stores the fixed-effect matrix
through the program's own ``DenseFeatures.bf16`` path: the lower-
precision control of the ``correct`` comparison, never a cell.
"""

from __future__ import annotations

import time
from typing import Callable, Dict

import jax
import numpy as np

from benchmark.trace_reduce import JOB_SPAN, PROBE_SPAN


class CdFitJob:
    def __init__(self, config: dict, workload: dict, problem,
                 storage: str = "float32"):
        from photon_ml_tpu.algorithm.coordinate_descent import (
            CoordinateDescent,
        )
        from photon_ml_tpu.algorithm.coordinates import (
            FixedEffectCoordinate,
            RandomEffectCoordinate,
        )
        from photon_ml_tpu.data.random_effect import (
            EntityBlock,
            RandomEffectDataConfiguration,
            RandomEffectDataset,
        )
        from photon_ml_tpu.data.shard_cache import StreamedFixedEffectData
        from photon_ml_tpu.ops.features import DenseFeatures
        from photon_ml_tpu.ops.glm_objective import GLMBatch
        from photon_ml_tpu.optimization.config import (
            GLMOptimizationConfiguration,
        )
        from photon_ml_tpu.types import TaskType

        if config.get("dtype", "float32") != "float32":
            raise ValueError("cd_fit runs float32 configurations")
        self.problem = problem
        self.iterations = int(config["iterations"])
        task = TaskType(config["task"])
        fixed = config["fixed"]
        n, d = problem.x.shape
        feats = (DenseFeatures.bf16(problem.x) if storage == "bfloat16"
                 else DenseFeatures(problem.x))
        batch = GLMBatch(feats, problem.labels, problem.offsets,
                         problem.weights)
        coords = {}
        by_name = {fixed["name"]: FixedEffectCoordinate(
            name=fixed["name"],
            data=StreamedFixedEffectData("global", batch, n, d, {}),
            feature_shard_id="global", task_type=task,
            config=GLMOptimizationConfiguration.parse(fixed["optimizer"]))}
        for g in config.get("random", []):
            dataset = RandomEffectDataset(
                config=RandomEffectDataConfiguration.parse(g["data_config"]),
                blocks=[EntityBlock(b.x, b.labels, b.offsets, b.weights,
                                    b.row_ids, b.feat_idx)
                        for b in problem.buckets],
                passive_blocks=[None] * len(problem.buckets),
                entity_codes=[b.codes for b in problem.buckets],
                vocabulary=np.arange(problem.n_entities).astype(str),
                n_rows=n, num_global_features=problem.d_entity)
            by_name[g["name"]] = RandomEffectCoordinate(
                name=g["name"], dataset=dataset, task_type=task,
                config=GLMOptimizationConfiguration.parse(g["optimizer"]))
        for name in config["updating_sequence"]:
            coords[name] = by_name[name]
        self.coords = coords
        self.fixed_name = fixed["name"]
        self.cd = CoordinateDescent(coords, task)
        # true rows of every entity, bucket by bucket
        self._rows = [np.asarray((b.row_ids < n).sum(axis=1))
                      for b in problem.buckets]

    # -- the timed path ------------------------------------------------------

    def run_job(self, k: int) -> dict:
        """One fit from zero; blocks until its parameters are there."""
        with jax.profiler.TraceAnnotation(JOB_SPAN):
            with jax.profiler.TraceAnnotation("bench.run"):
                # dispatch of the fused block, the wait for its history
                result = self.cd.run(self.iterations,
                                     seed=int(k) % (1 << 31))
            with jax.profiler.TraceAnnotation("bench.settle"):
                models = {name: result.model.get_model(name)
                          for name in self.coords}
                coefs = {name: self._coefs_of(name, m)
                         for name, m in models.items()}
                jax.block_until_ready(coefs)
        return {"history": np.asarray(result.objective_history, np.float64),
                "coefs": coefs, "models": models,
                "trackers": result.trackers}

    def warm_up(self, seed: int) -> None:
        self.run_job(seed)

    def window(self, seconds: float, seed: int) -> dict:
        """Jobs back to back for ``seconds``, ended on a job boundary.
        Every job's objective history is kept, and the whole answer of
        three: the first, the last, and one drawn from the seed."""
        rng = np.random.default_rng([int(seed), 7])
        histories, kept = [], {}
        jobs = 0
        t_start = time.perf_counter()
        while True:
            answer = self.run_job(seed + 1 + jobs)
            jobs += 1
            histories.append(answer["history"])
            if jobs == 1:
                kept["first"] = answer
            elif rng.integers(0, jobs - 1) == 0:
                kept["drawn"] = answer  # one of jobs 2.., equally likely
            now = time.perf_counter()
            if now - t_start >= seconds:
                break
        kept["last"] = answer
        return {"seconds": now - t_start, "attempted": jobs,
                "histories": histories, "kept": kept}

    def after_window(self, window: dict) -> None:
        """The kept models' scores on the training rows, for the check."""
        for answer in window["kept"].values():
            if "scores" in answer:
                continue
            total = None
            for name, coord in self.coords.items():
                s = coord.score(answer["models"][name])
                total = s if total is None else total + s
            answer["scores"] = jax.block_until_ready(total)

    def traced(self, seed: int, jobs: int) -> dict:
        """What a traced run profiles: ``jobs`` more jobs, then each
        layer's solve alone from zero under a span of its own (one warm
        call each before the profiler starts is the caller's: see
        ``warm_traced``). Returns what the probes reported."""
        for k in range(jobs):
            self.run_job(seed + k)
        return {layer: [probe() for _ in range(jobs)]
                for layer, probe in self.probes().items()}

    def warm_traced(self) -> None:
        for probe in self.probes().values():
            probe()

    def _coefs_of(self, name, model):
        if name == self.fixed_name:
            return model.glm.coefficients.means
        return list(model.local_coefs)

    # -- what the program counted ---------------------------------------------

    def counters(self, window: dict) -> Dict[str, float]:
        """Solver iterations of the window's last job, as the program
        reports them (``OptimizerResult.iterations`` per coordinate
        update), and the value-and-gradient work they stand for, at the
        true sizes."""
        n, d = self.problem.x.shape
        trackers = window["kept"]["last"]["trackers"]
        out = {"fe_iterations": 0.0, "re_iterations": 0.0,
               "re_row_iterations": 0.0, "updates": 0.0}
        out["fe_iterations_per_update"] = [
            float(np.asarray(tr.iterations))
            for tr in trackers[self.fixed_name]]
        out["fe_iterations"] = sum(out["fe_iterations_per_update"])
        # how far from its threshold the stopping test was: the decreases
        # of the last two iterations over tol * |f_0|, per update
        tol = float(self.coords[self.fixed_name].config.tolerance)
        out["fe_stop_margins"] = []
        for tr in trackers[self.fixed_name]:
            f = np.asarray(tr.value_history, np.float64)[
                :int(np.asarray(tr.iterations)) + 1]
            out["fe_stop_margins"].append(
                (np.abs(np.diff(f))[-2:] / (tol * abs(f[0]))).tolist())
        out["updates"] += len(out["fe_iterations_per_update"])
        for name in self.coords:
            if name == self.fixed_name:
                continue
            for per_bucket in trackers[name]:
                out["updates"] += 1
                for tr, rows in zip(per_bucket, self._rows):
                    its = np.asarray(tr.iterations, np.float64)
                    out["re_iterations"] += float(its.sum())
                    out["re_row_iterations"] += float((its * rows).sum())
        out["buckets"] = [list(b.x.shape) for b in self.problem.buckets]
        out["d_entity"] = self.problem.d_entity
        out["flops"] = (4.0 * n * d * out["fe_iterations"]
                        + 4.0 * self.problem.d_entity
                        * out["re_row_iterations"])
        return out

    # -- spans around single layers, outside the window ------------------------

    def probes(self) -> Dict[str, Callable[[], dict]]:
        """Layer name -> a call that runs that layer's solve once from
        zero under the span ``bench.probe.<layer>``, blocks, and returns
        the iterations the program reports. The trace gives the time."""
        key = jax.random.PRNGKey(0)

        def fe():
            coord = self.coords[self.fixed_name]
            model = coord.initialize_model()
            with jax.profiler.TraceAnnotation(PROBE_SPAN + "fe_solve"):
                new, result = coord.update_model(model, None, key)
                jax.block_until_ready(new.glm.coefficients.means)
            return {"iterations": float(np.asarray(result.iterations))}

        out = {"fe_solve": fe}
        for name, coord in self.coords.items():
            if name == self.fixed_name:
                continue

            def re(coord=coord):
                model = coord.initialize_model()
                with jax.profiler.TraceAnnotation(PROBE_SPAN + "re_solve"):
                    new, results = coord.update_model(model, None, key)
                    jax.block_until_ready(new.local_coefs)
                its = [np.asarray(r.iterations, np.float64)
                       for r in results]
                return {"iterations": float(sum(i.sum() for i in its)),
                        "row_iterations": float(sum(
                            (i * rows).sum()
                            for i, rows in zip(its, self._rows)))}

            out["re_solve"] = re
        return out

    def kernel_routing(self) -> dict:
        """Which random-effect path the program's guard picked."""
        from photon_ml_tpu.algorithm import coordinates

        return {"fallbacks": sorted(coordinates._FALLBACK_WARNED),
                "backend": jax.default_backend()}

    def release(self) -> None:
        """Drop the program's objects, so its buffers can be freed."""
        self.cd = None
        self.coords = None


def build(config: dict, workload: dict, problem, **kw) -> CdFitJob:
    return CdFitJob(config, workload, problem, **kw)
