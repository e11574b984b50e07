"""Job kind ``cd_fit_game``: one GAME fit with a matrix-factorization term
through the program's entry.

``cd_fit``'s job (one ``CoordinateDescent(coords, task).run(iterations,
seed)`` from zero models on one object, jobs back to back ended on a job
boundary, the spans ``bench.job`` / ``bench.probe.*``: all ``CdFitJob``'s
own methods) over the coordinates of a configuration with SEVERAL groups:
a fixed effect, N random effects (``config["random"]``, each over its own
group's blocks, ``problem.groups``) and M factored random effects
(``config["factored"]``, each the low-rank form of the coefficients of the
group it names, over that group's blocks: a ``random`` entry's or a
data-only ``groups`` entry's). This job kind builds its own coordinates:
one ``RandomEffectDataset`` a GROUP.

What the check needs besides ``cd_fit``'s: the ``B0`` each factored
coordinate was initialised with (``window["b0"]``: the check holds it to
the law the configuration states and the reference draws from on its own;
the coordinate is given that law's seed, ``start.seed``), and a factored
coordinate's coefficients as ``{"gammas": [...], "B": ...}``.

Probes (``workload["probes"]``: coordinate -> layer name): each
coordinate's ``update_model`` alone from zero under its
``bench.probe.<layer>`` span, and ``mf_refit``: the factored coordinate's
refit of B alone (the program's ``_solve_latent_matrix`` from B0 over the
program's own flattened batch at the last job's factors), and
``mf_latent``: its latent solves alone (the program's
``_solve_factored_block`` over every size class from zero factors against
B0).
"""

from __future__ import annotations

from typing import Callable, Dict

import jax
import numpy as np

from benchmark.jobs.cd_fit import CdFitJob
from benchmark.trace_reduce import PROBE_SPAN


class CdFitGameJob(CdFitJob):
    def __init__(self, config: dict, workload: dict, problem,
                 storage: str = "float32"):
        from photon_ml_tpu.algorithm.coordinate_descent import (
            CoordinateDescent,
        )
        # FactoredAlternationResult: a program from before the factored
        # coordinate reported its work (its latent solves' iterations, its
        # classes' routing) cannot run this job kind, and fails here, at
        # build.
        from photon_ml_tpu.algorithm.coordinates import (
            FactoredAlternationResult,  # noqa: F401
            FactoredRandomEffectCoordinate,
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
            MFOptimizationConfiguration,
        )
        from photon_ml_tpu.types import TaskType

        if config.get("dtype", "float32") != "float32":
            raise ValueError("cd_fit_game runs float32 configurations")
        self.problem = problem
        self.iterations = int(config["iterations"])
        task = TaskType(config["task"])
        fixed = config["fixed"]
        n, d = problem.x.shape
        feats = (DenseFeatures.bf16(problem.x) if storage == "bfloat16"
                 else DenseFeatures(problem.x))
        batch = GLMBatch(feats, problem.labels, problem.offsets,
                         problem.weights)
        by_name = {fixed["name"]: FixedEffectCoordinate(
            name=fixed["name"],
            data=StreamedFixedEffectData("global", batch, n, d, {}),
            feature_shard_id="global", task_type=task,
            config=GLMOptimizationConfiguration.parse(fixed["optimizer"]))}
        datasets = {}
        randoms = config.get("random", [])
        for g in randoms + config.get("groups", []):
            group = problem.groups[g["name"]]
            datasets[g["name"]] = RandomEffectDataset(
                config=RandomEffectDataConfiguration.parse(g["data_config"]),
                blocks=[EntityBlock(b.x, b.labels, b.offsets, b.weights,
                                    b.row_ids, b.feat_idx)
                        for b in group.buckets],
                passive_blocks=[None] * len(group.buckets),
                entity_codes=[b.codes for b in group.buckets],
                vocabulary=np.arange(group.n_entities).astype(str),
                n_rows=n, num_global_features=group.d_entity)
        for g in randoms:
            by_name[g["name"]] = RandomEffectCoordinate(
                name=g["name"], dataset=datasets[g["name"]], task_type=task,
                config=GLMOptimizationConfiguration.parse(g["optimizer"]))
        self.group_of = {g["name"]: g["name"] for g in randoms}
        self.factored = []
        for f in config.get("factored", []):
            by_name[f["name"]] = FactoredRandomEffectCoordinate(
                name=f["name"], dataset=datasets[f["group"]],
                task_type=task,
                config=GLMOptimizationConfiguration.parse(f["optimizer"]),
                latent_config=GLMOptimizationConfiguration.parse(
                    f["refit_optimizer"]),
                mf_config=MFOptimizationConfiguration.parse(f["mf"]),
                seed=int(f["start"]["seed"]))
            self.group_of[f["name"]] = f["group"]
            self.factored.append(f["name"])
        self.coords = {name: by_name[name]
                       for name in config["updating_sequence"]}
        self.fixed_name = fixed["name"]
        self.cd = CoordinateDescent(self.coords, task)
        # true rows of every entity, bucket by bucket, group by group
        self._rows_of = {
            name: [np.asarray((b.row_ids < n).sum(axis=1))
                   for b in group.buckets]
            for name, group in problem.groups.items()}
        # the B every cold start of a factored coordinate begins from
        self.b0 = {name: np.asarray(
            self.coords[name].initialize_model().projection_matrix)
            for name in self.factored}
        self._layers = dict(workload.get("probes", {}))
        self._last = None
        self._refit_batches = {}

    # -- the timed path: CdFitJob.run_job, with these coefficients ------------

    def _coefs_of(self, name, model):
        if name == self.fixed_name:
            return model.glm.coefficients.means
        if name in self.factored:
            gammas, b = self.coords[name].params_of(model)
            return {"gammas": list(gammas), "B": b}
        return list(model.local_coefs)

    def run_job(self, k: int) -> dict:
        self._last = super().run_job(k)
        return self._last

    def window(self, seconds: float, seed: int) -> dict:
        out = super().window(seconds, seed)
        out["b0"] = dict(self.b0)
        return out

    def after_window(self, window: dict) -> None:
        """``CdFitJob.after_window``, and for every kept answer what each
        factored coordinate's LAST refit says its objective was where it
        stopped (``OptimizerResult.value`` of the last alternation's
        tracker: the batch it was given, as its solver summed it), for the
        check to hold against the reference's objective at the same
        coefficients (``refit_obj_gap``)."""
        super().after_window(window)
        for answer in window["kept"].values():
            answer["refit_values"] = {
                name: float(np.asarray(
                    answer["trackers"][name][-1][-1].value))
                for name in self.factored}

    # -- what the program counted ---------------------------------------------

    def counters(self, window: dict) -> Dict[str, object]:
        """Of the window's last job, as the program's trackers report them:
        every coordinate's solver iterations, the iterations weighted by
        each entity's true rows (the value-and-gradient work at the true
        sizes), and how far from its threshold each capped solve's stopping
        test was; and the FLOPs they stand for (``work_model_game``)."""
        from benchmark import work_model_game

        n, d = self.problem.x.shape
        trackers = window["kept"]["last"]["trackers"]
        fe = trackers[self.fixed_name]
        out: Dict[str, object] = {
            "n_rows": int(n), "d_fixed": int(d),
            "fe_iterations_per_update": [
                float(np.asarray(tr.iterations)) for tr in fe],
            "fe_stop_margins": [_stop_margins(
                tr, self.coords[self.fixed_name].config.tolerance)
                for tr in fe]}
        out["fe_iterations"] = sum(out["fe_iterations_per_update"])
        out["updates"] = float(len(fe))
        out["groups"] = {
            name: {"d": group.d_entity, "entities": group.n_entities,
                   "buckets": [list(b.x.shape) for b in group.buckets]}
            for name, group in self.problem.groups.items()}
        for name, layer in self._layers.items():
            if layer == "re_solve":  # what ``re_solve_roofline`` reads
                group = out["groups"][self.group_of[name]]
                out["buckets"], out["d_entity"] = group["buckets"], group["d"]
        out["re"], out["mf"] = {}, {}
        for name, coord in self.coords.items():
            if name == self.fixed_name:
                continue
            rows = self._rows_of[self.group_of[name]]
            out["updates"] += len(trackers[name])
            if name not in self.factored:
                its = [(np.asarray(tr.iterations, np.float64), r)
                       for update in trackers[name]
                       for tr, r in zip(update, rows)]
                out["re"][name] = {
                    "group": self.group_of[name],
                    "iterations": float(sum(i.sum() for i, _ in its)),
                    "row_iterations": float(sum(
                        (i * r).sum() for i, r in its))}
                continue
            alts = [tr for update in trackers[name] for tr in update]
            latent = [[np.asarray(i, np.float64)
                       for i in tr.latent_iterations] for tr in alts]
            out["mf"][name] = {
                "group": self.group_of[name],
                "factors": int(coord.mf_config.num_factors),
                "alternations": len(alts),
                "refit_iterations": [float(np.asarray(tr.iterations))
                                     for tr in alts],
                "refit_stop_margins": [_stop_margins(
                    tr, coord.latent_config.tolerance) for tr in alts],
                "latent_iterations": [float(sum(i.sum() for i in per))
                                      for per in latent],
                "latent_row_iterations": [float(sum(
                    (i * r).sum() for i, r in zip(per, rows)))
                    for per in latent]}
        out["flops"] = work_model_game.job_flops(out)
        return out

    # -- spans around single layers, outside the window ------------------------

    def probes(self) -> Dict[str, Callable[[], dict]]:
        """Layer -> a call that runs that coordinate's ``update_model`` once
        from zero under ``bench.probe.<layer>`` and returns the iterations
        the program reports; ``mf_refit``: the refit of B alone."""
        out = {"fe_solve": super().probes()["fe_solve"]}
        key = jax.random.PRNGKey(0)
        for name, coord in self.coords.items():
            layer = self._layers.get(name)
            if name == self.fixed_name or layer is None:
                continue
            rows = self._rows_of[self.group_of[name]]

            def solve(coord=coord, layer=layer, rows=rows, name=name):
                model = coord.initialize_model()
                with jax.profiler.TraceAnnotation(PROBE_SPAN + layer):
                    new, results = coord.update_model(model, None, key)
                    jax.block_until_ready(coord.params_of(new))
                if name in self.factored:
                    return {"refit_iterations": [
                        float(np.asarray(r.iterations)) for r in results]}
                its = [np.asarray(r.iterations, np.float64)
                       for r in results]
                return {"iterations": float(sum(i.sum() for i in its)),
                        "row_iterations": float(sum(
                            (i * r).sum() for i, r in zip(its, rows)))}

            out[layer] = solve
        for name in self.factored:
            out["mf_refit"] = self._refit_probe(name)
            out["mf_latent"] = self._latent_probe(name)
        return out

    def _latent_probe(self, name: str) -> Callable[[], dict]:
        """The latent solves alone: the program's own
        ``_solve_factored_block`` over every size class, from zero factors
        against B0 and no residual (the first alternation's half of a
        probe of ``mf_solve``)."""
        import jax.numpy as jnp

        from photon_ml_tpu.algorithm import coordinates as co

        coord = self.coords[name]
        d = coord.dataset.num_global_features
        k = coord.mf_config.num_factors
        rows = self._rows_of[self.group_of[name]]

        @jax.jit
        def solve(blocks, b):
            return [co._solve_factored_block(
                coord._objective, coord.config, block, b, None,
                jnp.zeros((block.x.shape[0], k), block.x.dtype), d
            ).iterations for block in blocks]

        def latent():
            b0 = jnp.asarray(self.b0[name])
            with jax.profiler.TraceAnnotation(PROBE_SPAN + "mf_latent"):
                its = jax.block_until_ready(
                    solve(tuple(coord.dataset.blocks), b0))
            its = [np.asarray(i, np.float64) for i in its]
            return {"iterations": float(sum(i.sum() for i in its)),
                    "row_iterations": float(sum(
                        (i * r).sum() for i, r in zip(its, rows)))}

        return latent

    def _refit_probe(self, name: str) -> Callable[[], dict]:
        """The refit alone: the program's own ``_solve_latent_matrix`` from
        B0 over the batch its update flattens (no residual: from zero, as
        the other probes), at the factors the last job ended with. The
        batch is built once, outside the span."""
        import jax.numpy as jnp

        from photon_ml_tpu.algorithm import coordinates as co
        from photon_ml_tpu.ops.features import KroneckerFeatures
        from photon_ml_tpu.ops.glm_objective import GLMBatch

        coord = self.coords[name]
        d = coord.dataset.num_global_features

        @jax.jit
        def flatten(blocks, gammas):
            x, y, off, w = co._flatten_factored_static(
                blocks, [None] * len(blocks), d)
            return GLMBatch(KroneckerFeatures(
                x, co._flatten_gammas(blocks, gammas)), y, off, w)

        def refit():
            if name not in self._refit_batches:
                self._refit_batches[name] = jax.block_until_ready(flatten(
                    tuple(coord.dataset.blocks),
                    tuple(self._last["coefs"][name]["gammas"])))
            b0 = jnp.asarray(self.b0[name]).reshape(-1)
            with jax.profiler.TraceAnnotation(PROBE_SPAN + "mf_refit"):
                result = co._solve_latent_matrix(
                    coord._objective, coord.latent_config,
                    self._refit_batches[name], b0)
                jax.block_until_ready(result.x)
            return {"iterations": float(np.asarray(result.iterations))}

        return refit

    def kernel_routing(self) -> dict:
        """Every size class of every entity coordinate with the path the
        program's guard gives it (``Coordinate.routing()``)."""
        from photon_ml_tpu.algorithm import coordinates

        return {"fallbacks": sorted(coordinates._FALLBACK_WARNED),
                "backend": jax.default_backend(),
                "classes": {name: coord.routing()
                            for name, coord in self.coords.items()
                            if name != self.fixed_name}}

    def release(self) -> None:
        super().release()
        self._last = None
        self._refit_batches = {}


def _stop_margins(tracker, tolerance: float) -> list:
    """The decreases of a solve's last two iterations over
    tol * |f_0| (``cd_fit``'s ``fe_stop_margins``)."""
    f = np.asarray(tracker.value_history, np.float64)[
        :int(np.asarray(tracker.iterations)) + 1]
    return (np.abs(np.diff(f))[-2:] / (float(tolerance) * abs(f[0]))).tolist()


def build(config: dict, workload: dict, problem, **kw) -> CdFitGameJob:
    return CdFitGameJob(config, workload, problem, **kw)
