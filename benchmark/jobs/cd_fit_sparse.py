"""Job kind ``cd_fit_sparse``: one L2 GLM fit over a hashed sparse matrix,
through the program's entry.

``cd_fit``'s job over ONE fixed-effect coordinate whose matrix is sparse:
one job is ``CoordinateDescent({"fixed": FixedEffectCoordinate(...)},
task).run(iterations, seed)`` from a zero model, and the timed path, the
window, the kept answers and the traced segment are ``CdFitJob``'s own
methods. What differs is how the matrix gets to the program: the recipe's
plain device arrays (``cols i32[n, k]``, ``vals f32[n, k]``) are handed to
the program's one entry for a sparse matrix that is already on the device,
``photon_ml_tpu.ops.features.sparse_rows_to_device``, which counts it
there and chooses its layout. No layout is named here.

``storage="bfloat16"`` hands the entry the values cast to bfloat16 (the
indices stay exact): the lower-precision control of the ``correct``
comparison, owned by this job kind, never a cell.
"""

from __future__ import annotations

from typing import Callable, Dict

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.jobs.cd_fit import CdFitJob
from benchmark.trace_reduce import PROBE_SPAN


class CdFitSparseJob(CdFitJob):
    def __init__(self, config: dict, workload: dict, problem,
                 storage: str = "float32"):
        from photon_ml_tpu.algorithm.coordinate_descent import (
            CoordinateDescent,
        )
        from photon_ml_tpu.algorithm.coordinates import FixedEffectCoordinate
        from photon_ml_tpu.data.shard_cache import StreamedFixedEffectData
        from photon_ml_tpu.ops.features import sparse_rows_to_device
        from photon_ml_tpu.ops.glm_objective import GLMBatch
        from photon_ml_tpu.optimization.config import (
            GLMOptimizationConfiguration,
        )
        from photon_ml_tpu.types import TaskType

        if config.get("dtype", "float32") != "float32":
            raise ValueError("cd_fit_sparse runs float32 configurations")
        if config.get("random") or config["updating_sequence"] != [
                config["fixed"]["name"]]:
            raise ValueError("cd_fit_sparse runs one fixed-effect coordinate")
        self.problem = problem
        self.iterations = int(config["iterations"])
        task = TaskType(config["task"])
        fixed = config["fixed"]
        n, d = problem.n_rows, problem.n_features
        vals = (problem.vals.astype(jnp.bfloat16) if storage == "bfloat16"
                else problem.vals)
        self.features = sparse_rows_to_device(problem.cols, vals, d)
        batch = GLMBatch(self.features, problem.labels, problem.offsets,
                         problem.weights)
        self.fixed_name = fixed["name"]
        self.coords = {self.fixed_name: FixedEffectCoordinate(
            name=self.fixed_name,
            data=StreamedFixedEffectData("global", batch, n, d, {}),
            feature_shard_id="global", task_type=task,
            config=GLMOptimizationConfiguration.parse(fixed["optimizer"]))}
        # what the program's chooser counted and chose, as the program
        # reports it
        self.layout, _ = self.coords[self.fixed_name].sparse_work()
        self.cd = CoordinateDescent(self.coords, task)
        self._last_w = None
        self._matvec = jax.jit(lambda feats, w: feats.matvec(w))
        self._rmatvec = jax.jit(lambda feats, u: feats.rmatvec(u))

    def run_job(self, k: int) -> dict:
        answer = super().run_job(k)
        self._last_w = answer["coefs"][self.fixed_name]
        return answer

    # -- what the program counted ---------------------------------------------

    def counters(self, window: dict) -> Dict[str, object]:
        """The solver's iterations of the window's last job, as the program
        reports them (``OptimizerResult.iterations`` per update), the sparse
        products they stand for (a margin-cached L-BFGS solve of ``it``
        iterations is ``it + 1`` matvec and ``it + 1`` rmatvec; the block
        scores once a sweep: one matvec more), and what the program's
        chooser counted and chose for the matrix."""
        trackers = window["kept"]["last"]["trackers"][self.fixed_name]
        its = [float(np.asarray(tr.iterations)) for tr in trackers]
        matvecs = sum(i + 1 for i in its) + self.iterations
        rmatvecs = sum(i + 1 for i in its)
        lc = self.layout
        return {"fe_iterations_per_update": its, "fe_iterations": sum(its),
                "updates": float(len(its)), "matvecs": matvecs,
                "rmatvecs": rmatvecs, "products": matvecs + rmatvecs,
                "nnz": lc.nnz, "slots": lc.slots, "layout": lc.layout,
                "max_col_degree": lc.max_col_degree,
                "slots_per_row": lc.slots_per_row}

    # -- spans around single layers, outside the window ------------------------

    def probes(self) -> Dict[str, Callable[[], dict]]:
        """``fe_solve`` as ``cd_fit``'s (the solve alone from zero), and
        each sparse product alone under a span of its own: one
        ``features.matvec(w)`` at the last job's coefficients, one
        ``features.rmatvec(u)`` at an n-vector of a residual's scale."""
        out = {"fe_solve": super().probes()["fe_solve"]}
        u = self.problem.labels - 0.5

        def matvec():
            with jax.profiler.TraceAnnotation(PROBE_SPAN + "fe_matvec"):
                jax.block_until_ready(
                    self._matvec(self.features, self._last_w))
            return {"products": 1.0}

        def rmatvec():
            with jax.profiler.TraceAnnotation(PROBE_SPAN + "fe_rmatvec"):
                jax.block_until_ready(self._rmatvec(self.features, u))
            return {"products": 1.0}

        out["fe_matvec"] = matvec
        out["fe_rmatvec"] = rmatvec
        return out

    def kernel_routing(self) -> dict:
        """The layout the program's chooser picked, and the recipe's
        column-degree summary (in every run's ``notes.routing``: two seeds
        can be seen to have been handed the same work)."""
        return {"layout": self.layout.layout,
                "backend": jax.default_backend(),
                "degrees": self.problem.notes}

    def release(self) -> None:
        super().release()
        self.features = None
        self._last_w = None


def build(config: dict, workload: dict, problem, **kw) -> CdFitSparseJob:
    return CdFitSparseJob(config, workload, problem, **kw)
