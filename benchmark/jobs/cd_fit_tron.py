"""Job kind ``cd_fit_tron``: one L2 GLM fit by trust-region Newton (TRON)
over a dense matrix, through the program's entry.

``cd_fit``'s job over ONE fixed-effect coordinate whose optimizer string
names TRON: one job is ``CoordinateDescent({"fixed":
FixedEffectCoordinate(...)}, task).run(iterations, seed)`` from a zero
model, and the timed path, the window, the kept answers, the traced segment
and the probe are ``CdFitJob``'s own. What differs is what the job counts:
a TRON solve's work is its CG steps (one Hessian-vector product, a matvec
and an rmatvec, each) and its outer steps attempted, accepted or rejected,
which the program reports in its result (``OptimizerResult.cg_iterations``,
``.attempted_iterations``). A program that does not report them cannot run
this cell: the job says so at build, before any work.

The check holds the product the CG ran to the reference's: the last outer
step's CG keeps, in the solve's result, the point it ran at, the step it
returned and the residual it carried (``OptimizerResult.cg_point``,
``.cg_step``, ``.cg_residual``; ``checks/cd_fit_tron.py``).

``storage="bfloat16"`` stores X through the program's own
``DenseFeatures.bf16`` path (``cd_fit``'s): the lower-precision control of
the ``correct`` comparison, never a cell.
"""

from __future__ import annotations

import dataclasses
from typing import Dict

import numpy as np

from benchmark import work_model_tron
from benchmark.jobs.cd_fit import CdFitJob


class CdFitTronJob(CdFitJob):
    def __init__(self, config: dict, workload: dict, problem,
                 storage: str = "float32"):
        from photon_ml_tpu.optimization.convergence import OptimizerResult

        if "cg_iterations" not in {
                f.name for f in dataclasses.fields(OptimizerResult)}:
            raise RuntimeError(
                "the program does not report a TRON solve's CG steps "
                "(OptimizerResult.cg_iterations): cd_fit_tron cannot count "
                "its work")
        fixed = config["fixed"]
        if config.get("random") or config["updating_sequence"] != [
                fixed["name"]]:
            raise ValueError("cd_fit_tron runs one fixed-effect coordinate")
        if "TRON" not in fixed["optimizer"].upper():
            raise ValueError("cd_fit_tron runs a TRON optimizer string")
        super().__init__(config, workload, problem, storage=storage)

    # -- what the program counted ---------------------------------------------

    def counters(self, window: dict) -> Dict[str, object]:
        """The window's last job as the program reports it: the accepted
        iterations of every update, the CG steps and attempted outer steps
        of all of them (``FixedEffectCoordinate.tron_work``), the passes
        over X they stand for (``work_model_tron.passes``: the solves' and
        the block's scoring pass, one a sweep) and the FLOPs."""
        n, d = self.problem.x.shape
        trackers = window["kept"]["last"]["trackers"][self.fixed_name]
        cg, attempted = self.coords[self.fixed_name].tron_work(trackers)
        iterations = [int(np.asarray(tr.iterations)) for tr in trackers]
        return {"fe_iterations_per_update": iterations,
                "fe_iterations": float(sum(iterations)),
                "updates": float(len(trackers)),
                "cg_steps": float(cg), "tron_steps": float(attempted),
                "passes": work_model_tron.passes(len(trackers), attempted, cg,
                                                 self.iterations),
                "hvp_passes": work_model_tron.hvp_passes(cg),
                "flops": work_model_tron.job_flops(
                    n, d, len(trackers), attempted, cg, self.iterations)}


def build(config: dict, workload: dict, problem, **kw) -> CdFitTronJob:
    return CdFitTronJob(config, workload, problem, **kw)
