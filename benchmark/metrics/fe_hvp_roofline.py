"""``fe_hvp_roofline``: the trust-region solve's Hessian-vector products'
share of their roofline.

Bandwidth-bound: least time = the CG steps a job reports
(``counters.cg_steps``: ``OptimizerResult.cg_iterations``) x 2 reads of X
(n.d.4 B: ``work_model_tron.hvp_bytes``, from the configuration's shape) at
the chip's HBM peak, over those products' device time inside the job
(``fe_hvp_job_ms``'s). Every job of a run fits the same problem, so the
traced jobs run the CG steps the window's last job reports. ``None``, never
0, where nothing was read."""

from benchmark import work_model, work_model_tron
from benchmark.metrics import fe_hvp_job_ms


def read(ctx):
    cg = (ctx.get("counters") or {}).get("cg_steps")
    ms = fe_hvp_job_ms.read(ctx)
    if not cg or not ms:
        return None
    n, d = work_model_tron.shape_of(ctx["config"])
    least = (work_model_tron.hvp_bytes(n, d, cg)
             / work_model.peaks_of(ctx)["hbm_bytes_per_s"])
    return 100.0 * least / (ms / 1e3)
