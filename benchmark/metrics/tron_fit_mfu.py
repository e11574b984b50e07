"""``tron_fit_mfu``: a fit by trust-region Newton's share of the chip's
peak FLOP/s.

FLOPs the job needed (``counters.flops``: ``work_model_tron.job_flops``
from n, d and the counts the program reports: 4.n.d a value and gradient,
2.n.d a margin pass, 4.n.d a Hessian-vector product), over the window's
seconds per job times the peak in ``peaks.json``. ``fit_mfu`` reads nothing
under TRON, whose work it cannot count. A bandwidth-bound model: the number
bounds claims, it is no target."""

from benchmark import work_model


def read(ctx):
    flops = (ctx.get("counters") or {}).get("flops")
    if not flops or "cg_steps" not in ctx["counters"]:
        return None
    fit_s = ctx["window"]["seconds"] / ctx["window"]["attempted"]
    peak = work_model.peaks_of(ctx)["flops_per_s_bf16"]
    return 100.0 * flops / fit_s / peak
