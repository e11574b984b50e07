"""``fit_mfu``: the whole fit's share of the chip's peak FLOP/s.

FLOPs the jobs needed: 4.n.d per value-and-gradient, one per solver
iteration the program reports (``OptimizerResult.iterations``: the fixed
effect's, and every entity's at its own rows and width), over the window's
seconds per job times the peak in ``peaks.json``. A bandwidth-bound
model: the number bounds claims, it is no target. TRON reports no CG
steps, so where a coordinate runs TRON the work is not known and the
reader returns nothing."""

from benchmark import work_model


def read(ctx):
    config = ctx["config"]
    if not ctx.get("counters") or any(
            work_model.uses_tron(c)
            for c in [config["fixed"]] + config.get("random", [])):
        return None
    fit_s = ctx["window"]["seconds"] / ctx["window"]["attempted"]
    peak = work_model.peaks_of(ctx)["flops_per_s_bf16"]
    return 100.0 * ctx["counters"]["flops"] / fit_s / peak
