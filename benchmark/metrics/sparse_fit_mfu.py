"""``sparse_fit_mfu``: a sparse fit's share of the chip's peak FLOP/s.

FLOPs the job needed: 2 a TRUE non-zero a product
(``work_model_sparse.product_flops``: from the configuration's shape, not
from what the layout stores) times the products the job reports
(``counters.products``: the solver's own iteration counts, and the block's
scoring pass), over the window's seconds per job times the peak in
``peaks.json``. A sparse GLM is index work: the number is parts in a
million. It bounds later claims; it is no target."""

from benchmark import work_model, work_model_sparse


def read(ctx):
    products = (ctx.get("counters") or {}).get("products")
    if not products:
        return None
    nnz, _, _ = work_model_sparse.shape_of(ctx["config"])
    fit_s = ctx["window"]["seconds"] / ctx["window"]["attempted"]
    peak = work_model.peaks_of(ctx)["flops_per_s_bf16"]
    return (100.0 * products * work_model_sparse.product_flops(nnz)
            / fit_s / peak)
