"""``fe_rmatvec_roofline``: the sparse product's share of its roofline.

Bandwidth-bound: least time = 8 B a TRUE non-zero (its value and its column
id) + 4 (n + d) B (the two vectors) at the chip's HBM peak
(``work_model_sparse.product_bytes``, from the configuration's shape:
whatever the layout stores or copies counts against the program), over the
product's device-busy time in the trace (``fe_rmatvec_ms``'s). Nothing where
nothing was read."""

from benchmark import work_model, work_model_sparse


def read(ctx):
    if not work_model_sparse.probe_busy(ctx, "fe_rmatvec"):
        return None
    return work_model_sparse.product_roofline(
        ctx, "fe_rmatvec", work_model.peaks_of(ctx)["hbm_bytes_per_s"])
