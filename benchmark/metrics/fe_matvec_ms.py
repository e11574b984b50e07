"""``fe_matvec_ms``: one sparse product of the program's matrix,
``features.matvec(w)`` (X.w at the last job's coefficients), run alone after the traced jobs: the
device-busy time inside its ``bench.probe.fe_matvec`` span, from the
profiler's trace, mean of the repeats (one warm call before the trace)."""


from benchmark import work_model_sparse


def read(ctx):
    return work_model_sparse.product_ms(ctx, "fe_matvec")
