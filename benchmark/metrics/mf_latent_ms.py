"""``mf_latent_ms``: the factored coordinate's latent solves alone (the
program's ``_solve_factored_block`` over every size class, from zero
factors against B0: the projection ``x B^T`` and the solve at width k, the
fused kernel or the vmapped solver as the guard decides by ``r x k``), run
alone after the traced jobs: the device-busy time inside its
``bench.probe.mf_latent`` span, from the profiler's trace, mean of the
repeats. One alternation's worth: a job runs ``mf``'s first number of them.
Nothing where the job has no such probe."""


from benchmark.metrics.mf_solve_ms import probe_ms


def read(ctx):
    return probe_ms(ctx, "mf_latent")
