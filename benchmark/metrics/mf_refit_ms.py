"""``mf_refit_ms``: the factored coordinate's refit of B alone (the program's
``_solve_latent_matrix`` from B0 over the batch its update flattens, at the
last job's factors), run alone after
the traced jobs: the device-busy time inside its ``bench.probe.mf_refit``
span, from the profiler's trace, mean of the repeats. Nothing where the job
has no such probe.

Why a probe of its own and not a part of ``mf_solve``'s: inside that span
the refit's operations cannot be told from the latent solves' (the
reduction keeps an operation's name and drops its scope path, ``PERF.md``
section 7 wiring (1); in the compiled block they are ``%fusion.<n>`` like
the rest), so the refit is timed where it runs alone. The program has no
public entry for one refit, so the job kind calls the function the
coordinate's update calls (``_solve_latent_matrix`` over
``_flatten_factored_static`` / ``_flatten_gammas``' batch), at a state
that is near but not on a job's path (B0 with the LAST alternation's
factors; a job's refits start from B0 with the first alternation's, then
from B1): the work an iteration does is the same, the iteration count is
the probe's own and is what the roofline divides by. When the reduction
keeps the scope path, ``photon.mf.refit`` in the job replaces this
probe."""


from benchmark.metrics.mf_solve_ms import probe_ms


def read(ctx):
    return probe_ms(ctx, "mf_refit")
