"""``re_solve_job_ms``: the random-effect solves inside the job users run
(``re_solve_ms`` is the same layer alone from zero, under a probe span): the
summed device time of the operations whose innermost scope is
``photon.re.solve``, every size class, kernel and fallback, over the traced
jobs, per job, mean over chips. Read through the block's instruction table
(``benchmark/scope_seconds.py``); nothing where there is no trace or no
table."""

from benchmark import scope_seconds


def read(ctx):
    from photon_ml_tpu.telemetry import scopes

    return scope_seconds.leaf_ms(ctx, (scopes.RE_SOLVE,))
