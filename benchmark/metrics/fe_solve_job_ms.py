"""``fe_solve_job_ms``: the fixed-effect solve inside the job users run
(``fe_solve_ms`` is the same layer alone from zero, under a probe span): the
summed device time of the operations whose innermost scope is
``photon.fe.solve`` (a sparse matrix's products included), over the traced
jobs, per job, mean over chips. Read through the block's instruction table
(``benchmark/scope_seconds.py``); nothing where there is no trace or no
table."""

from benchmark import scope_seconds


def read(ctx):
    from photon_ml_tpu.telemetry import scopes

    return scope_seconds.leaf_ms(ctx, (scopes.FE_SOLVE,))
