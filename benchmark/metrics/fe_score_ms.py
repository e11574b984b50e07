"""``fe_score_ms``: what a job runs beside its solves and its exchange under
a name of its own: the fixed effect's scoring pass over X
(``photon.fe.score``) and the objectives (``photon.cd.objective``: the loss
sum and the penalties, once a coordinate update), summed device time over the
traced jobs, per job, mean over chips. Read through the block's instruction
table (``benchmark/scope_seconds.py``); nothing where there is no trace or
no table."""

from benchmark import scope_seconds


def read(ctx):
    from photon_ml_tpu.telemetry import scopes

    return scope_seconds.leaf_ms(ctx, (scopes.FE_SCORE, scopes.CD_OBJECTIVE))
