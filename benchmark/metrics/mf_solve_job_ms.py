"""``mf_solve_job_ms``: the factored coordinate's own phases inside the job
users run (``mf_solve_ms`` is its whole update alone from zero, under a probe
span): the summed device time of the operations whose innermost scope is
``photon.mf.flatten``, ``.project``, ``.latent`` or ``.refit``
(``scopes.MF_SCOPES``; its exchange is in ``exchange_ms``), over the traced
jobs, per job. Read through the block's instruction table
(``benchmark/scope_seconds.py``); nothing where there is no trace or no
table."""

from benchmark import scope_seconds


def read(ctx):
    from photon_ml_tpu.telemetry import scopes

    return scope_seconds.leaf_ms(ctx, scopes.MF_SCOPES)
