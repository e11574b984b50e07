"""``fe_rmatvec_job_ms``: a sparse fixed effect's ``X^T.u`` inside the job
users run (``fe_rmatvec_ms`` is ONE product alone, under a probe span): the
summed device time of the operations under ``photon.fe.rmatvec`` (3 a job on
``sparse-lr.fit``), over the traced jobs, per job. Read through the block's
instruction table (``benchmark/scope_seconds.py``); nothing where there is no
trace or no table, or the matrix is dense."""

from benchmark import scope_seconds


def read(ctx):
    from photon_ml_tpu.telemetry import scopes

    return scope_seconds.product_ms(ctx, scopes.FE_RMATVEC)
