"""``fe_hvp_job_ms``: a trust-region (TRON) solve's Hessian-vector products
inside the job users run: the summed device time of the operations under
``photon.fe.hvp`` (one a CG step: a matvec and an rmatvec over the outer
step's curvature weights), over the traced jobs, per job, mean over chips.
Read through the block's instruction table (``benchmark/scope_seconds.py``:
the scope is keyed as a product under its leaf, ``<leaf>/photon.fe.hvp``).
Nothing where there is no trace or no table, or the program has no such
scope."""

from benchmark import scope_seconds


def read(ctx):
    from photon_ml_tpu.telemetry import scopes

    hvp = getattr(scopes, "FE_HVP", None)
    return scope_seconds.product_ms(ctx, hvp) if hvp else None
