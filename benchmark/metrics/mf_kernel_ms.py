"""``mf_kernel_ms``: the entity kernel's events of the LATENT solves inside
the job users run: the summed device time of the operations named
``pallas_entity_lbfgs*`` whose innermost scope is ``photon.mf.latent``, over
the traced jobs, per job. By name alone the latent solves' calls and the
per-user random effect's cannot be told apart
(``notes.trace_sums.re_kernel_s`` / jobs is both; the rest of it is the
random effect's). Read through the block's instruction table
(``benchmark/scope_seconds.py``); nothing where there is no trace or no
table, or no such event ran."""

from benchmark import scope_seconds


def read(ctx):
    from photon_ml_tpu.telemetry import scopes

    found = scope_seconds.by_scope(ctx)
    return (found["kernel"].get(scopes.MF_LATENT) or None) if found else None
