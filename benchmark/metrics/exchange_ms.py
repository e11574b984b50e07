"""``exchange_ms``: the score exchange inside the job users run: the summed
device time of the operations whose innermost scope is ``photon.re.gather``,
``photon.re.margins`` or ``photon.re.scatter`` (``scopes.EXCHANGE_SCOPES``:
the residual gathered into the slots, the margins, their way back by row),
every entity coordinate's, over the traced jobs, per job, mean over chips.
Read through the block's instruction table (``benchmark/scope_seconds.py``);
nothing where there is no trace or no table."""

from benchmark import scope_seconds


def read(ctx):
    from photon_ml_tpu.telemetry import scopes

    return scope_seconds.leaf_ms(ctx, scopes.EXCHANGE_SCOPES)
