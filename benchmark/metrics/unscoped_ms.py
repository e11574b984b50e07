"""``unscoped_ms``: the device time of a job that no name of the program
claims: the operations under no ``photon.*`` scope (copies and slices
between the scopes, the scan's bookkeeping) and those whose instruction name
the block's table does not hold (another program's: the zero vectors of a
cold start, a transfer), summed over the traced jobs, per job, mean over
chips. With the solves, the exchange and ``fe_score_ms`` it adds up to the
job's busy time. Read through the block's instruction table
(``benchmark/scope_seconds.py``); nothing where there is no trace or no
table."""

from benchmark import scope_seconds


def read(ctx):
    found = scope_seconds.by_scope(ctx)
    return (found["unscoped"] or None) if found else None
