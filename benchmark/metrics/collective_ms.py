"""``collective_ms``: what the chips spend in collectives, per job: the
summed device time of the operations named ``%all-reduce*``,
``%all-gather*``, ``%reduce-scatter*``, ``%all-to-all*`` and
``%collective-permute*`` (the ``-start`` and ``-done`` halves of an
asynchronous one are two events, both counted) inside the traced jobs,
mean over chips, in milliseconds a job. The score exchange's gather and
reduce of n-vectors across chips and the all-reduce of every fixed-effect
evaluation. Nothing where no such operation ran (one chip, no trace)."""

from benchmark import trace_reduce

COLLECTIVE_OPS = ("%all-reduce", "%all-gather", "%reduce-scatter",
                  "%all-to-all", "%collective-permute")


def read(ctx):
    trace = ctx.get("trace")
    if not trace or not trace.get("traced_jobs"):
        return None
    seconds = sum(trace_reduce.op_sum(trace, p) for p in COLLECTIVE_OPS)
    if seconds <= 0:
        return None
    return 1e3 * seconds / trace["traced_jobs"]
