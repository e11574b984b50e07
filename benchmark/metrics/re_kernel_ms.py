"""``re_kernel_ms``: the entity kernel inside the job users run, not the
probe: the summed device time of the operations named
``%pallas_entity_lbfgs*`` (the ``name=`` of the program's ``pallas_call``;
a mode suffix may follow) over the traced jobs, per job. Nothing where no
such operation ran (a parent without the kernel, a CPU run, no trace)."""

from benchmark import trace_reduce

KERNEL_OPS = "%pallas_entity_lbfgs"


def read(ctx):
    trace = ctx.get("trace")
    if not trace or not trace.get("traced_jobs"):
        return None
    seconds = trace_reduce.op_sum(trace, KERNEL_OPS)
    if seconds <= 0:
        return None
    return 1e3 * seconds / trace["traced_jobs"]
