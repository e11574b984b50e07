"""``fe_cg_steps``: the CG steps of a job's trust-region solves (one
Hessian-vector product each), as the program reports them
(``OptimizerResult.cg_iterations``, the window's last job: every job of a
run fits the same problem). Beside ``fit_s``, it says whether a change in
the time came with a change in the work the solver chose. Nothing where
the program reports no such count."""


def read(ctx):
    cg = (ctx.get("counters") or {}).get("cg_steps")
    return None if cg is None else float(cg)
