"""``fe_solve_roofline``: the fixed-effect solve's share of its roofline.

Bandwidth-bound: least time = iterations x 2 reads of X (n.d.4 B) at the
chip's HBM peak, over the solve's device-busy time in the trace
(``fe_solve_ms``'s). Only where the solver's iterations are whole passes
(L-BFGS); TRON's CG steps are not reported, so the reader returns nothing
there."""

from benchmark import work_model


def read(ctx):
    busy = ((ctx.get("trace") or {}).get("probe_busy_s") or {}).get(
        "fe_solve")
    runs = (ctx.get("probes") or {}).get("fe_solve")
    if not busy or not runs or work_model.uses_tron(ctx["config"]["fixed"]):
        return None
    n, d = ctx["config"]["n_rows"], ctx["config"]["fixed"]["d"]
    bw = work_model.peaks_of(ctx)["hbm_bytes_per_s"]
    least = sum(r["iterations"] for r in runs) \
        * work_model.fe_iteration_bytes(n, d) / bw
    return 100.0 * least / sum(busy)
