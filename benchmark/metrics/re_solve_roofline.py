"""``re_solve_roofline``: the random-effect solve's share of its roofline.

Least time of one sweep: the larger of one read of every block plus the
coefficients written, at the chip's HBM peak, and the iterations' FLOPs
(4.rows.d per entity iteration, at the true rows and width) at the chip's
peak FLOP/s; at these shapes the bytes bound it. Over the sweep's
device-busy time in the trace (``re_solve_ms``'s): every bucket, whichever
path the program's guard gave it (the fused kernel or the vmapped solver),
with the layout changes and the gathers around them. The kernel's own
events over the traced jobs are summed in the line's ``notes``
(``trace_sums``, as the workload file names them)."""

from benchmark import work_model


def read(ctx):
    busy = ((ctx.get("trace") or {}).get("probe_busy_s") or {}).get(
        "re_solve")
    runs = (ctx.get("probes") or {}).get("re_solve")
    counters = ctx.get("counters")
    if not busy or not runs or not counters:
        return None
    peaks = work_model.peaks_of(ctx)
    least_bytes = work_model.re_sweep_bytes(counters["buckets"]) \
        / peaks["hbm_bytes_per_s"]
    least = sum(max(least_bytes,
                    work_model.value_and_grad_flops(
                        r["row_iterations"], counters["d_entity"])
                    / peaks["flops_per_s_bf16"]) for r in runs)
    return 100.0 * least / sum(busy)
