"""``mf_refit_roofline``: the refit of B's share of its roofline.

Least time of one refit: the iterations its solver reports, each two reads
of every true row's features and factors (``work_model_game``:
2.n.(d + k).4 B an iteration) at the chip's HBM peak, or their FLOPs
(4.n.d.k an iteration) at the chip's peak FLOP/s, whichever is larger; at
these shapes the bytes bound it. Over the refit's device-busy time in the
trace (``mf_refit_ms``'s probe span). ``None``, never 0, where nothing is
read."""

from benchmark import work_model, work_model_game


def read(ctx):
    busy = ((ctx.get("trace") or {}).get("probe_busy_s") or {}).get(
        "mf_refit")
    runs = (ctx.get("probes") or {}).get("mf_refit")
    mf = (ctx.get("counters") or {}).get("mf")
    if not busy or not runs or not mf:
        return None
    counters = ctx["counters"]
    spec = next(iter(mf.values()))
    n, k = counters["n_rows"], spec["factors"]
    d = counters["groups"][spec["group"]]["d"]
    peaks = work_model.peaks_of(ctx)
    least = sum(max(
        work_model_game.refit_iteration_bytes(n, d, k) * r["iterations"]
        / peaks["hbm_bytes_per_s"],
        work_model_game.refit_value_and_grad_flops(n, d, k)
        * r["iterations"] / peaks["flops_per_s_bf16"]) for r in runs)
    if least <= 0:
        return None
    return 100.0 * least / sum(busy)
