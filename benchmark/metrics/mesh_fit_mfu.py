"""``mesh_fit_mfu``: the whole fit's share of the peak FLOP/s of all the
chips it ran on. ``fit_mfu``'s count of the operations the jobs needed
(4.n.d per value-and-gradient, one per solver iteration the program
reports), over the window's seconds per job times the peak in
``peaks.json`` times the devices of the job's mesh (``fit_mfu`` knows one
chip). Nothing where the job reports no mesh, or a coordinate runs TRON."""

from benchmark.metrics import fit_mfu


def read(ctx):
    devices = (ctx.get("counters") or {}).get("devices")
    one_chip = fit_mfu.read(ctx)
    if not devices or one_chip is None:
        return None
    return one_chip / devices
