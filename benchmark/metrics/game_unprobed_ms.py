"""``game_unprobed_ms``: what a job of several entity coordinates spends
outside its coordinates' solves AS THE PROBES TIME THEM, per job: the traced
jobs' device-busy time less the probe means of every coordinate's
``update_model`` alone from zero (``fe_solve``, and the layers the workload
file maps its coordinates to). A remainder, named for what it is.

A probe from zero is given no residual and scores nothing, so the
remainder holds the score exchange of every entity coordinate (the
residual gathered into the group's slots, the margins, the way back by
row), the fixed effect's scoring pass, the objectives, what runs under no
scope, and whatever a solve costs more inside the job than alone (a
probe's solves stop at their own counts). On ``game-mf.fit`` the exchange
is nine tenths of it (``dev_scripts/trace_scopes.py`` gives the exchange
exactly: ``PERF.md`` section 5). It stands in until the reduction keeps
the scope path (``PERF.md`` section 7, wiring (3)), when ``photon.re.gather
+ .margins + .scatter`` can be read in place. ``None`` where there is no
trace, a probe is missing, or the job kind maps no coordinate to a probe."""


def read(ctx):
    trace = ctx.get("trace") or {}
    jobs = trace.get("traced_jobs")
    mapped = sorted(ctx["workload"].get("probes", {}).values())
    if not jobs or not mapped:
        return None
    # (``mf_refit`` and ``mf_latent`` are parts of ``mf_solve``: not summed)
    alone = 0.0
    for layer in ["fe_solve"] + mapped:
        busy = (trace.get("probe_busy_s") or {}).get(layer)
        if not busy:
            return None
        alone += sum(busy) / len(busy)
    rest = trace["busy_s"] / jobs - alone
    return 1e3 * rest if rest > 0 else None
