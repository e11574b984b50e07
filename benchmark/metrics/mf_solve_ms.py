"""``mf_solve_ms``: the factored coordinate's whole update from zero
(``FactoredRandomEffectCoordinate.update_model``: the flattening and every
alternation's projections, latent solves and refit), run alone after
the traced jobs: the device-busy time inside its ``bench.probe.mf_solve``
span, from the profiler's trace, mean of the repeats. Nothing where the job
has no such probe."""


def probe_ms(ctx, layer: str):
    """Mean device-busy ms inside the ``bench.probe.<layer>`` spans of a
    traced run; None where there is no such span."""
    busy = ((ctx.get("trace") or {}).get("probe_busy_s") or {}).get(layer)
    if not busy:
        return None
    return 1e3 * sum(busy) / len(busy)


def read(ctx):
    return probe_ms(ctx, "mf_solve")
