"""``fe_solve_ms``: one fixed-effect solve from zero
(``FixedEffectCoordinate.update_model``), run alone after the traced jobs:
the device-busy time inside its ``bench.probe.fe_solve`` span, from the
profiler's trace, mean of the repeats (one warm call before the trace)."""


def read(ctx):
    busy = ((ctx.get("trace") or {}).get("probe_busy_s") or {}).get(
        "fe_solve")
    if not busy:
        return None
    return 1e3 * sum(busy) / len(busy)
