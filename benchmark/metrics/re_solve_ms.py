"""``re_solve_ms``: one random-effect sweep from zero over every bucket
(``RandomEffectCoordinate.update_model``), run alone after the traced
jobs: the device-busy time inside its ``bench.probe.re_solve`` span, from
the profiler's trace, mean of the repeats."""


def read(ctx):
    busy = ((ctx.get("trace") or {}).get("probe_busy_s") or {}).get(
        "re_solve")
    if not busy:
        return None
    return 1e3 * sum(busy) / len(busy)
