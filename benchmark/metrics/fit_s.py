"""``fit_s``: seconds per fit, the whole window over all the jobs it
completed (host clock; a job is one ``CoordinateDescent.run`` from zero,
ended by ``block_until_ready`` on its parameters)."""


def read(ctx):
    w = ctx["window"]
    return w["seconds"] / w["attempted"]
