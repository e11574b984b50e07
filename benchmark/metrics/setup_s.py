"""``setup_s``: process start to the first timed job: imports, compile or
cache load, data made from the seed, one warm-up job."""


def read(ctx):
    return ctx["setup_s"]
