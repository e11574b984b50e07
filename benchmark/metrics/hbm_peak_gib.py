"""``hbm_peak_gib``: ``memory_stats()["peak_bytes_in_use"]`` of the
fullest chip, read when the window has closed, before the layer spans and
the reference run. Per-layer on purpose: to be seen, not to decide a PR."""


def read(ctx):
    peak = ctx["device"].get("memory_peak_bytes")
    return None if peak is None else peak / 2 ** 30
