"""``device_idle``: 1 - the union of device-op intervals over the traced
steady window (a few jobs back to back), in percent."""


def read(ctx):
    tr = ctx.get("trace")
    if not tr:
        return None
    return 100.0 * tr["idle_share"]
