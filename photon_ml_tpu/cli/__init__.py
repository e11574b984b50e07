"""Command-line drivers (reference: ml/Driver.scala, ml/cli/game/)."""


def device_summary() -> dict:
    """The device a driver ran on, as JAX reports it — written into every
    driver's metrics so no number is read without its device."""
    import jax

    devices = jax.devices()
    return {"platform": devices[0].platform,
            "kind": devices[0].device_kind,
            "count": len(devices)}
