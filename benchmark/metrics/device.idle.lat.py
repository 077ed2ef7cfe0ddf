"""The device's idle share of the traced slice (``harness.device_idle``)."""

NAME = "device.idle.lat"
UNIT = "%"
LAYER = "device"
MOVES = "verdict_p50_ms"


def read(ctx):
    return ctx.harness.device_idle(ctx)
