"""The device's idle share of the traced slice (``harness.device_idle``)."""

NAME = "device.idle.tput"
UNIT = "%"
LAYER = "device"
MOVES = "records_per_s"


def read(ctx):
    return ctx.harness.device_idle(ctx)
