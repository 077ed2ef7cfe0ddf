"""The device's idle share of the traced slice while the program served:
inside its ``fsx.serve`` span on the host plane (``trace_host``)."""

from benchmark import trace_host

NAME = "device.idle_serving.lat"
UNIT = "%"
LAYER = "device"
MOVES = "verdict_p50_ms"


def read(ctx):
    return trace_host.serving_idle(ctx)
