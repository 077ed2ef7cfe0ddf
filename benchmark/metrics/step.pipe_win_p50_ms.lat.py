"""Median, over the records sunk in the window, of step call returned ->
the group's wire is on the host (``spans["latency.device"]``, window
counts): the device's queue of launched groups, the step itself and the
fetch."""

from benchmark import span_window

NAME = "step.pipe_win_p50_ms.lat"
UNIT = "ms"
LAYER = "device step"
MOVES = "verdict_p50_ms"


def read(ctx):
    return span_window.p_ms(ctx, "latency.device", 50)
