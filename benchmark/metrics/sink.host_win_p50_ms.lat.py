"""Median, over the records sunk in the window, of wire on the host ->
verdict sunk (``spans["latency.sink_host"]``, window counts): the sink's
own work, without its wait on the device."""

from benchmark import span_window

NAME = "sink.host_win_p50_ms.lat"
UNIT = "ms"
LAYER = "sink"
MOVES = "verdict_p50_ms"


def read(ctx):
    return span_window.p_ms(ctx, "latency.sink_host", 50)
