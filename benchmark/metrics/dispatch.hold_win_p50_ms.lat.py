"""Median, over the records sunk in the window, of engine dequeue -> the
launch section takes the entry (``spans["latency.hold"]``, window counts):
arena residency, the ladder's wait for a rung and the wait for room in
the pipe."""

from benchmark import span_window

NAME = "dispatch.hold_win_p50_ms.lat"
UNIT = "ms"
LAYER = "dispatch thread"
MOVES = "verdict_p50_ms"


def read(ctx):
    return span_window.p_ms(ctx, "latency.hold", 50)
