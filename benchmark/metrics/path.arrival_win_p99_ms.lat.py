"""99th percentile, over the records sunk in the window, of publish in
the feature ring -> verdict sunk (``spans["latency.arrival_to_verdict"]``,
the window's counts: later report less earlier)."""

from benchmark import span_window

NAME = "path.arrival_win_p99_ms.lat"
UNIT = "ms"
LAYER = "whole path (first record in its batch to verdict sunk)"
MOVES = "verdict_p95_ms"


def read(ctx):
    return span_window.p_ms(ctx, "latency.arrival_to_verdict", 99)
