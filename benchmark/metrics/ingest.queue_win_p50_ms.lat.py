"""Median, over the records sunk in the window, of seal -> engine dequeue
(``spans["latency.queue"]``, the window's counts: later report less
earlier; an entry is charged from its oldest batch)."""

from benchmark import span_window

NAME = "ingest.queue_win_p50_ms.lat"
UNIT = "ms"
LAYER = "ingest workers"
MOVES = "verdict_p50_ms"


def read(ctx):
    return span_window.p_ms(ctx, "latency.queue", 50)
