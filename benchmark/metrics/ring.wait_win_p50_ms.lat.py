"""Median, over the records sunk in the window, of the first record's wait
in the feature ring before its batch starts to fill
(``spans["latency.ring"]``, the window's counts: later report less
earlier; an entry is charged from its oldest batch).  The ingest worker
dates a record by when its cursor log first saw the ring's producer
cursor pass it: a lower bound, short by at most one of its looks."""

from benchmark import span_window

NAME = "ring.wait_win_p50_ms.lat"
UNIT = "ms"
LAYER = "daemon and shm rings"
MOVES = "verdict_p50_ms"


def read(ctx):
    return span_window.p_ms(ctx, "latency.ring", 50)
