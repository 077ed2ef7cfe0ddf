"""Share of the window's wall that periodic saves held the dispatch
thread: the window's ``fsx.ckpt.hold`` sum (the boundary's ``_reap(0)``,
the table's device-side snapshot and the stats' fetch, until the save
is handed to its own thread) over the window.  Serving stops only for
this share.  A program without the span reads as nothing."""

from benchmark import span_window

NAME = "ckpt.hold_share.tput"
UNIT = "%"
LAYER = "checkpoint"
MOVES = "records_per_s"


def read(ctx):
    return span_window.busy_share(ctx, ("fsx.ckpt.hold",))
