"""Share of the window's wall the dispatch thread spent working: its
``fsx.dispatch.poll``, ``.upload`` and ``.launch`` spans (window sums of
``sum_us``) over the window.  The rest it waited: backpressure, idle."""

from benchmark import span_window

NAME = "dispatch.host_busy.tput"
UNIT = "%"
LAYER = "dispatch thread"
MOVES = "records_per_s"


def read(ctx):
    return span_window.busy_share(
        ctx, ("fsx.dispatch.poll", "fsx.dispatch.upload",
              "fsx.dispatch.launch"))
