"""Share of the window's wall the sink spent on its own work: its
``fsx.sink.decode`` and ``.apply`` spans (window sums of ``sum_us``) over
the window — without ``fsx.sink.fetch``, its wait on the device, which
``sink.busy.tput`` counts as busy."""

from benchmark import span_window

NAME = "sink.host_busy.tput"
UNIT = "%"
LAYER = "sink"
MOVES = "records_per_s"


def read(ctx):
    return span_window.busy_share(ctx, ("fsx.sink.decode", "fsx.sink.apply"))
