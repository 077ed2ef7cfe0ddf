"""How full the dispatched batches were in the window
(``harness.occupancy``)."""

NAME = "dispatch.occupancy.tput"
UNIT = "%"
LAYER = "dispatch thread"
MOVES = "records_per_s"


def read(ctx):
    return ctx.harness.occupancy(ctx)
