"""How full the dispatched batches were in the window
(``harness.occupancy``)."""

NAME = "dispatch.occupancy.lat"
UNIT = "%"
LAYER = "dispatch thread"
MOVES = "verdict_p50_ms"


def read(ctx):
    return ctx.harness.occupancy(ctx)
