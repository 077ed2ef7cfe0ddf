"""Percentile 95 over every record of the window of first record in
its batch -> verdict sunk (``harness.window_percentile``)."""

NAME = "verdict_p95_ms"
UNIT = "ms"
LAYER = "end to end"
MOVES = ""


def read(ctx):
    return ctx.harness.window_percentile(ctx, 95.0)
