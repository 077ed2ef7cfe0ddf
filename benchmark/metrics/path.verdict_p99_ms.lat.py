"""Percentile 99 over every record of the window of first record in
its batch -> verdict sunk (``harness.window_percentile``).  Not an end-to-end metric: one host
stall of a second in a 20 s window moves it by a factor of two or three
(PERF.md section 2), which no bound up to 10 % can hold."""

NAME = "path.verdict_p99_ms.lat"
UNIT = "ms"
LAYER = "whole path (first record in its batch to verdict sunk)"
MOVES = "verdict_p95_ms"


def read(ctx):
    return ctx.harness.window_percentile(ctx, 99.0)
