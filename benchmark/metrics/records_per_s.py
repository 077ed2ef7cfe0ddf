"""Records whose verdicts were sunk in the window (the benchmark's own
count, from the engine's ``on_reap`` hook) over the window's wall clock."""

NAME = "records_per_s"
UNIT = "records/s"
LAYER = "end to end"
MOVES = ""


def read(ctx):
    n = ctx.snap1["sunk"] - ctx.snap0["sunk"]
    return n / ctx.window_s if n > 0 else None
