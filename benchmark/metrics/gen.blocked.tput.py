"""Share of the window in which the closed loop held the generator: the
time ``fsxd --sim`` (free-running) stood blocked on ring backpressure
(``benchmark/governor.py``: held while a ring held ``high_water``
records) over the window's wall clock.  High means the engine binds;
toward 0 the cell has begun to read the generator, and what ``fsxd``
produced over the time it was not held is the generator's ceiling.  A
paced (open-loop) cell has no governor and reads nothing."""

NAME = "gen.blocked.tput"
UNIT = "%"
LAYER = "load generator"
MOVES = "records_per_s"


def read(ctx):
    g0, g1 = ctx.snap0["gen"], ctx.snap1["gen"]
    if "blocked_ns" not in g0 or "blocked_ns" not in g1:
        return None
    alive = g1["governed_ns"] - g0["governed_ns"]
    if alive <= 0:
        return None
    return 100.0 * (g1["blocked_ns"] - g0["blocked_ns"]) / alive
