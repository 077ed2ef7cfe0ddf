"""The sink thread's busy share of its run (``readback.sink_occupancy``),
of the window's last ``Engine.run`` call."""

NAME = "sink.busy.tput"
UNIT = "%"
LAYER = "sink"
MOVES = "records_per_s"


def read(ctx):
    occ = (ctx.snap1["rep"].get("readback") or {}).get("sink_occupancy")
    return 100.0 * occ if occ else None
