"""Occupied rows over the table's capacity at the window's end (the
report's ``table`` summary, ``tracked``): the load factor the step's
probe and insert worked against."""

NAME = "table.load.tput"
UNIT = "%"
LAYER = "flow table"
MOVES = "records_per_s"


def read(ctx):
    table = ctx.snap1["rep"].get("table") or {}
    if "tracked" not in table:
        return None
    return 100.0 * table["tracked"] / ctx.config["table"]["capacity"]
