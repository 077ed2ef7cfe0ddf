"""Share of the records served in the window whose source lies outside
``::ffff:0:0/96`` (``GlobalStats.v6_records`` over the report's
records, both the window's): the mix that reached the engine after the
daemon's suppression.  A program without the counter reads as
nothing."""

NAME = "key.v6_share.tput"
UNIT = "%"
LAYER = "flow table"
MOVES = "records_per_s"


def read(ctx):
    r0, r1 = ctx.snap0["rep"], ctx.snap1["rep"]
    if "v6_records" not in r1["stats"]:
        return None
    served = r1["records"] - r0["records"]
    if served <= 0:
        return None
    return 100.0 * (r1["stats"]["v6_records"]
                    - r0["stats"]["v6_records"]) / served
