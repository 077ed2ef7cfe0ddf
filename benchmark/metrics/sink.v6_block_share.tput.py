"""Share of the blocks of the window whose source lies outside
``::ffff:0:0/96`` (``GlobalStats.v6_blocks``, the window's, over the
blocks the driver's sink tap saw in the window: ``sink_blocks`` in its
counters).  A program without the counter, or a driver that does not
count its sink's blocks, reads as nothing."""

NAME = "sink.v6_block_share.tput"
UNIT = "%"
LAYER = "sink"
MOVES = "records_per_s"


def read(ctx):
    s0, s1 = ctx.snap0["rep"]["stats"], ctx.snap1["rep"]["stats"]
    b0 = ctx.snap0["gen"].get("sink_blocks")
    b1 = ctx.snap1["gen"].get("sink_blocks")
    if "v6_blocks" not in s1 or b0 is None or b1 is None or b1 <= b0:
        return None
    return 100.0 * (s1["v6_blocks"] - s0["v6_blocks"]) / (b1 - b0)
