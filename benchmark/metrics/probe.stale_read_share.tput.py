"""Share of the window's batches whose probe read ``last_seen`` too: the
second ``[B, probes]`` table gather, taken only by a batch in which some
key found neither a match nor an empty slot among its probes
(``GlobalStats.stale_reads`` over ``batches``, the window's).  Near 0
while the table has room, 100 once it is half full.  A program without
the counter reads as nothing."""

NAME = "probe.stale_read_share.tput"
UNIT = "%"
LAYER = "kernels (the fused step; no Pallas kernel is on the window's path)"
MOVES = "records_per_s"


def read(ctx):
    s0, s1 = ctx.snap0["rep"]["stats"], ctx.snap1["rep"]["stats"]
    batches = s1["batches"] - s0["batches"]
    if "stale_reads" not in s1 or batches <= 0:
        return None
    return 100.0 * (s1["stale_reads"] - s0["stale_reads"]) / batches
