"""Share of the window's flows that ended their batch with no row
(``GlobalStats.untracked``, the window's, over the (batch, key) pairs of
the sealed batches the source tap saw in the window): what the table's
load costs, about ``load ** probes`` of the new flows.  A program
without the counter, or a driver that does not say which of the tap's
batches the window held, reads as nothing."""

NAME = "table.untracked_share.tput"
UNIT = "%"
LAYER = "flow table"
MOVES = "records_per_s"


def read(ctx):
    from benchmark import churn

    s0, s1 = ctx.snap0["rep"]["stats"], ctx.snap1["rep"]["stats"]
    b0 = ctx.snap0["gen"].get("tap_batches")
    b1 = ctx.snap1["gen"].get("tap_batches")
    if "untracked" not in s1 or b0 is None or b1 is None:
        return None
    flows, _ = churn.flows_between(ctx.reaps.tap.words, b0, b1)
    if flows <= 0:
        return None
    return 100.0 * (s1["untracked"] - s0["untracked"]) / flows
