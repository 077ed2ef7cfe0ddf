"""The fullest the verdict ring was after a push of the sink's, since
boot, as a share of its slots: the ring writer's
``verdict_ring_fill_peak`` at the window's end, through the driver's
counters (``drivers/sim_paced_vring.py``).  A program without the
counter reads as nothing."""

NAME = "sink.vring_fill_peak.tput"
UNIT = "%"
LAYER = "sink"
MOVES = "records_per_s"


def read(ctx):
    peak = (ctx.snap1["gen"].get("vring") or {}).get(
        "verdict_ring_fill_peak")
    return None if peak is None else 100.0 * peak
