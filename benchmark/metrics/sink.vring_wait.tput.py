"""Share of the window's wall the sink spent waiting for room in the
verdict ring: the span ``fsx.sink.vring_wait`` (entered only when a push
into the ring came back short; window sum of ``sum_us``) over the
window.  It lies inside ``fsx.sink.apply``, so ``sink.host_busy.tput``
holds it too.  Read from the ring's writer through the driver's counters
(``drivers/sim_paced_vring.py``: the harness's sink tap keeps it out of
the engine's report).  A program without the span reads as nothing."""

from benchmark import span_window

NAME = "sink.vring_wait.tput"
UNIT = "%"
LAYER = "sink"
MOVES = "records_per_s"
SPAN = "fsx.sink.vring_wait"


def read(ctx):
    later = (ctx.snap1["gen"].get("vring") or {}).get("spans") or {}
    if SPAN not in later or ctx.window_s <= 0:
        return None
    earlier = (ctx.snap0["gen"].get("vring") or {}).get("spans") or {}
    window = span_window.subtract(earlier.get(SPAN), later[SPAN])
    return 100.0 * window["sum_us"] / (ctx.window_s * 1e6)
