"""How fast the engine served while the window's last periodic save was
in flight, as a share of the window's rate: the report's
``checkpoint.last`` gives the records emitted between the save's
boundary and its publish (``records_during``) and the save's length
(``seconds``) less its hold on the dispatch thread (``hold_s``);
``records_during`` over that time, over the window's records over the
window's wall.  100 % is serving unslowed by the save, 0 a save that
stops serving.  Nothing without a save in the window or without those
counters."""

from benchmark import harness

NAME = "ckpt.overlap_serve_share.tput"
UNIT = "%"
LAYER = "checkpoint"
MOVES = "records_per_s"


def read(ctx):
    n = harness.load_module("metrics", "ckpt.fetch_ms.tput").saves(ctx)
    last = (ctx.snap1["rep"].get("checkpoint") or {}).get("last") or {}
    if n <= 0 or "records_during" not in last or ctx.window_s <= 0:
        return None
    during_s = last["seconds"] - last["hold_s"]
    rate = (ctx.snap1["rep"]["records"]
            - ctx.snap0["rep"]["records"]) / ctx.window_s
    if during_s <= 0 or rate <= 0:
        return None
    return 100.0 * last["records_during"] / during_s / rate
