"""Device time of the step programs in the traced slice over the batches
dispatched in it."""

NAME = "step.device_ms_per_batch.tput"
UNIT = "ms"
LAYER = "device step"
MOVES = "records_per_s"


def read(ctx):
    t = ctx.trace
    if not t or t["step_s"] <= 0:
        return None
    d_bat = t["snap1"]["rep"]["batches"] - t["snap0"]["rep"]["batches"]
    return 1e3 * t["step_s"] / d_bat if d_bat > 0 else None
