"""The whole step's share of the chip's int8 peak: the classifier's
operations for the traced slice's records over the peak, over the step
programs' device time.  Tiny by nature: the step is table traffic."""

NAME = "step.mfu.tput"
UNIT = "%"
LAYER = "device step"
MOVES = "records_per_s"


def read(ctx):
    from benchmark import peaks

    t = ctx.trace
    if not t or t["step_s"] <= 0 or ctx.peaks is None:
        return None
    n = ctx.harness.traced_records(ctx)
    if n <= 0:
        return None
    return 100.0 * peaks.step_ops(n, ctx.config["model"]["name"]) / ctx.peaks["int8_ops"] / t["step_s"]
