"""The least time HBM could take for the traced slice's records (bytes
bound: ``peaks.step_bytes`` over the peak bytes/s) over the step
programs' device time."""

NAME = "step.hbm_roofline.tput"
UNIT = "%"
LAYER = "kernels (the fused step; no Pallas kernel is on the window's path)"
MOVES = "records_per_s"


def read(ctx):
    from benchmark import peaks

    t = ctx.trace
    if not t or t["step_s"] <= 0 or ctx.peaks is None:
        return None
    n = ctx.harness.traced_records(ctx)
    if n <= 0:
        return None
    least_s = peaks.step_bytes(n) / ctx.peaks["hbm_bytes_per_s"]
    return 100.0 * least_s / t["step_s"]
