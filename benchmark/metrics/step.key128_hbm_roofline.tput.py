"""The least time HBM could take for the traced slice's records at
128-bit keys (bytes bound: ``key128.step_bytes``, the 7-word row and a
16 B key a table row, over the peak bytes/s) over the step programs'
device time: ``step.hbm_roofline.tput`` at the widths of a table keyed
by the full source."""

NAME = "step.key128_hbm_roofline.tput"
UNIT = "%"
LAYER = "kernels (the fused step; no Pallas kernel is on the window's path)"
MOVES = "records_per_s"


def read(ctx):
    from benchmark import key128

    t = ctx.trace
    if not t or t["step_s"] <= 0 or ctx.peaks is None:
        return None
    if ctx.config["table"].get("key_bits", 32) != 128:
        return None
    n = ctx.harness.traced_records(ctx)
    if n <= 0:
        return None
    least_s = key128.step_bytes(n) / ctx.peaks["hbm_bytes_per_s"]
    return 100.0 * least_s / t["step_s"]
