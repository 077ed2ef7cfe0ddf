"""The least time HBM could take for the aging sweep of the traced
slice's batches over the time the device spent under ``fsx.evict``.
The bytes are the algorithm's (``churn.sweep_bytes``): each batch reads
its window of ``ceil(capacity / evict_every)`` rows and writes the rows
it frees (the slice's ``evicted``), a row at the schema's 52 B.  A
program with no such scope, or a kind with no peaks, reads as nothing."""

NAME = "evict.hbm_roofline.tput"
UNIT = "%"
LAYER = "kernels (the fused step; no Pallas kernel is on the window's path)"
MOVES = "records_per_s"


def read(ctx):
    from benchmark import churn, trace_scopes

    r = trace_scopes.stages(ctx)
    if not r or ctx.peaks is None or r["stage_s"].get("evict", 0.0) <= 0:
        return None
    rep0, rep1 = ctx.trace["snap0"]["rep"], ctx.trace["snap1"]["rep"]
    if "evicted" not in rep1["stats"]:
        return None
    need = churn.sweep_bytes(
        ctx.config["table"], rep1["batches"] - rep0["batches"],
        rep1["stats"]["evicted"] - rep0["stats"]["evicted"])
    least_s = need / ctx.peaks["hbm_bytes_per_s"]
    return 100.0 * least_s / r["stage_s"]["evict"]
