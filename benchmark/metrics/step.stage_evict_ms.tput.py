"""Device self-time, in the traced slice, of the step programs' operations
under the scope ``fsx.evict`` (the aging sweep: compiled in only where
the configuration's ``table.evict_ttl_s`` is above 0), over the batches
dispatched in the slice (ms a batch).  With the seven other
``step.stage_*`` it adds up to the step programs' busy time.  A program
whose trace holds no such scope reads as nothing."""

from benchmark import trace_scopes

NAME = "step.stage_evict_ms.tput"
UNIT = "ms"
LAYER = "kernels (the fused step; no Pallas kernel is on the window's path)"
MOVES = "records_per_s"


def read(ctx):
    r = trace_scopes.stages(ctx)
    if not r or "evict" not in r["stage_s"]:
        return None
    return trace_scopes.stage_ms(ctx, "evict")
