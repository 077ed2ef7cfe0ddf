"""Device self-time, in the traced slice, of the step programs' operations
under the scope ``fsx.update``, over the batches
dispatched in the slice (ms a batch).  The seven ``step.stage_*`` add up
to the step programs' busy time."""

from benchmark import trace_scopes

NAME = "step.stage_update_ms.tput"
UNIT = "ms"
LAYER = "kernels (the fused step; no Pallas kernel is on the window's path)"
MOVES = "records_per_s"


def read(ctx):
    return trace_scopes.stage_ms(ctx, "update")
