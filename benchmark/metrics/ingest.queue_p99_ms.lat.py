"""The worst ingest worker's p99 of seal -> engine dequeue.  A running
percentile of the report: covers warm-up too."""

NAME = "ingest.queue_p99_ms.lat"
UNIT = "ms"
LAYER = "ingest workers"
MOVES = "verdict_p95_ms"


def read(ctx):
    ing = ctx.snap1["rep"].get("ingest")
    if not ing:
        return None
    v = [w["queue_ms"].get("p99") for w in ing["workers"].values()]
    v = [x for x in v if x is not None]
    return max(v) if v else None
