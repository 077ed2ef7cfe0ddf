"""The slowest ingest worker's median batch fill time (first record in ->
seal).  A running percentile of the report: covers warm-up too."""

NAME = "ingest.fill_p50_ms.lat"
UNIT = "ms"
LAYER = "ingest workers"
MOVES = "verdict_p50_ms"


def read(ctx):
    ing = ctx.snap1["rep"].get("ingest")
    if not ing:
        return None
    v = [w["fill_ms"].get("p50") for w in ing["workers"].values()]
    v = [x for x in v if x is not None]
    return max(v) if v else None
