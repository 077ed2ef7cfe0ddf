"""Median of wire fetch -> writeback applied (``latency.stages.sink``).  A
running histogram of the report: covers warm-up too."""

NAME = "sink.stage_p50_ms.lat"
UNIT = "ms"
LAYER = "sink"
MOVES = "verdict_p50_ms"


def read(ctx):
    lat = ctx.snap1["rep"].get("latency") or {}
    p = (lat.get("stages") or {}).get("sink", {}).get("p50")
    return p / 1e3 if p else None
