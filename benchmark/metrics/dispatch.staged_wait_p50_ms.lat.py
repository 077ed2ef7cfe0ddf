"""Median of seal -> launch (``latency.stages.staged_wait``).  A running
histogram of the report: covers warm-up too."""

NAME = "dispatch.staged_wait_p50_ms.lat"
UNIT = "ms"
LAYER = "dispatch thread"
MOVES = "verdict_p50_ms"


def read(ctx):
    lat = ctx.snap1["rep"].get("latency") or {}
    p = (lat.get("stages") or {}).get("staged_wait", {}).get("p50")
    return p / 1e3 if p else None
