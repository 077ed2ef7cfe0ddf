"""Share of the window's sunk batches whose blocks overflowed the
compact verdict wire (``verdict_k`` flows a batch), so that the sink
fetched the full block arrays of their entry as well (8 B a record): the
report's ``readback.fallback_sinks`` over ``fallback_sinks +
compact_sinks``, both as window differences and both counted in
batches."""

NAME = "sink.fallback_share.tput"
UNIT = "%"
LAYER = "sink"
MOVES = "records_per_s"


def read(ctx):
    r0 = ctx.snap0["rep"].get("readback") or {}
    r1 = ctx.snap1["rep"].get("readback") or {}
    if "fallback_sinks" not in r1 or "compact_sinks" not in r1:
        return None
    fallback = r1["fallback_sinks"] - r0.get("fallback_sinks", 0)
    sunk = fallback + r1["compact_sinks"] - r0.get("compact_sinks", 0)
    return 100.0 * fallback / sunk if sunk > 0 else None
