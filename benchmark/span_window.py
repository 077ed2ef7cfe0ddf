"""A window of the engine's span store: two reports, subtracted.

``EngineReport.spans`` is ``{name: {"n", "sum_us", "max_us", "hist"}}``,
cumulative since boot, where ``hist`` is ``{"scheme": "log2x16us",
"buckets": {index: count}, ...}``.  The scheme, as the program's
documentation defines it (docs/ENGINE.md §Observability; nothing of the
program is imported here): a duration is rounded UP to whole
microseconds ``u >= 1``; with ``e = floor(log2 u)`` its bucket is
``16 e + floor(16 (u - 2^e) / 2^e)`` — sixteen equal sub-buckets an
octave, 27 octaves, the last bucket (431) holding everything beyond.
A percentile is the UPPER edge of the bucket it falls in, so it is never
under the true value and at most 1/16 over.

A window's count, sum and buckets are the later report's less the
earlier's (``max_us`` is all-time and does not subtract).  The readers
under ``metrics/`` take ``snap0`` and ``snap1`` of the benchmark's
window; a program without the block (or without the name) reads as
``None`` and the metric is left out of the line.
"""

from __future__ import annotations

SCHEME = "log2x16us"
SUB = 16
BUCKETS = 27 * SUB


def upper_edge_us(index: int) -> float:
    """Upper edge of bucket ``index``: the lower edge of the next."""
    e, sub = divmod(index + 1, SUB)
    return float((1 << e) * (1.0 + sub / SUB))


def _buckets(entry: dict | None) -> dict[int, int]:
    if not entry:
        return {}
    hist = entry["hist"]
    if hist.get("scheme") != SCHEME:
        raise ValueError(f"span histogram scheme {hist.get('scheme')!r}, "
                         f"this reader knows {SCHEME!r}")
    return {int(i): int(c) for i, c in hist["buckets"].items()}


def subtract(earlier: dict | None, later: dict | None) -> dict | None:
    """One name's window: ``later`` less ``earlier`` (``None``: nothing
    before).  ``None`` when ``later`` is missing."""
    if later is None:
        return None
    b0, b1 = _buckets(earlier), _buckets(later)
    buckets = {i: c - b0.get(i, 0) for i, c in b1.items()
               if c - b0.get(i, 0)}
    e = earlier or {"n": 0, "sum_us": 0.0}
    return {"n": later["n"] - e["n"],
            "sum_us": later["sum_us"] - e["sum_us"], "buckets": buckets}


def percentile_us(window: dict, q: float) -> float | None:
    """Percentile ``q`` of a window, as its bucket's upper edge (µs)."""
    n = sum(window["buckets"].values())
    if n <= 0:
        return None
    rank = max(-(-n * q // 100), 1)  # ceil, like the program's own walk
    seen = 0
    for index in sorted(window["buckets"]):
        seen += window["buckets"][index]
        if seen >= rank:
            return upper_edge_us(index)
    return upper_edge_us(BUCKETS - 1)


def of(ctx, name: str) -> dict | None:
    """The benchmark window (``snap0`` -> ``snap1``) of one name."""
    s0 = ctx.snap0["rep"].get("spans") or {}
    s1 = ctx.snap1["rep"].get("spans")
    if not s1 or name not in s1:
        return None
    return subtract(s0.get(name), s1[name])


def p_ms(ctx, name: str, q: float) -> float | None:
    """Window percentile of one name, in ms."""
    w = of(ctx, name)
    p = percentile_us(w, q) if w else None
    return p / 1e3 if p is not None else None


def busy_share(ctx, names) -> float | None:
    """Share (%) of the window's wall that the named spans were open:
    the sum of their window ``sum_us`` over ``window_s``."""
    total = 0.0
    for name in names:
        w = of(ctx, name)
        if w is None:
            return None
        total += w["sum_us"]
    return 100.0 * total / (ctx.window_s * 1e6) if ctx.window_s > 0 else None
