"""The host plane of the raw profiler trace: when a named host span was
open, and the device's idle share while it was.

``trace_reduce`` reads the device's idle share over the traced slice,
first device operation to last, and a traced slice holds more than
serving: the start of its ``run()`` call, the call's final drain and its
report (``fsx.report``, the table summary included).  The program marks
its serving window with a span, ``fsx.serve``: one a ``run()`` call, from
its first poll until its loop stops launching.  This file reads that
span's intervals from the host plane (``/host:CPU``) of the
``.xplane.pb`` with ``trace_scopes``' wire reader (an ``XEvent`` lies at
its line's ``timestamp_ns`` plus its ``offset_ps``, on the clock of the
device planes), and the device planes' ``XLA Ops`` with
``trace_scopes.load``, and gives the device's idle share inside those
intervals, averaged over the device planes as ``trace_reduce`` does and
cut, as its slice is, to the first device operation and the last (the
call's start, before its first launch reaches the device, is the seam
too).  A trace without the span (a program that has none) reads as
``None``.
"""

from __future__ import annotations

from pathlib import Path

from benchmark import trace_scopes

HOST_PLANE = "/host:CPU"
SERVE_SPAN = "fsx.serve"

_cache: dict[str, float | None] = {}


def _plane_spans(buf: bytes, span, name: str) -> list[tuple[int, int]]:
    """``(start_ps, end_ps)`` of the events called ``name`` in one plane,
    if it is the host plane."""
    plane, lines, md_names = "", [], {}
    for f, v in trace_scopes._message(buf, *span, "XPlane"):
        if f == "name":
            plane = trace_scopes._text(buf, v)
            if plane != HOST_PLANE:
                return []
        elif f == "lines":
            lines.append(v)
        elif f == "event_metadata":
            key, md = trace_scopes._map(buf, v, "XEventMetadata")
            md_names[key] = (trace_scopes._text(buf, md["name"][0])
                             if "name" in md else "")
    if plane != HOST_PLANE:
        return []
    ids = {k for k, n in md_names.items() if n == name}
    out = []
    for ln in lines:
        t0_ps, events = 0, []
        for f, v in trace_scopes._message(buf, *ln, "XLine"):
            if f == "timestamp_ns":
                t0_ps = v * 1000
            elif f == "events":
                events.append(v)
        for ev in events:
            e = dict(trace_scopes._message(buf, *ev, "XEvent"))
            if e.get("metadata_id", 0) in ids:
                start = t0_ps + e.get("offset_ps", 0)
                out.append((start, start + e.get("duration_ps", 0)))
    return out


def host_spans(path: Path, name: str) -> list[tuple[int, int]]:
    """Every interval (ps) in which a host event ``name`` was open."""
    buf = Path(path).read_bytes()
    out = []
    for _, span in trace_scopes._message(buf, 0, len(buf), "XSpace"):
        out += _plane_spans(buf, span, name)
    return sorted(out)


def _union(intervals) -> list[list[int]]:
    merged: list[list[int]] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        elif e > s:
            merged.append([s, e])
    return merged


def _overlap(a: list[list[int]], b: list[list[int]]) -> int:
    """Length of the intersection of two sorted, disjoint interval sets."""
    total = i = j = 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        total += max(hi - lo, 0)
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


def idle_share(path: Path, name: str = SERVE_SPAN) -> float | None:
    """The device's idle share (%) while host span ``name`` was open,
    inside the device planes' first operation to their last: 1 - the
    planes' mean union of ``XLA Ops`` there, over its length.  ``None``
    without the span or without a device operation in it."""
    spans = host_spans(path, name)
    planes = trace_scopes.load(path) if spans else []
    busy = [_union((s, s + d) for _, s, d in p["ops"] if d > 0)
            for p in planes]
    if not any(busy):
        return None
    lo = min(b[0][0] for b in busy if b)
    hi = max(b[-1][1] for b in busy if b)
    window = _union((max(s, lo), min(e, hi)) for s, e in spans)
    length = sum(e - s for s, e in window)
    if length <= 0:
        return None
    covered = [_overlap(b, window) for b in busy]
    return 100.0 * (1.0 - sum(covered) / len(covered) / length)


def serving_idle(ctx) -> float | None:
    """:func:`idle_share` of ``fsx.serve`` in this run's trace, read
    once a process."""
    if not ctx.trace:
        return None
    path = trace_scopes.newest_trace()
    if path is None:
        return None
    key = str(path)
    if key not in _cache:
        _cache[key] = idle_share(path)
    return _cache[key]
