"""Device time of the step programs by ``jax.named_scope``, from the raw
profiler trace.

``jax.profiler.ProfileData`` gives an ``XLA Ops`` event its name and its
own three stats, not the stats of its *metadata* — and on this runtime
(TPU v5 lite, jax 0.9; looked at by hand, PR 29) that is where the scope
is: every event's ``XEventMetadata`` carries a ``tf_op`` stat holding the
operation's name path, ``jit(mega)/while/body/closed_call/fsx.update/
gather/gather:``, with each ``jax.named_scope`` as a component.  So this
file reads the ``.xplane.pb`` itself, with a protobuf wire reader for
the seven messages of ``tsl/profiler/protobuf/xplane.proto`` it needs
(field numbers in :data:`FIELDS`): no import beyond the standard
library, and an event's own stats are skipped, not decoded.

An operation's stage is the first ``fsx.<stage>`` component of its
``tf_op`` (``unscoped`` when there is none).  Time is self-time: an
event's duration less the events nested in it on the same line (a
``while`` holds its body's operations), over the operations that lie in
an ``XLA Modules`` event of a step program.  ``check_trace_scopes.py``
checks all of it on ``fixtures/scopes_small.xplane.pb``.

``ctx.trace`` does not carry the trace's directory: ``run.py`` writes it
to ``<tempdir>/fsxbench-*/trace`` and removes it at exit, so
:func:`newest_trace` takes the newest such file.
"""

from __future__ import annotations

import bisect
import re
import tempfile
from pathlib import Path

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
SCOPE = re.compile(r"(?:^|/)fsx\.([A-Za-z0-9_]+)(?:/|:|$)")
UNSCOPED = "unscoped"

#: message -> {field number: name} of what is read (xplane.proto)
FIELDS = {
    "XSpace": {1: "planes"},
    "XPlane": {2: "name", 3: "lines", 4: "event_metadata",
               5: "stat_metadata"},
    "XLine": {2: "name", 3: "timestamp_ns", 4: "events"},
    "XEvent": {1: "metadata_id", 2: "offset_ps", 3: "duration_ps"},
    "XEventMetadata": {1: "id", 2: "name", 5: "stats"},
    "XStat": {1: "metadata_id", 5: "str_value", 7: "ref_value"},
    "XStatMetadata": {1: "id", 2: "name"},
    "MapEntry": {1: "key", 2: "value"},
}

_cache: dict[str, dict] = {}


def _varint(buf: bytes, i: int) -> tuple[int, int]:
    value = shift = 0
    while True:
        b = buf[i]
        i += 1
        value |= (b & 0x7F) << shift
        if b < 0x80:
            return value, i
        shift += 7


def _message(buf: bytes, start: int, end: int, kind: str):
    """``(field name, value)`` of the wanted fields of one message:
    an int for a varint, a ``(start, end)`` span for a length-delimited
    field (string, bytes or sub-message); the rest is skipped."""
    want = FIELDS[kind]
    i = start
    while i < end:
        key, i = _varint(buf, i)
        field, wire = key >> 3, key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            n, i = _varint(buf, i)
            value, i = (i, i + n), i + n
        elif wire == 1:
            value, i = None, i + 8
        elif wire == 5:
            value, i = None, i + 4
        else:
            raise ValueError(f"xplane: wire type {wire} in {kind}")
        name = want.get(field)
        if name is not None and value is not None:
            yield name, value


def _text(buf: bytes, span: tuple[int, int]) -> str:
    return buf[span[0]:span[1]].decode("utf-8", "replace")


def _map(buf: bytes, span, kind: str) -> tuple[int, dict]:
    """One ``map<int64, kind>`` entry as ``(key, {field: value})``."""
    key, fields = 0, {}
    for name, value in _message(buf, *span, "MapEntry"):
        if name == "key":
            key = value
        else:
            for f, v in _message(buf, *value, kind):
                fields.setdefault(f, []).append(v)
    return key, fields


def stage_of(tf_op: str) -> str:
    m = SCOPE.search(tf_op or "")
    return m.group(1) if m else UNSCOPED


def _plane(buf: bytes, span) -> dict | None:
    """A device plane as ``{"name", "modules": [(name, start_ps,
    duration_ps)], "ops": [(stage, start_ps, duration_ps)]}``."""
    name, lines, stat_names, event_md = "", [], {}, {}
    for f, v in _message(buf, *span, "XPlane"):
        if f == "name":
            name = _text(buf, v)
            if not DEVICE_PLANE.match(name):
                return None
        elif f == "lines":
            lines.append(v)
        elif f == "stat_metadata":
            key, md = _map(buf, v, "XStatMetadata")
            stat_names[key] = _text(buf, md["name"][0]) if "name" in md \
                else ""
        elif f == "event_metadata":
            key, md = _map(buf, v, "XEventMetadata")
            event_md[key] = md
    if not DEVICE_PLANE.match(name):
        return None
    tf_op_ids = {k for k, n in stat_names.items() if n == "tf_op"}
    md_name, md_stage = {}, {}
    for key, md in event_md.items():
        md_name[key] = _text(buf, md["name"][0]) if "name" in md else ""
        tf_op = ""
        for stat in md.get("stats", ()):
            st = dict(_message(buf, *stat, "XStat"))
            if st.get("metadata_id") in tf_op_ids:
                tf_op = (_text(buf, st["str_value"]) if "str_value" in st
                         else stat_names.get(st.get("ref_value"), ""))
        md_stage[key] = stage_of(tf_op)
    out = {"name": name, "modules": [], "ops": []}
    for ln in lines:
        line_name, t0_ps, events = "", 0, []
        for f, v in _message(buf, *ln, "XLine"):
            if f == "name":
                line_name = _text(buf, v)
            elif f == "timestamp_ns":
                t0_ps = v * 1000
            else:
                events.append(v)
        if line_name not in (OPS_LINE, MODULES_LINE):
            continue
        for ev in events:
            e = dict(_message(buf, *ev, "XEvent"))
            start = t0_ps + e.get("offset_ps", 0)
            dur = e.get("duration_ps", 0)
            mid = e.get("metadata_id", 0)
            if line_name == OPS_LINE:
                out["ops"].append((md_stage.get(mid, UNSCOPED), start, dur))
            else:
                out["modules"].append((md_name.get(mid, ""), start, dur))
    return out


def load(path: Path) -> list[dict]:
    """The device planes of one ``.xplane.pb``."""
    buf = Path(path).read_bytes()
    planes = []
    for _, span in _message(buf, 0, len(buf), "XSpace"):
        p = _plane(buf, span)
        if p is not None:
            planes.append(p)
    return planes


def self_time_by_stage(ops: list, inside: list[tuple[int, int]]) -> dict:
    """Self-time (ps) by stage of the operations that start inside one
    of the ``inside`` intervals (sorted, disjoint)."""
    starts = [s for s, _ in inside]
    out: dict[str, float] = {}
    stack: list[list] = []  # [end, stage, self_ps]

    def close(upto: float) -> None:
        while stack and stack[-1][0] <= upto:
            _, stage, own = stack.pop()
            out[stage] = out.get(stage, 0.0) + max(own, 0)

    for stage, s, d in sorted(ops, key=lambda e: (e[1], -e[2])):
        k = bisect.bisect_right(starts, s) - 1
        if d <= 0 or k < 0 or s >= inside[k][1]:
            continue
        close(s)
        if stack:
            stack[-1][2] -= d
        stack.append([s + d, stage, d])
    close(float("inf"))
    return out


def reduce_planes(planes: list[dict], step_programs) -> dict:
    """``{"stage_s": {stage: seconds}, "scoped": bool}`` averaged over
    the device planes; ``scoped`` says whether any operation of a step
    program carried an ``fsx.*`` scope at all."""
    step_programs = tuple(step_programs)
    total: dict[str, float] = {}
    for p in planes:
        inside = sorted((s, s + d) for name, s, d in p["modules"]
                        if name.startswith(step_programs) and d > 0)
        for stage, ps in self_time_by_stage(p["ops"], inside).items():
            total[stage] = total.get(stage, 0.0) + ps
    n = max(len(planes), 1)
    return {"stage_s": {k: v / n / 1e12 for k, v in total.items()},
            "scoped": any(k != UNSCOPED for k in total)}


def newest_trace() -> Path | None:
    files = list(Path(tempfile.gettempdir()).glob(
        "fsxbench-*/trace/plugins/profile/*/*.xplane.pb"))
    return max(files, key=lambda f: f.stat().st_mtime) if files else None


def stages(ctx) -> dict | None:
    """The traced slice's reduction, read once a process."""
    if not ctx.trace:
        return None
    path = newest_trace()
    if path is None:
        return None
    key = str(path)
    if key not in _cache:
        _cache[key] = reduce_planes(load(path),
                                    ctx.config["step_programs"])
    return _cache[key]


def stage_ms(ctx, stage: str) -> float | None:
    """Device self-time of one stage over the batches dispatched in the
    traced slice (ms a batch).  ``None`` where the trace holds no
    ``fsx.*`` scope at all: a program without them."""
    r = stages(ctx)
    if not r or not r["scoped"]:
        return None
    t = ctx.trace
    d_bat = t["snap1"]["rep"]["batches"] - t["snap0"]["rep"]["batches"]
    if d_bat <= 0:
        return None
    return 1e3 * r["stage_s"].get(stage, 0.0) / d_bat
