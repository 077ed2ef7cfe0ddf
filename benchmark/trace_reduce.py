"""From a profiler trace to the few numbers the metrics read.

Input is the plain form ``{"planes": [{"name", "lines": [{"name",
"events": [[name, start_ns, duration_ns], ...]}]}]}`` — what
:func:`load_xplane` makes of a ``.xplane.pb`` through
``jax.profiler.ProfileData``, and what ``fixtures/trace_small.json``
holds, so that ``check_trace_reduce.py`` can check the arithmetic on a
trace whose answers are known.

* device planes are those named ``/device:TPU:<n>``; a device's busy time
  is the union of its operation intervals (line ``XLA Ops``; every line
  of the plane where that line is missing), averaged over the devices;
* the traced window is first operation start -> last operation end over
  all device planes, unless the caller knows better;
* a step program is an event of the line ``XLA Modules`` whose name
  starts with one of the configuration's ``step_programs`` (for the
  fused step: the jitted single step and the mega rungs, functions named
  ``step`` and ``mega`` in ``ops/fused.py``, so XLA names the modules
  ``jit_step`` / ``jit_mega``);
* the breakdown lists the ten operations with most device time and the
  ten longest idle gaps, each gap named by the host event (host plane,
  any thread) that covers most of it — the program has no spans of its
  own yet, so that name is as much as can be said.
"""

from __future__ import annotations

import re
from pathlib import Path

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
HOST_PLANE = "/host:CPU"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


def load_xplane(path: Path) -> dict:
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(str(path))
    planes = []
    for pl in pd.planes:
        if not (DEVICE_PLANE.match(pl.name) or pl.name == HOST_PLANE):
            continue
        lines = []
        for ln in pl.lines:
            if pl.name == HOST_PLANE and ln.name == "python":
                continue
            lines.append({"name": ln.name, "events": [
                [e.name, float(e.start_ns), float(e.duration_ns)]
                for e in ln.events]})
        planes.append({"name": pl.name, "lines": lines})
    return {"planes": planes}


def union_s(intervals: list[tuple[float, float]]) -> tuple[float, list]:
    """Total covered ns and the merged intervals."""
    merged: list[list[float]] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return sum(e - s for s, e in merged), merged


def short_name(name: str) -> str:
    """An event's own name: the trace names a device operation by its
    whole HLO text (``%fusion.12 = f32[...] fusion(...)``)."""
    return name.split(" = ", 1)[0].lstrip("%")[:64]


def base_name(name: str) -> str:
    """An operation's name without its instance suffix (``fusion.123``
    -> ``fusion``), so that the breakdown adds up kinds of work."""
    name = short_name(name)
    return re.sub(r"[.\d]+$", "", name) or name


def self_times(events: list) -> dict[str, float]:
    """Time each kind of operation ran itself, less the operations nested
    in it (a ``while`` holds its body's operations on the same line)."""
    out: dict[str, float] = {}
    stack: list[list] = []  # [end, name, self_ns]

    def close(upto: float) -> None:
        while stack and stack[-1][0] <= upto:
            _, name, own = stack.pop()
            out[name] = out.get(name, 0.0) + max(own, 0.0)

    for name, s, d in sorted(events, key=lambda e: (e[1], -e[2])):
        if d <= 0:
            continue
        close(s)
        if stack:
            stack[-1][2] -= d
        stack.append([s + d, base_name(name), d])
    close(float("inf"))
    return out


def reduce_trace(trace: dict, step_programs,
                 window_ns: float | None = None) -> dict:
    step_programs = tuple(step_programs)
    devs = [p for p in trace["planes"] if DEVICE_PLANE.match(p["name"])]
    host = [p for p in trace["planes"] if p["name"] == HOST_PLANE]
    if not devs:
        return {"planes": [p["name"] for p in trace["planes"]],
                "busy_s": 0.0, "window_s": 0.0, "step_s": 0.0,
                "step_calls": 0, "breakdown": {"device_ops": [],
                                               "idle_gaps": []}}
    per_dev, ops_time, step_ns, step_calls = [], {}, 0.0, 0
    lo, hi = float("inf"), 0.0
    gaps_src = None
    for p in devs:
        ops = [ln for ln in p["lines"] if ln["name"] == OPS_LINE] \
            or p["lines"]
        iv = [(s, s + d) for ln in ops for _, s, d in ln["events"] if d > 0]
        busy, mergedv = union_s(iv)
        per_dev.append(busy)
        if iv:
            lo = min(lo, min(s for s, _ in iv))
            hi = max(hi, max(e for _, e in iv))
        if gaps_src is None:
            gaps_src = mergedv
        for ln in ops:
            for k, v in self_times(ln["events"]).items():
                ops_time[k] = ops_time.get(k, 0.0) + v
        for ln in p["lines"]:
            if ln["name"] != MODULES_LINE:
                continue
            for name, _, d in ln["events"]:
                if short_name(name).startswith(step_programs):
                    step_ns += d
                    step_calls += 1
    window = window_ns if window_ns else max(hi - lo, 0.0)
    host_ev = [(s, s + d, name) for p in host for ln in p["lines"]
               for name, s, d in ln["events"] if d > 0]
    gaps = []
    merged = gaps_src or []
    for (_, e0), (s1, _) in zip(merged, merged[1:]):
        gaps.append((s1 - e0, e0, s1))
    gaps.sort(reverse=True)
    idle = []
    for length, g0, g1 in gaps[:10]:
        best, cover = "unattributed", 0.0
        for s, e, name in host_ev:
            c = min(e, g1) - max(s, g0)
            if c > cover:
                best, cover = name, c
        idle.append([short_name(best), length / 1e9])
    top = sorted(ops_time.items(), key=lambda kv: -kv[1])[:10]
    n = len(devs)
    return {
        "planes": [p["name"] for p in trace["planes"]],
        "lines": {p["name"]: {ln["name"]: len(ln["events"])
                              for ln in p["lines"]} for p in trace["planes"]},
        "modules": sorted({short_name(name) for p in devs
                           for ln in p["lines"] if ln["name"] == MODULES_LINE
                           for name, _, _ in ln["events"]})[:12],
        "busy_s": sum(per_dev) / n / 1e9,
        "window_s": window / 1e9,
        "step_s": step_ns / n / 1e9,
        "step_calls": step_calls // n,
        "breakdown": {"device_ops": [[k, v / n / 1e9] for k, v in top],
                      "idle_gaps": idle},
    }


def reduce_dir(trace_dir: Path, step_programs) -> dict:
    files = sorted(Path(trace_dir).glob("plugins/profile/*/*.xplane.pb"))
    if not files:
        raise SystemExit(f"benchmark: no trace under {trace_dir}")
    return reduce_trace(load_xplane(files[-1]), step_programs)
