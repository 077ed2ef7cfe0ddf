#!/usr/bin/env python3
"""Run by hand: the scope reduction on ``fixtures/scopes_small.xplane.pb``,
a synthetic trace whose answers are known (written once with the
generated ``xplane_pb2`` of another package, so this also checks the
wire reader against an independent writer).

One device, line timestamps 1 ns.  ``XLA Modules``: ``jit_mega(1)`` over
[1000,11000) ns and ``jit__table_summary(2)`` over [12000,13000), which
is not a step program.  ``XLA Ops``: a ``while`` over [1000,11000) with
``tf_op`` ``jit(mega)/while:`` holding [1000,3000) under ``fsx.decode``,
[3000,6000) under ``fsx.update/gather``, [6000,8000) under
``fsx.update/scatter/fsx.emit`` (the outermost scope counts: update),
[8000,9000) with no ``tf_op`` at all, and [9000,10500) whose ``tf_op`` is
given as a reference to ``jit(mega)/fsx.emit/concatenate:``; the
``while`` itself keeps 500 ns.  So: decode 2000, update 5000, emit 1500,
unscoped 1000 + 500 = 1500, the rest 0; together the module's 10,000 ns.
The operation under ``fsx.update`` in the table summary, the ``Async XLA
Ops`` line and the host plane (which holds an event named like a scope)
count for nothing.
"""

import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from benchmark import trace_scopes  # noqa: E402

HERE = Path(__file__).resolve().parent


def main() -> int:
    planes = trace_scopes.load(HERE / "fixtures" / "scopes_small.xplane.pb")
    r = trace_scopes.reduce_planes(planes, ("jit_step", "jit_mega"))
    want = {"decode": 2000e-9, "update": 5000e-9, "emit": 1500e-9,
            "unscoped": 1500e-9}
    bad = [k for k in set(want) | set(r["stage_s"])
           if abs(r["stage_s"].get(k, 0.0) - want.get(k, 0.0)) > 1e-15]
    if [p["name"] for p in planes] != ["/device:TPU:0"]:
        bad.append("planes")
    if not r["scoped"]:
        bad.append("scoped")
    if trace_scopes.reduce_planes(planes, ("jit__table",))["stage_s"] != {
            "update": 1000e-9}:
        bad.append("step_programs")
    names = {"jit(mega)/while/body/closed_call/fsx.probe/reduce:": "probe",
             "jit(mega)/while/body/closed_call/probe_slots/reduce:":
                 "unscoped",
             "fsx.emit": "emit", "jit(f)/notfsx.emit/x:": "unscoped",
             "": "unscoped"}
    bad += [f"stage_of({k!r})" for k, v in names.items()
            if trace_scopes.stage_of(k) != v]
    print(r)
    print("FAILED: " + ", ".join(bad) if bad else "ok")
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main())
