#!/usr/bin/env python3
"""Run by hand, beside ``check_trace_scopes.py`` (whose fixture dates
from before a cell had aging on): the scope reduction knows an eighth
stage.  Planes as ``trace_scopes.load`` returns them, built here: one
device, ``jit_mega`` over [0, 12,000) ns holding a ``while`` whose body
spends 3,000 ns under ``fsx.evict`` (a gather of 2,000 with a fusion of
500 nested in it, and a scatter of 1,000) before the seven stages the
fixture has, 1,000 ns each, and keeps 2,000 ns itself; a second module,
not a step program, holds an ``fsx.evict`` operation that counts for
nothing.  So: evict 3,000, the six named stages 1,000 each, unscoped
1,000 + 2,000, together the module's 12,000 ns.
"""

import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from benchmark import trace_scopes  # noqa: E402

NS = 1000  # ps
STAGES = ("decode", "classify", "probe", "aggregate", "update", "emit")


def planes() -> list[dict]:
    path = "jit(mega)/while/body/closed_call/fsx.{}/{}:"
    ops = [(trace_scopes.stage_of("jit(mega)/while:"), 0, 12_000 * NS),
           (trace_scopes.stage_of(path.format("evict", "gather")),
            0, 2_000 * NS),
           (trace_scopes.stage_of(path.format("evict", "gather/fusion")),
            500 * NS, 500 * NS),
           (trace_scopes.stage_of(path.format("evict", "scatter")),
            2_000 * NS, 1_000 * NS)]
    t = 3_000
    for stage in (*STAGES, None):
        tf_op = path.format(stage, "fusion") if stage else ""
        ops.append((trace_scopes.stage_of(tf_op), t * NS, 1_000 * NS))
        t += 1_000
    ops.append((trace_scopes.stage_of(path.format("evict", "gather")),
                13_000 * NS, 500 * NS))
    return [{"name": "/device:TPU:0",
             "modules": [("jit_mega(1)", 0, 12_000 * NS),
                         ("jit__table_summary(2)", 13_000 * NS, 1_000 * NS)],
             "ops": ops}]


def main() -> int:
    r = trace_scopes.reduce_planes(planes(), ("jit_step", "jit_mega"))
    want = dict({s: 1000e-9 for s in STAGES}, evict=3000e-9,
                unscoped=3000e-9)
    bad = [k for k in set(want) | set(r["stage_s"])
           if abs(r["stage_s"].get(k, 0.0) - want.get(k, 0.0)) > 1e-15]
    if abs(sum(r["stage_s"].values()) - 12_000e-9) > 1e-15:
        bad.append("sum")
    if not r["scoped"]:
        bad.append("scoped")
    print(r)
    print("FAILED: " + ", ".join(bad) if bad else "ok")
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main())
