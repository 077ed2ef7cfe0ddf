#!/usr/bin/env python3
"""Run by hand: the trace reduction on ``fixtures/trace_small.json``, a
synthetic trace whose answers are known.

One device, operations at [1000,2500) [2500,5000), a ``while`` over
[7000,15000) holding [7000,12000) and [12000,15000), and [16000,17000)
ns: the union is 4000 + 8000 + 1000 = 13,000 ns busy of a 16,000 ns
window (idle 18.75 %); the ``while`` itself ran 0 ns, the three fusions
7,500 ns.  Two step programs
(4000 + 8000 ns = 12,000 ns; ``jit_table_summary`` is not one).  The
longest idle gap is [5000,7000), which the host's ``device_put`` covers
most of; the next is [15000,16000), covered by ``poll``.
"""

import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from benchmark import trace_reduce  # noqa: E402

HERE = Path(__file__).resolve().parent


def main() -> int:
    r = trace_reduce.reduce_trace(
        json.loads((HERE / "fixtures" / "trace_small.json").read_text()),
        ("jit_step", "jit_mega"))
    want = {"busy_s": 13000e-9, "window_s": 16000e-9, "step_s": 12000e-9,
            "step_calls": 2}
    bad = [k for k, v in want.items() if abs(r[k] - v) > 1e-12]
    ops = dict(r["breakdown"]["device_ops"])
    if abs(ops.get("fusion", 0) - 7500e-9) > 1e-12 or ops.get("while") != 0:
        bad.append("device_ops")
    gaps = r["breakdown"]["idle_gaps"]
    if [g[0] for g in gaps] != ["device_put", "poll"] or \
            abs(gaps[0][1] - 2000e-9) > 1e-12:
        bad.append("idle_gaps")
    print(json.dumps(r, indent=1))
    print("FAILED: " + ", ".join(bad) if bad else "ok")
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main())
