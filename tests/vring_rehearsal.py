#!/usr/bin/env python3
"""One rehearsed benchmark run against a verdict ring of a chosen size
(a helper of ``test_benchmark_cells.py``, not a test file).

    python3 tests/vring_rehearsal.py <slots> <waiting|discarding> <run.py arguments...>

``benchmark/run.py`` as it is, with two things put under it: ``fsxd`` is
started through a wrapper that adds ``--verdict-ring-capacity <slots>``
(the benchmark's driver builds the daemon's command line and sets no
such option), and with ``discarding`` the ring's writer is the parent
commit's: it pushes what fits and counts the rest ``dropped`` at once.
The sink's accounting goes to stderr as ``vring_accounting {...}`` when
the run ends; the last line of stdout stays the result.
"""

import atexit
import json
import os
import stat
import sys
import tempfile
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))


def plant(slots: int, writer: str) -> None:
    import numpy as np

    from benchmark import harness
    from flowsentryx_tpu.core import schema
    from flowsentryx_tpu.engine import shm

    build_fsxd = harness.build_fsxd
    wrapdir = Path(tempfile.mkdtemp(prefix="fsxd-wrap-"))

    def wrapped_fsxd() -> Path:
        real = build_fsxd()
        script = wrapdir / "fsxd"
        script.write_text(f'#!/bin/sh\nexec "{real}" '
                          f'--verdict-ring-capacity {slots} "$@"\n')
        script.chmod(script.stat().st_mode | stat.S_IXUSR)
        return script

    harness.build_fsxd = wrapped_fsxd

    sinks = []
    init = shm.ShmVerdictSink.__init__

    def remembered(self, *a, **kw):
        init(self, *a, **kw)
        sinks.append(self)

    shm.ShmVerdictSink.__init__ = remembered

    if writer == "discarding":
        def discard(self, update):  # the parent's apply, word for word
            n = len(update.key)
            if not n:
                return
            rec = np.zeros(n, schema.VERDICT_RECORD_DTYPE)
            rec["saddr"] = update.key
            rec["until_ns"] = (
                update.until_s.astype(np.float64) * 1e9
            ).astype(np.uint64) + np.uint64(self.t0_ns)
            pushed = self.ring.produce(rec)
            self.dropped += n - pushed

        shm.ShmVerdictSink.apply = discard
    elif writer != "waiting":
        raise SystemExit(f"unknown writer {writer!r}")

    def tell():
        for s in sinks:
            acc = dict(s.ring_accounting(), slots=s.ring.capacity,
                       wait_samples=int(s.vring_wait.hist.n))
            print("vring_accounting", json.dumps(acc), file=sys.stderr)
        for f in wrapdir.iterdir():
            os.unlink(f)
        wrapdir.rmdir()

    atexit.register(tell)


if __name__ == "__main__":
    slots, writer = int(sys.argv[1]), sys.argv[2]
    sys.argv = ["benchmark/run.py", *sys.argv[3:]]
    import runpy

    sys.path.insert(0, str(ROOT / "benchmark"))
    plant(slots, writer)
    runpy.run_path(str(ROOT / "benchmark" / "run.py"), run_name="__main__")
