#!/usr/bin/env python3
"""``faulty_run.py`` for the churn cell: its faults, and two of the
table's own (tests only).

    python3 benchmark/tests/faulty_churn_run.py <fault> <run.py arguments...>

Both table faults are planted in the PROGRAM's side — the ``FsxConfig``
the engine is built from — while the configuration's file, which the
reference, the driver and the readers see, stays as it is:

* ``aging_off``       — ``evict_ttl_s`` 0: the sweep is not compiled in,
  the table silts up (``occupancy_drift``, ``evicted_gap``);
* ``ttl_under_block`` — ``evict_ttl_s`` half the limiter's block: a
  blocked source's row is freed before it returns, so it comes back
  with no votes and no history and is blocked later than the dense
  reference, which forgets nothing, blocks it (``blocks_gap``): the
  reference does see a forgetful table;
* anything else is ``faulty_run.py``'s (``none`` included).
"""

import dataclasses
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

TABLE_FAULTS = {"aging_off": 0.0, "ttl_under_block": 0.5}


def plant(fault: str) -> None:
    if fault not in TABLE_FAULTS:
        from benchmark.tests import faulty_run

        return faulty_run.plant(fault)
    from benchmark import harness

    stated = harness.engine_config

    def altered(config: dict):
        cfg = stated(config)
        ttl = TABLE_FAULTS[fault] * cfg.limiter.block_s
        return dataclasses.replace(cfg, table=dataclasses.replace(
            cfg.table, evict_ttl_s=ttl))

    harness.engine_config = altered


if __name__ == "__main__":
    fault = sys.argv[1]
    sys.argv = ["benchmark/run.py", *sys.argv[2:]]
    import runpy

    sys.path.insert(0, str(ROOT / "benchmark"))
    plant(fault)
    runpy.run_path(str(ROOT / "benchmark" / "run.py"), run_name="__main__")
