"""Run by hand (``python3 -m pytest benchmark/tests -q``, some minutes on
the CPU; not part of the repo's tier-1 tests): ``correct`` can fail.

Every case is a whole ``run.py --rehearse`` run — tiny sizes on the CPU,
the harness's look for a chip skipped, everything else as in a chip run.

* the control: the reference in the program's place at the precision
  below the configuration's (int4 for int8) must come out NOT correct;
* each fault the one-chip cells can have, planted in the program's side
  (``faulty_run.py``), must come out NOT correct;
* the same run with no fault must come out correct.
"""

import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
FILE_CELL = "c3-offline-ml.file"
RING_CELL = "c4-syn-mix.steady"
CLOSED_CELL = "c4-syn-mix.saturate"  # the ring cell fed closed-loop


def result(cmd: list[str]) -> dict:
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, *cmd], cwd=ROOT, env=env,
                       capture_output=True, text=True, timeout=900)
    assert p.returncode == 0, p.stderr[-2000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


def cell_args(cell: str, seed: int) -> list[str]:
    return ["--workload", cell, "--seed", str(seed), "--seconds", "3",
            "--trace", "0", "--rehearse"]


@pytest.mark.parametrize("cell", [FILE_CELL, RING_CELL, CLOSED_CELL])
@pytest.mark.parametrize("seed", [11, 2147483659, 4000000007])
def test_control_int4_is_not_correct(cell, seed):
    r = result(["benchmark/run.py", *cell_args(cell, seed),
                "--control", "int4"])
    assert r["correct"] is False
    c = r["compared"]
    assert (c["blocks_gap"]["value"] > c["blocks_gap"]["limit"]
            or c["counters_gap"]["value"] > c["counters_gap"]["limit"])


@pytest.mark.parametrize("cell", [FILE_CELL, RING_CELL, CLOSED_CELL])
@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch",
                                   "answer_altered"])
def test_fault_is_not_correct(cell, fault):
    r = result(["benchmark/tests/faulty_run.py", fault,
                *cell_args(cell, 23)])
    assert r["correct"] is False, r["compared"]


def test_block_lost_before_the_ring_is_not_correct():
    r = result(["benchmark/tests/faulty_run.py", "ring_block_lost",
                *cell_args(RING_CELL, 23)])
    assert r["correct"] is False, r["compared"]
    assert r["compared"]["verdict_ring_differ"]["value"] > 0
    assert r["compared"]["blocks_gap"]["value"] == 0


@pytest.mark.parametrize("cell", [FILE_CELL, RING_CELL, CLOSED_CELL])
def test_no_fault_is_correct(cell):
    r = result(["benchmark/tests/faulty_run.py", "none",
                *cell_args(cell, 23)])
    assert r["correct"] is True, r["compared"]
