"""The churn cell's two planted table faults, rehearsed on the CPU
(ISSUE 39): ``benchmark/tests/faulty_churn_run.py`` builds the engine
with aging left out, or with a TTL under the limiter's block, while the
configuration's file (what the plain reference, the driver and the
readers see) stays as it is.  Both have to come out not correct, each
by the comparison meant for it.  (The int4 control and the other cells'
faults on this cell are run by hand: ``benchmark/tests/``.)"""

import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[1]
CELL = "c6-spoof-churn.saturate"
STEADY = {"occupancy_drift", "evicted_gap", "untracked_share"}


@pytest.fixture(scope="module")
def cache(tmp_path_factory):
    """One CPU device and a compile cache of its own, as the other
    rehearsals (tests/test_benchmark_cells.py)."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(
                   tmp_path_factory.mktemp("jax_cache")))
    env.pop("XLA_FLAGS", None)
    return env


def faulty(env, fault: str) -> tuple[dict, set]:
    p = subprocess.run(
        [sys.executable, "benchmark/tests/faulty_churn_run.py", fault,
         "--workload", CELL, "--seed", "23", "--seconds", "3",
         "--trace", "0", "--rehearse"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=240)
    assert p.returncode == 0, p.stderr[-2000:]
    r = json.loads(p.stdout.strip().splitlines()[-1])
    return r, {k for k, c in r["compared"].items()
               if c["value"] > c["limit"]}


def test_aging_left_out_fails_the_steady_state_gate(cache):
    r, over = faulty(cache, "aging_off")
    assert r["correct"] is False
    assert {"occupancy_drift", "evicted_gap"} <= over <= STEADY
    d = r["compared_detail"]["occupancy_drift"]
    assert d["evicted"] == 0 and d["tracked"][1] > 0.8 * d["capacity"]
    # the pooled sources took their rows while the table was empty, and
    # a spoofed source gets the same verdict with a row or without
    assert r["compared"]["blocks_gap"]["value"] == 0


def test_a_ttl_under_the_block_fails_blocks_gap(cache):
    """The dense reference forgets nothing, and so it sees a table
    that forgets a row that mattered."""
    r, over = faulty(cache, "ttl_under_block")
    assert r["correct"] is False
    assert "blocks_gap" in over and not over & STEADY
    assert r["compared_detail"]["blocks"]["far"] > 100
