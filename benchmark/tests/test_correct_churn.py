"""Run by hand with ``test_correct.py`` (``python3 -m pytest
benchmark/tests -q``): the churn cell's ``correct`` can fail.

``c6-spoof-churn.saturate`` at its rehearse size joins the int4 control
and the planted faults of the other cells, with two of the table's own
(``faulty_churn_run.py``): aging left out must fail the steady-state
gate, and a TTL under the block must fail ``blocks_gap``, which shows
that the dense reference, one row a source and none ever forgotten,
does see a table that forgets a row that mattered.
"""

import pytest

from benchmark.tests.test_correct import cell_args, result

CELL = "c6-spoof-churn.saturate"
RUN = "benchmark/tests/faulty_churn_run.py"


def over(r: dict) -> set:
    return {k for k, c in r["compared"].items() if c["value"] > c["limit"]}


@pytest.mark.parametrize("seed", [11, 2147483659, 4000000007])
def test_control_int4_is_not_correct(seed):
    r = result(["benchmark/run.py", *cell_args(CELL, seed),
                "--control", "int4"])
    assert r["correct"] is False
    assert over(r) & {"blocks_gap", "counters_gap"}


@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch",
                                   "answer_altered", "ring_block_lost"])
def test_fault_is_not_correct(fault):
    r = result([RUN, fault, *cell_args(CELL, 23)])
    assert r["correct"] is False, r["compared"]


def test_aging_left_out_is_not_correct():
    r = result([RUN, "aging_off", *cell_args(CELL, 23)])
    assert r["correct"] is False
    assert {"occupancy_drift", "evicted_gap"} <= over(r)
    # the pooled sources keep their rows all the same
    assert r["compared"]["blocks_gap"]["value"] == 0


def test_a_ttl_under_the_block_is_not_correct():
    r = result([RUN, "ttl_under_block", *cell_args(CELL, 23)])
    assert r["correct"] is False
    assert "blocks_gap" in over(r)
    # the table is steady; it is what it forgets that is wrong
    assert not over(r) & {"occupancy_drift", "evicted_gap",
                          "untracked_share"}


def test_no_fault_is_correct():
    r = result([RUN, "none", *cell_args(CELL, 23)])
    assert r["correct"] is True, r["compared"]
