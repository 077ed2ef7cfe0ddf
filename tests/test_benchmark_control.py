"""The other half of ``test_benchmark_judge.py``: the int4 control in
the program's place, and the block lost before the verdict ring, must
both come out NOT correct (``benchmark/tests/test_correct.py``'s own
functions, called, not copied)."""

import pytest

from test_benchmark_judge import CLOSED, FILE, RING, correct


@pytest.mark.parametrize("cell,seed", [
    (FILE, 11), (FILE, 2147483659), (FILE, 4000000007),
    (RING, 11), (CLOSED, 11)])
def test_control_int4_is_not_correct(cell, seed):
    correct.test_control_int4_is_not_correct(cell, seed)


def test_block_lost_before_the_ring_is_not_correct():
    correct.test_block_lost_before_the_ring_is_not_correct()
