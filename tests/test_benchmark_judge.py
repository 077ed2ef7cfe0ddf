"""What decides ``correct``, guarded between ``benchmark`` PRs: the fast
subset of ``benchmark/tests/`` (whole ``run.py --rehearse`` runs, tiny
sizes on the CPU), by calling those files' own functions — none is
copied here, so the judge's tests and these cannot drift apart.  The
int4 control and the block lost before the ring are in
``test_benchmark_control.py``, so that no one worker's share of the
two passes four minutes."""

import importlib.util
from pathlib import Path

import pytest

BENCH_TESTS = Path(__file__).resolve().parents[1] / "benchmark" / "tests"


def load(name: str):
    """``benchmark/tests/<name>.py`` under a module name of its own (the
    directory is not a package, and pytest must not collect it twice)."""
    spec = importlib.util.spec_from_file_location(
        f"bench_tests_{name}", BENCH_TESTS / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


correct = load("test_correct")
FILE, RING, CLOSED = (correct.FILE_CELL, correct.RING_CELL,
                      correct.CLOSED_CELL)


@pytest.mark.parametrize("cell", [FILE, RING, CLOSED])
def test_no_fault_is_correct(cell):
    correct.test_no_fault_is_correct(cell)


@pytest.mark.parametrize("cell,fault", [
    (FILE, "state_unchanged"), (FILE, "half_batch"),
    (FILE, "answer_altered"), (RING, "answer_altered"),
    (CLOSED, "answer_altered")])
def test_fault_is_not_correct(cell, fault):
    correct.test_fault_is_not_correct(cell, fault)


def test_benchmark_json_names_only_what_exists():
    load("test_closed_loop").test_benchmark_json_names_only_what_exists()
