"""A run whose timed path is broken underneath must come out not correct.

Each case breaks the system under test in the child process before the
harness runs (CPU rehearsal, so the look for a chip is skipped) and checks
that ``correct`` is false. The faults of a cell are those of its job, in
``tests/bench/faults/<job>.py``: an answer altered where it is produced (in
every job, or from the second window job on), a step that leaves its state
unchanged, half of a batch left out, and, on the mesh, the exchange between
chips left out."""
import pytest

from bench_util import fault_cases, run_fault

CASES = fault_cases()


@pytest.mark.parametrize("cell,fault,prelude", CASES, ids=[f"{c}-{f}" for c, f, _ in CASES])
def test_broken_timed_path_is_not_correct(cell, fault, prelude):
    line, err = run_fault(cell, prelude)
    assert line is not None, err[-3000:]
    assert line["attempted"] >= 2
    assert line["correct"] is False, line["checks"]
