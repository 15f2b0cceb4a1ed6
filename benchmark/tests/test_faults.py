"""With the timed path broken underneath, a run's `correct` comes out
false: for each fault a cell's mix lists, and for the control (every RS
matrix built by the Cauchy construction).  Each in a process of its own,
so no codec cache built before the plant survives into it.  The cases
come from BENCHMARK.json and the mixes: a new cell brings its own."""

import pytest

from benchmark.tests import plant
from benchmark.tests.test_cells import CELLS, _control

CASES = [(c["name"], c["chips"], f) for c in CELLS
         for f in plant.faults(c["name"])]


@pytest.mark.parametrize("cell,chips,fault", CASES)
def test_fault_turns_correct_false(cell, chips, fault):
    (r,) = _control(cell, f"fault:{fault}", devices=chips)
    assert not r["correct"], r["compared"]


@pytest.mark.parametrize("cell,chips",
                         [(c["name"], c["chips"]) for c in CELLS])
def test_control_turns_correct_false(cell, chips):
    (r,) = _control(cell, "control", devices=chips)
    assert not r["correct"], r["compared"]
