"""A run with the timed path broken underneath comes out not correct.

Each test drives the harness past its look for a chip, on the CPU, with a
small copy of a cell (``tiny.py``) under the cell's own limits: a sound run
is correct, and each fault that the cell can have, planted in the program
(``faults.py``), makes ``correct`` false.  The four-chip cell runs on four
virtual CPU devices, where the exchange between them can be left out.
"""

import time

import jax
import pytest

from benchmarks.chip import faults
from benchmarks.chip.runners import lm_train
from benchmarks.chip.tests import tiny

CELLS = {
    "yi6b-train-s4k": (dict(), ["state_unchanged", "half_batch"]),
    "granite8b-train-s1k": (dict(), ["state_unchanged", "half_batch"]),
    "yi6b-zero1-dp4-s4k": (dict(), ["state_unchanged", "half_batch", "no_exchange"]),
}
CASES = [(c, f) for c, (_, fs) in CELLS.items() for f in [None] + fs]


def run(name, seed=2**31 + 99):
    cell = tiny.cell(**(CELLS[name][0] or {"workload": name}))
    return lm_train.run_cell(cell, seed, 0.2, False, time.perf_counter(),
                            jax.devices()[:cell.chips])


@pytest.mark.parametrize("name,fault", CASES, ids=[f"{c}-{f or 'sound'}" for c, f in CASES])
def test_fault_is_caught(name, fault):
    if fault is None:
        r = run(name)
        assert r["correct"], r["check"]
        return
    with faults.ALL[fault]():
        r = run(name)
    assert not r["correct"], r["check"]
    assert list(r)[-1] == "check"
