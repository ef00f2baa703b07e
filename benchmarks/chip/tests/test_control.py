"""The control, the reference computed in float8 and put in the program's
place, comes out not correct under each cell's limits (on the CPU, at a small
size; ``calibrate.py`` reads it on the chip at the cell's own size)."""

import time

import jax
import pytest

from benchmarks.chip import traffic
from benchmarks.chip.runners import lm_train
from benchmarks.chip.tests import tiny


@pytest.mark.parametrize("workload", ["yi6b-train-s4k", "granite8b-train-s1k",
                                      "yi6b-zero1-dp4-s4k"])
def test_control_fails(workload, monkeypatch):
    cell = tiny.cell(workload)
    devices = jax.devices()[:cell.chips]
    program_steps = lm_train.Run.first_steps

    def control_steps(self):
        program_steps(self)  # the window goes on from the program's state
        batches = [traffic.batch(cell.traffic, cell.sizes.vocab, self.seed, k, cell.chips)
                   for k in range(cell.traffic["checked_steps"])]
        return lm_train.default_reference(cell, devices, "fp8").run(self.seed, batches)

    monkeypatch.setattr(lm_train.Run, "first_steps", control_steps)
    r = lm_train.run_cell(cell, 2**31 + 5, 0.2, False, time.perf_counter(), devices)
    assert not r["correct"], r["check"]
