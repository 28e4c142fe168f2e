"""The control, the reference computed in bfloat16 in the program's place,
has to fail the check of every cell at a size the CPU holds."""

import pytest

from bench import control
from bench.selftest.small import SEED, small_cell, workloads


@pytest.mark.parametrize("name", workloads())
def test_control_reads_above_the_limits(name):
    cell = small_cell(name)
    numbers = control.control_readings(cell, SEED, 64)
    assert any(numbers[k] > cell.limits[k] for k in cell.limits), numbers
