"""The check must refuse a broken timed path: each fault of
``bench/faults.py`` is planted under the serving entry the cell drives, and
``correct`` has to come out false.  Per tick, half of the batch left out
runs at the cell's own camera count and check sample, so the sampled
cameras fall into both halves as they do on the chip."""

import pytest

from bench import faults, harness
from bench.selftest.small import SEED, entry, small_cell, traffic, workloads


@pytest.mark.parametrize("fault", faults.KINDS)
@pytest.mark.parametrize("name", workloads())
def test_fault_is_refused(name, fault):
    full = traffic(name)
    per_tick = entry(name) == "tick"
    cell = (small_cell(name, cameras=full["cameras"], check=full["check"])
            if fault == "half" and per_tick else small_cell(name))
    with faults.plant(fault, entry(name)):
        r = harness.run_cell(cell, SEED, 1.0, False)
    assert not r["correct"], r["checks"]
