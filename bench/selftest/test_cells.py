"""Every cell end to end on the CPU at a tiny size (the four-chip cell on
four virtual devices): the harness's cell runner, past the look for a chip
that ``bench/run.py`` makes."""

import subprocess
import sys

import pytest

from bench import harness
from bench.selftest.small import SEED, small_cell, workloads


@pytest.mark.parametrize("name", workloads())
def test_cell_serves_and_checks_correct(name):
    cell = small_cell(name)
    r = harness.run_cell(cell, SEED, 1.0, False)
    assert r["correct"], r["checks"]
    assert r["failed"] == 0 and r["attempted"] > 0
    assert set(r["metrics"]) == {m["name"] for m in cell.end_to_end}
    assert list(r)[-1] == "checks"
    assert r["device"]["count"] >= cell.chips


def test_traced_run_reports_counter_and_span_metrics():
    cell = small_cell("cnn_backlog_busy")
    r = harness.run_cell(cell, SEED, 1.0, True)
    assert r["correct"], r["checks"]
    # no device plane and no peak off the chip: only the program's own
    # spans and counters can be read here
    assert set(r["metrics"]) == {"serve_tick_host_ms.backlog",
                                 "kept_window_share.backlog"}
    assert r["metrics"]["kept_window_share.backlog"]["value"] > 90.0
    assert "busy_s" in r["device"] and "breakdown" in r


def test_run_py_refuses_a_host_without_a_tpu():
    out = subprocess.run(
        [sys.executable, str(harness.BENCH / "run.py"), "--workload", "cnn_backlog_busy",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, env={"JAX_PLATFORMS": "cpu", "PATH": ""},
        timeout=300)
    assert out.returncode != 0
    assert out.stdout.strip() == ""
