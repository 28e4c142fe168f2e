"""The trace reduction on hand-made intervals."""

import re

from bench import trace


def _trace():
    dev = trace.Device(ops=[(0, 10, "a"), (5, 20, "fpca_kernel"), (40, 50, "b")])
    dev.busy = trace._merge((s, e) for s, e, _ in dev.ops)
    host = [(0, 100, "bench:next"), (25, 35, "bench:schedule_wait")]
    return trace.Trace(window=(0, 100), devices={0: dev}, host=host)


def test_busy_is_the_union_of_op_intervals():
    t = _trace()
    assert t.devices[0].busy == [(0, 20), (40, 50)]
    assert abs(t.busy_s - 30e-9) < 1e-18 and abs(t.window_s - 100e-9) < 1e-18
    assert abs(t.op_seconds(re.compile("fpca_kernel")) - 15e-9) < 1e-18


def test_idle_gaps_are_labelled_by_the_innermost_host_span():
    gaps = trace.idle_gaps(_trace())
    assert [g[0] for g in gaps] == ["bench:next", "bench:schedule_wait"]
    assert abs(gaps[0][1] - 50e-9) < 1e-18 and abs(gaps[1][1] - 20e-9) < 1e-18


def test_top_ops_sum_by_name():
    ops = trace.top_ops(_trace())
    assert ops[0][0] == "fpca_kernel" and abs(ops[0][1] - 15e-9) < 1e-18


def test_recorded_chip_trace_reduces_to_the_run_s_numbers():
    """A traced ``cnn_backlog_busy`` run on one TPU v5e (operation names
    shortened): busy time, traced window and kernel time as that run
    printed them, its kernel first among the operations, and its idle gaps
    spent in ``StreamServer.run``."""
    import json
    from pathlib import Path

    data = json.loads((Path(__file__).parent / "data" / "backlog_busy_trace.json").read_text())
    t = trace.from_json(data)
    assert t.busy_s == 0.224418364
    assert abs(t.window_s - 14.199020733) < 1e-9
    kernel = re.compile("tpu_custom_call")
    assert abs(t.op_seconds(kernel) - 0.073975246) < 1e-12
    assert 100.0 * (1.0 - t.busy_s / t.window_s) > 98.0
    top = trace.top_ops(t)
    assert top[0][0] == "%run.1 custom-call tpu_custom_call"
    assert len(top) == 10 and all(a[1] >= b[1] for a, b in zip(top, top[1:]))
    gaps = trace.idle_gaps(t)
    assert gaps[0] == ["bench:next", 2.299478]
    assert all(g[1] > 2.0 for g in gaps[:6])
