"""Fleet budget arbitration + multi-device sharding (the ``-m fleet`` lane).

Contracts pinned here:

* **Water-filling split** — allocations sum to the budget, respect the
  per-stream ``[floor, ceiling]`` clamp, and order like the weights
  (``priority * activity``); ceiling-capped excess re-spreads.
* **Arbitration dynamics** — a busy scene's allocation rises at a static
  scene's expense while the fleet total stays pinned to the budget, and
  every re-solved share lands in that stream's PI servo as its new target
  (bumpless: EMA/integrator state carries over).
* **Admission control** — at most ``budget // floor`` streams; over
  capacity the fleet rejects (default) or queues FIFO, and rejections
  leave all telemetry surfaces reconciled.
* **Multi-device parity** — serving the fleet with the fused batch sharded
  over a host mesh's data axes is bit-identical to unsharded serving, with
  gate/arbitration state host-local.  The CI lane re-runs this module under
  ``XLA_FLAGS=--xla_force_host_platform_device_count=8``; locally it adapts
  to however many devices exist.
"""

from __future__ import annotations

import json

import jax
import numpy as np
import pytest

from repro.core.mapping import FPCASpec
from repro.data.pipeline import SyntheticMovingObject
from repro.fpca import telemetry
from repro.launch.mesh import make_host_mesh
from repro.serving.fleet import FleetAdmissionError, FleetConfig, FleetController
from repro.serving.fpca_pipeline import FPCAPipeline
from repro.serving.observe import (
    assert_reconciled,
    fleet_report,
    render_fleet_report,
)
from repro.serving.streaming import (
    DeltaGateConfig,
    GateControllerConfig,
    StreamServer,
)
from repro.serving.fleet import _waterfill

pytestmark = pytest.mark.fleet

H = W = 24
SPEC = FPCASpec(image_h=H, image_w=W, out_channels=4, kernel=5, stride=5)
GATE = DeltaGateConfig(threshold=0.05, hysteresis=1, keyframe_interval=8)


def _kernel(seed: int = 0) -> np.ndarray:
    rng = np.random.default_rng(seed)
    k = SPEC.kernel
    return (rng.normal(size=(SPEC.out_channels, k, k, 3)) * 0.2).astype(
        np.float32
    )


def _pipeline(mesh=None) -> FPCAPipeline:
    pipe = FPCAPipeline(backend="basis", mesh=mesh)
    pipe.register("cam", SPEC, _kernel())
    return pipe


def _fleet(config: FleetConfig, mesh=None, target: float = 0.5):
    pipe = _pipeline(mesh)
    server = StreamServer(
        pipe, gate=GATE, controller=GateControllerConfig(target=target)
    )
    return pipe, server, FleetController(server, config)


def _busy(seed: int = 3) -> SyntheticMovingObject:
    return SyntheticMovingObject((H, W), seed=seed, radius=4.0)


def _static_frame(seed: int = 7) -> np.ndarray:
    rng = np.random.default_rng(seed)
    return rng.uniform(0, 1, (H, W, 3)).astype(np.float32)


# ---------------------------------------------------------------------------
# water-filling split (pure math)
# ---------------------------------------------------------------------------


def test_waterfill_sums_to_budget_within_bounds():
    weights = {"a": 1.0, "b": 2.0, "c": 4.0}
    alloc = _waterfill(weights, 0.6, 0.05, 0.4)
    assert sum(alloc.values()) == pytest.approx(0.6)
    for v in alloc.values():
        assert 0.05 <= v <= 0.4 + 1e-12
    # allocations order like the weights
    assert alloc["a"] < alloc["b"] < alloc["c"]


def test_waterfill_ceiling_respreads_excess():
    # one dominant stream would claim ~0.55 of 0.6 unclamped; the ceiling
    # caps it and the clawed-back excess re-spreads over the rest
    alloc = _waterfill({"hog": 100.0, "a": 1.0, "b": 1.0}, 0.6, 0.02, 0.3)
    assert alloc["hog"] == pytest.approx(0.3)
    assert alloc["a"] == pytest.approx(alloc["b"])
    assert sum(alloc.values()) == pytest.approx(0.6)


def test_waterfill_floor_only_when_budget_tight():
    # budget == n * floor: everyone sits exactly at the floor
    alloc = _waterfill({"a": 5.0, "b": 1.0}, 0.2, 0.1, 0.9)
    assert alloc == {"a": pytest.approx(0.1), "b": pytest.approx(0.1)}


def test_fleet_config_validation():
    with pytest.raises(ValueError):
        FleetConfig(budget=0.0)
    with pytest.raises(ValueError):
        FleetConfig(floor=0.5, ceiling=0.4)
    with pytest.raises(ValueError):
        FleetConfig(budget=0.1, floor=0.2)
    with pytest.raises(ValueError):
        FleetConfig(admission="defer")
    with pytest.raises(ValueError):
        FleetConfig(rebalance_ticks=0)


# ---------------------------------------------------------------------------
# arbitration dynamics
# ---------------------------------------------------------------------------


def test_busy_stream_wins_budget_from_static_stream():
    """The starved-vs-greedy contract: a moving scene's activity EMA rises,
    so arbitration shifts budget to it; the static stream decays toward the
    floor; the fleet total stays pinned to the budget."""
    cfg = FleetConfig(budget=0.6, floor=0.1, ceiling=0.9, rebalance_ticks=4)
    pipe, server, fc = _fleet(cfg)
    fc.add_stream("busy", "cam")
    fc.add_stream("static", "cam")
    # right after admission both weigh in at full activity -> equal split
    assert fc._members["busy"].allocation == pytest.approx(0.3)
    assert fc._members["static"].allocation == pytest.approx(0.3)
    cam, still = _busy(), _static_frame()
    for _ in fc.run({"busy": cam.frame_at(t), "static": still}
                    for t in range(24)):
        pass
    m_busy, m_static = fc._members["busy"], fc._members["static"]
    assert m_busy.activity > m_static.activity
    assert m_busy.allocation > m_static.allocation
    assert m_busy.allocation + m_static.allocation == pytest.approx(
        cfg.budget
    )
    # each share was pushed into that stream's servo as its new target
    for m in (m_busy, m_static):
        assert m.session.controller.config.target == pytest.approx(
            m.allocation
        )
    assert fc.rebalances >= 24 // cfg.rebalance_ticks


def test_retarget_is_bumpless():
    """A rebalance re-points the servo without resetting its state."""
    _, server, fc = _fleet(FleetConfig(budget=0.6, floor=0.1))
    fc.add_stream("s0", "cam")
    cam = _busy(seed=5)
    list(fc.serve("s0", (cam.frame_at(t) for t in range(6))))
    ctl = server.sessions["s0"].controller
    ema, hist, thr = ctl.ema, len(ctl.history), ctl.threshold
    assert hist == 6 and ema is not None
    ctl.retarget(0.123)
    assert ctl.config.target == 0.123
    assert ctl.ema == ema and len(ctl.history) == hist
    assert ctl.threshold == thr        # actuation waits for an observation
    ctl.retarget(0.123)                # no-op on an unchanged target
    assert ctl.config.target == 0.123
    with pytest.raises(ValueError):
        ctl.retarget(0.0)              # GateControllerConfig re-validates


def test_segment_serving_rebalances_every_boundary():
    cfg = FleetConfig(budget=0.6, floor=0.1, rebalance_ticks=1000)
    _, server, fc = _fleet(cfg)
    fc.add_stream("s0", "cam")
    before = fc.rebalances
    cam = _busy(seed=6)
    frames = np.stack([cam.frame_at(t) for t in range(12)])
    got = list(fc.serve_segments("s0", frames, segment_length=4))
    assert len(got) == 12
    # one re-solve per boundary (the only point a traced threshold moves),
    # regardless of the per-tick cadence
    assert fc.rebalances - before == 3
    assert fc._members["s0"].ticks_observed == 12


def test_fleet_segment_serving_matches_plain_server():
    """Arbitration wraps serving without perturbing a single-stream trace:
    with one admitted stream the allocation is budget-clamped once at
    admission, after which results must match a plain server given the same
    initial target."""
    cfg = FleetConfig(budget=0.4, floor=0.1, ceiling=0.4)
    _, _, fc = _fleet(cfg)
    fc.add_stream("s0", "cam")
    cam = _busy(seed=9)
    frames = np.stack([cam.frame_at(t) for t in range(8)])
    got = list(fc.serve_segments("s0", frames, segment_length=4))
    ref_srv = StreamServer(
        _pipeline(), gate=GATE,
        controller=GateControllerConfig(target=0.4),
    )
    ref_srv.add_stream("s0", "cam")
    ref = list(ref_srv.serve_segments("s0", frames, segment_length=4))
    assert len(got) == len(ref)
    for a, b in zip(got, ref):
        assert a.frame_idx == b.frame_idx
        assert a.kept_windows == b.kept_windows
        np.testing.assert_array_equal(a.counts, b.counts)
        np.testing.assert_array_equal(a.block_mask, b.block_mask)


# ---------------------------------------------------------------------------
# admission control
# ---------------------------------------------------------------------------


def test_admission_rejects_over_capacity_and_reconciles():
    cfg = FleetConfig(budget=0.6, floor=0.2)     # capacity 3
    pipe, server, fc = _fleet(cfg)
    assert fc.capacity == 3
    for i in range(3):
        assert fc.add_stream(f"s{i}", "cam") is not None
    with pytest.raises(FleetAdmissionError):
        fc.add_stream("s3", "cam")
    with pytest.raises(FleetAdmissionError):
        fc.add_stream("s4", "cam")
    assert fc.rejections == 2
    assert len(server.sessions) == 3             # rejected streams left no trace
    # rejected admissions must not skew any stats surface
    assert_reconciled(pipe, server)


def test_admission_queue_fifo():
    cfg = FleetConfig(budget=0.6, floor=0.2, admission="queue")
    _, server, fc = _fleet(cfg)
    for i in range(3):
        fc.add_stream(f"s{i}", "cam")
    assert fc.add_stream("s3", "cam", priority=2.0) is None
    assert fc.add_stream("s4", "cam") is None
    assert fc.queued == ("s3", "s4")
    assert fc.rejections == 2
    admitted = fc.remove_stream("s1")
    assert [s.stream_id for s in admitted] == ["s3"]   # FIFO
    assert fc.queued == ("s4",)
    assert "s3" in server.sessions and "s1" not in server.sessions
    assert fc._members["s3"].priority == 2.0           # kwargs survived the queue
    # freeing two slots admits the rest
    admitted = fc.remove_stream("s2")
    assert [s.stream_id for s in admitted] == ["s4"]
    assert fc.queued == ()


def test_duplicate_and_invalid_admissions():
    _, server, fc = _fleet(FleetConfig(budget=0.6, floor=0.1))
    fc.add_stream("s0", "cam")
    with pytest.raises(ValueError, match="already admitted"):
        fc.add_stream("s0", "cam")
    with pytest.raises(ValueError, match="priority"):
        fc.add_stream("s1", "cam", priority=0.0)
    with pytest.raises(KeyError):
        fc.remove_stream("ghost")
    # a fleet stream without a servo has no actuator: rejected AND rolled back
    srv_plain = StreamServer(_pipeline(), gate=GATE)   # no controller default
    fc2 = FleetController(srv_plain, FleetConfig(budget=0.6, floor=0.1))
    with pytest.raises(ValueError, match="GateController"):
        fc2.add_stream("s0", "cam")
    assert "s0" not in srv_plain.sessions


# ---------------------------------------------------------------------------
# telemetry rollups + reporting
# ---------------------------------------------------------------------------


def test_allocation_gauges_sum_to_budget():
    cfg = FleetConfig(budget=0.6, floor=0.1)
    _, _, fc = _fleet(cfg)
    fc.add_stream("g0", "cam")
    fc.add_stream("g1", "cam", priority=3.0)
    reg = telemetry.registry()
    rows = {
        labels["stream"]: value
        for name, _k, labels, value in reg.collect()
        if name == "fpca_fleet_allocation" and labels.get("stream") in
        ("g0", "g1")
    }
    assert sum(rows.values()) == pytest.approx(cfg.budget)
    assert rows["g1"] > rows["g0"]               # priority weighs in pre-serving
    budget = [v for n, _k, _l, v in reg.collect() if n == "fpca_fleet_budget"]
    assert budget == [pytest.approx(cfg.budget)]


def test_idle_stream_round_trips_strict_json():
    """An admitted-but-never-served stream (0 executed windows) flows
    through the arbitration table and fleet report with None sentinels —
    never Infinity (the strict-JSON writer would refuse it)."""
    pipe, server, fc = _fleet(FleetConfig(budget=0.6, floor=0.1))
    fc.add_stream("idle", "cam")
    cam = _busy(seed=8)
    fc.add_stream("live", "cam")
    list(fc.serve("live", (cam.frame_at(t) for t in range(4))))
    table = fc.arbitration_table()
    rows = {r["stream"]: r for r in table["streams"]}
    assert rows["idle"]["activity"] is None      # never observed
    assert rows["idle"]["ticks_observed"] == 0
    assert rows["live"]["activity"] is not None
    report = fleet_report(server, fleet=fc)
    text = json.dumps(report, allow_nan=False)   # strict RFC 8259
    assert "Infinity" not in text and "NaN" not in text
    assert report["arbitration"]["admitted"] == 2
    rendered = render_fleet_report(report)
    assert "arbitration: budget 0.6" in rendered
    assert "idle: prio 1" in rendered


def test_removed_stream_zeroes_its_gauges():
    _, _, fc = _fleet(FleetConfig(budget=0.6, floor=0.1))
    fc.add_stream("r0", "cam")
    fc.add_stream("r1", "cam")
    fc.remove_stream("r0")
    rows = {
        labels["stream"]: value
        for name, _k, labels, value in telemetry.registry().collect()
        if name == "fpca_fleet_allocation" and labels.get("stream") in
        ("r0", "r1")
    }
    assert rows["r0"] == 0.0
    assert rows["r1"] == pytest.approx(0.6)      # sole survivor takes it all


# ---------------------------------------------------------------------------
# multi-device sharding (8 emulated devices in the CI lane, adapts locally)
# ---------------------------------------------------------------------------


def test_sharded_fleet_serving_matches_unsharded():
    """The fused union-masked fleet batch shards over the mesh data axes
    bit-identically, with gate + arbitration state host-local.  Under the CI
    lane's XLA_FLAGS this runs with data=8; locally with whatever exists."""
    ndev = jax.device_count()
    mesh = make_host_mesh(data=ndev)
    cfg = FleetConfig(budget=0.6, floor=0.1, rebalance_ticks=4)
    cams = {f"cam{i}": _busy(seed=10 + i) for i in range(3)}

    def _serve(mesh_arg):
        pipe, server, fc = _fleet(cfg, mesh=mesh_arg)
        for sid in cams:
            fc.add_stream(sid, "cam")
        out = [
            r
            for results in fc.run(
                {sid: cam.frame_at(t) for sid, cam in cams.items()}
                for t in range(10)
            )
            for r in results
        ]
        return pipe, server, fc, out

    pipe_m, server_m, fc_m, got = _serve(mesh)
    _, _, fc_p, ref = _serve(None)
    assert len(got) == len(ref) == 30
    for a, b in zip(got, ref):
        assert (a.stream_id, a.frame_idx) == (b.stream_id, b.frame_idx)
        assert a.kept_windows == b.kept_windows
        np.testing.assert_array_equal(a.counts, b.counts)
        np.testing.assert_array_equal(a.block_mask, b.block_mask)
    # arbitration solved identically on both sides
    for sid in cams:
        assert fc_m._members[sid].allocation == pytest.approx(
            fc_p._members[sid].allocation
        )
    # the compiled handles really shard over every (virtual) device...
    handles = list(pipe_m._handles.values())
    assert handles and all(h.data_parallelism == ndev for h in handles)
    # ...while each stream's gate state stays readable on the host
    for session in server_m.sessions.values():
        assert isinstance(session._prev, np.ndarray)
    assert_reconciled(pipe_m, server_m)


def test_data_parallelism_property_unsharded():
    pipe, server, fc = _fleet(FleetConfig(budget=0.6, floor=0.1))
    fc.add_stream("s0", "cam")
    cam = _busy(seed=11)
    list(fc.serve("s0", (cam.frame_at(t) for t in range(2))))
    handles = list(pipe._handles.values())
    assert handles and all(h.data_parallelism == 1 for h in handles)
