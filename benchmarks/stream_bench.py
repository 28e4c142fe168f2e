"""Streaming frontend benchmark: delta-gated vs dense serving throughput,
plus the adaptive control plane on top.

A synthetic moving-object stream (small frame-to-frame change fraction —
the paper's continuous-vision regime) runs through the double-buffered
:class:`~repro.serving.streaming.StreamServer` three ways: dense, delta-gated
with the stateless (flapping) row bucket, and delta-gated with sticky bucket
hysteresis (``bucket_patience``).  Records frames/sec, the kept/skipped
window fractions, the masked-over-dense speedup, the executable bucket
switch counts (sticky vs flap), and a keep-fraction servo convergence trace
(:class:`~repro.serving.control.GateController` against a 0.15 budget) to
``BENCH_stream.json`` at the repo root — compare against the PR-1 batch
baseline with ``python -m benchmarks.perf_compare --stream``.
"""

from __future__ import annotations

import time
from pathlib import Path

import numpy as np

from benchmarks._util import write_json
from benchmarks.common import Row
from repro.core.curvefit import fit_bucket_model
from repro.core.mapping import FPCASpec, output_dims
from repro.data.pipeline import SyntheticMovingObject
from repro.fpca import DeltaGateConfig, GateControllerConfig, telemetry
from repro.serving.fpca_pipeline import FPCAPipeline
from repro.serving.observe import fleet_report
from repro.serving.streaming import StreamServer

BENCH_JSON = Path(__file__).resolve().parents[1] / "BENCH_stream.json"
TELEMETRY_JSONL = Path(__file__).resolve().parents[1] / "telemetry_stream.jsonl"

# c_o = 32 puts real matmul-bank work behind every window (the Fig. 9
# "savings erased at c_o=32" operating point) — small channel counts are
# dispatch-overhead-bound on CPU and would understate the masked win.
H = 160
C_O = 32
N_FRAMES = 48
N_STREAMS = 2
GATE = DeltaGateConfig(threshold=0.02, hysteresis=1, keyframe_interval=24)
BUCKET_PATIENCE = 4
# servo scene: blob big enough that the 0.15 budget is inside the gate's
# reachable kept-fraction range at this resolution
CONTROLLER = GateControllerConfig(target=0.15)
# energy servo: same loop closed on analysis.frontend_energy's
# executed-energy fraction (cycle-granular RS/SW gating + IO term) instead
# of the raw kept-window fraction — the budget a battery deployment sets
CONTROLLER_ENERGY = GateControllerConfig(target=0.15, metric="energy")
SERVO_RADIUS = 18.0


def _serve(
    pipe: FPCAPipeline,
    cams: dict,
    gating: bool,
    controller: GateControllerConfig | None = None,
) -> tuple[float, StreamServer]:
    server = StreamServer(pipe, GATE, depth=2, gating=gating, controller=controller)
    for name in cams:
        server.add_stream(name, "cam")
    ticks = (
        {name: cam.frame_at(t) for name, cam in cams.items()}
        for t in range(N_FRAMES)
    )
    t0 = time.perf_counter()
    for _ in server.run(ticks):
        pass
    return time.perf_counter() - t0, server


def _serve_scan(
    pipe: FPCAPipeline,
    frame_stacks: dict[str, np.ndarray],
    m_bucket: int | None = None,
) -> tuple[float, StreamServer]:
    """All N_FRAMES ticks of every stream served as ONE compiled
    ``lax.scan`` segment per stream (K = N_FRAMES, gate inside the carry)."""
    server = StreamServer(pipe, GATE, depth=2, gating=True)
    for name in frame_stacks:
        server.add_stream(name, "cam")
    t0 = time.perf_counter()
    for name, stack in frame_stacks.items():
        server.run_segment(name, stack, m_bucket=m_bucket)
    return time.perf_counter() - t0, server


def run() -> list[Row]:
    model = fit_bucket_model(n_pixels=75)
    spec = FPCASpec(image_h=H, image_w=H, out_channels=C_O, kernel=5, stride=5)
    rng = np.random.default_rng(0)
    kernel = (rng.normal(size=(C_O, 5, 5, 3)) * 0.2).astype(np.float32)

    def make_pipe(patience: int) -> FPCAPipeline:
        pipe = FPCAPipeline(model, backend="basis", bucket_patience=patience)
        pipe.register("cam", spec, kernel)
        return pipe

    pipe_flap = make_pipe(1)            # stateless buckets: the PR-2 behaviour
    pipe_sticky = make_pipe(BUCKET_PATIENCE)
    cams = {
        f"cam{i}": SyntheticMovingObject((H, H), seed=i + 1)
        for i in range(N_STREAMS)
    }

    # warm both pipelines (compiles), then time; bucket-switch counts are
    # measured over the timed serve only (stats delta)
    _serve(pipe_flap, cams, gating=True)
    _serve(pipe_flap, cams, gating=False)
    _serve(pipe_sticky, cams, gating=True)

    # reset sticky state so each timed pass replays exactly the bucket
    # sequence its warm-up compiled (and switch counts are self-contained)
    pipe_flap.reset_bucket_state()
    sw0 = pipe_flap.stats.bucket_switches
    t_gated, server = _serve(pipe_flap, cams, gating=True)
    switches_flap = pipe_flap.stats.bucket_switches - sw0
    t_dense, _ = _serve(pipe_flap, cams, gating=False)
    pipe_sticky.reset_bucket_state()
    sw0 = pipe_sticky.stats.bucket_switches
    df0 = pipe_sticky.stats.bucket_shrinks_deferred
    t_sticky, _ = _serve(pipe_sticky, cams, gating=True)
    switches_sticky = pipe_sticky.stats.bucket_switches - sw0
    shrinks_deferred = pipe_sticky.stats.bucket_shrinks_deferred - df0

    # scan-segment lane: the same gated workload, but every stream's
    # N_FRAMES ticks come from ONE device-compiled lax.scan launch.  The
    # probe pass realises each scene's kept counts (and compiles the
    # masked-dense scan); the timed pass serves the pow2 row bucket those
    # counts suggest — the servo-picks-the-bucket-between-segments contract.
    frame_stacks = {
        name: np.stack([cam.frame_at(t) for t in range(N_FRAMES)])
        for name, cam in cams.items()
    }
    _, probe = _serve_scan(pipe_flap, frame_stacks)
    scan_bucket = max(
        probe.sessions[n]._segment_state.suggested_bucket or 1
        for n in frame_stacks
    )
    _serve_scan(pipe_flap, frame_stacks, m_bucket=scan_bucket)   # warm-up
    t_scan, scan_server = _serve_scan(
        pipe_flap, frame_stacks, m_bucket=scan_bucket
    )
    fps_scan = N_FRAMES * N_STREAMS / t_scan

    # telemetry lane: the SAME scan workload with a live session (JSONL
    # spans) — what the CI bench-smoke job uploads —
    # plus the zero-overhead-when-disabled guard for the hot tick path
    telemetry.enable(
        TELEMETRY_JSONL, run_labels={"bench": "stream_scan_segment"},
    )
    t_scan_tel, tel_server = _serve_scan(
        pipe_flap, frame_stacks, m_bucket=scan_bucket
    )
    fleet = fleet_report(tel_server)
    n_events = telemetry.session().events_written
    telemetry.disable()

    # disabled-mode overhead: measured per-crossing cost of the disabled
    # hooks (span() null return + the instrumented-launch is-None check)
    # times the hook crossings the timed scan lane actually makes, as a
    # fraction of its wall time.  The guard (<= 2%) is asserted over the
    # committed artifact by tests/test_bench_schema.py.
    n_iter = 200_000
    fields = {"stream": "cam0"}
    t0 = time.perf_counter()
    for _ in range(n_iter):
        with telemetry.span("serve_segment", fields):
            pass
    hook_cost_s = (time.perf_counter() - t0) / n_iter
    # per segment: serve_segment + run_segment spans, the run_segment
    # dispatch enabled() check, and one instrumented launch — x streams
    hook_crossings = 4 * N_STREAMS
    disabled_overhead_frac = hook_cost_s * hook_crossings / t_scan

    # keep-fraction servo convergence (one camera, servo-friendly scene)
    servo_cams = {"cam0": SyntheticMovingObject((H, H), seed=1, radius=SERVO_RADIUS)}
    _, servo_server = _serve(pipe_sticky, servo_cams, gating=True, controller=CONTROLLER)
    ctl = servo_server.sessions["cam0"].controller
    assert ctl is not None

    # energy-budget servo on the same scene: the controller observes the
    # sensor-model executed-energy fraction per tick instead of the kept
    # fraction (ROADMAP open item: servo the "energy" metric end to end)
    _, servo_e_server = _serve(
        pipe_sticky, servo_cams, gating=True, controller=CONTROLLER_ENERGY
    )
    ctl_e = servo_e_server.sessions["cam0"].controller
    assert ctl_e is not None

    frames = N_FRAMES * N_STREAMS
    fps_gated = frames / t_gated
    fps_dense = frames / t_dense
    fps_sticky = frames / t_sticky
    s = server.stats
    kept_frac = s.windows_kept / s.windows_total
    h_o, w_o = output_dims(spec)
    rep = server.sessions["cam0"].energy_report()

    record = {
        "workload": {
            "streams": N_STREAMS, "frames_per_stream": N_FRAMES,
            "image": [H, H, 3],
            "spec": {"kernel": spec.kernel, "stride": spec.stride,
                     "out_channels": spec.out_channels, "binning": spec.binning},
            "windows_per_frame": h_o * w_o,
            "gate": {"threshold": GATE.threshold, "hysteresis": GATE.hysteresis,
                     "keyframe_interval": GATE.keyframe_interval},
        },
        "backend": "basis (XLA lowering of the Pallas kernel math)",
        "masked": {"s_total": t_gated, "frames_per_s": fps_gated},
        "dense": {"s_total": t_dense, "frames_per_s": fps_dense},
        "scan_segment": {
            "s_total": t_scan,
            "frames_per_s": fps_scan,
            "segment_length": N_FRAMES,
            "m_bucket": scan_bucket,
            "kept_window_frac": (
                scan_server.stats.windows_kept
                / max(scan_server.stats.windows_total, 1)
            ),
            "launches_skipped": scan_server.stats.launches_skipped,
            "speedup_vs_per_tick_masked": None,  # filled below
        },
        "speedup_masked_vs_dense": fps_gated / fps_dense,
        "kept_window_frac": kept_frac,
        "skipped_window_frac": 1.0 - kept_frac,
        "sticky_buckets": {
            "patience": BUCKET_PATIENCE,
            "switches_flap": switches_flap,
            "switches_sticky": switches_sticky,
            "shrinks_deferred": shrinks_deferred,
            "s_total": t_sticky,
            "frames_per_s": fps_sticky,
        },
        "controller": {
            "target_kept_frac": CONTROLLER.target,
            "metric": CONTROLLER.metric,
            "servo_radius": SERVO_RADIUS,
            "converged_tick": ctl.converged_tick(rel_tol=0.2),
            "ticks": len(ctl.history),
            "final_threshold": ctl.threshold,
            "final_ema": ctl.ema,
            "history": [
                {"tick": h["tick"], "threshold": round(h["threshold"], 6),
                 "ema": None if h["ema"] is None else round(h["ema"], 4)}
                for h in ctl.history
            ],
        },
        "controller_energy": {
            "target_energy_frac": CONTROLLER_ENERGY.target,
            "metric": CONTROLLER_ENERGY.metric,
            "servo_radius": SERVO_RADIUS,
            "converged_tick": ctl_e.converged_tick(rel_tol=0.2),
            "ticks": len(ctl_e.history),
            "final_threshold": ctl_e.threshold,
            "final_ema": ctl_e.ema,
            "history": [
                {"tick": h["tick"], "threshold": round(h["threshold"], 6),
                 "ema": None if h["ema"] is None else round(h["ema"], 4)}
                for h in ctl_e.history
            ],
        },
        "sensor_model": {
            "energy_vs_dense": rep["energy_vs_dense"],
            "latency_vs_dense": rep["latency_vs_dense"],
            "fps_effective": rep["fps_effective"],
        },
        "telemetry": {
            "jsonl": TELEMETRY_JSONL.name,
            "events": n_events,
            "s_total_enabled": t_scan_tel,
            "enabled_overhead_frac": t_scan_tel / t_scan - 1.0,
            "disabled_hook_cost_s": hook_cost_s,
            "hook_crossings": hook_crossings,
            "disabled_overhead_frac": disabled_overhead_frac,
            "fleet_report": fleet,
        },
    }
    record["scan_segment"]["speedup_vs_per_tick_masked"] = fps_scan / fps_gated
    write_json(BENCH_JSON, record)

    us_gated = t_gated / frames * 1e6
    us_dense = t_dense / frames * 1e6
    return [
        ("stream_scan_segment", t_scan / frames * 1e6,
         f"K={N_FRAMES} lax.scan segments -> {fps_scan:.0f} frames/s "
         f"(bucket {scan_bucket}, "
         f"{fps_scan / fps_gated:.2f}x per-tick masked)"),
        ("stream_delta_gated", us_gated,
         f"{N_STREAMS}x{N_FRAMES} frames {H}x{H} -> {fps_gated:.0f} frames/s "
         f"kept={kept_frac:.1%} speedup_vs_dense="
         f"{record['speedup_masked_vs_dense']:.2f}x (json: {BENCH_JSON.name})"),
        ("stream_dense", us_dense, f"{fps_dense:.0f} frames/s"),
        ("stream_sticky_buckets", t_sticky / frames * 1e6,
         f"{fps_sticky:.0f} frames/s  bucket switches {switches_sticky} "
         f"(vs {switches_flap} stateless)"),
        ("stream_servo", 0.0,
         f"kept->{CONTROLLER.target:.2f} budget converged at tick "
         f"{record['controller']['converged_tick']} "
         f"(thr {ctl.threshold:.4f}, ema {ctl.ema:.3f})"),
        ("stream_servo_energy", 0.0,
         f"energy->{CONTROLLER_ENERGY.target:.2f} budget converged at tick "
         f"{record['controller_energy']['converged_tick']} "
         f"(thr {ctl_e.threshold:.4f}, ema {ctl_e.ema:.3f})"),
        ("stream_telemetry", 0.0,
         f"disabled hooks {disabled_overhead_frac:.2e} of scan lane, "
         f"{n_events} JSONL events when enabled "
         f"(jsonl: {TELEMETRY_JSONL.name})"),
    ]
