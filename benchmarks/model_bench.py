"""End-to-end classifier benchmark: the whole model program (FPCA analog
frontend + digital CNN head) served as per-frame class logits.

Three serving modes of the same trained-architecture network
(`configs/fpca_cnn`-style head on a c_o=32 frontend):

* **batched dense**  — `CompiledModel.run` on a frame batch (ONE fused
  frontend+head jit per batch: the offline / high-throughput path);
* **streaming dense** — `StreamServer` with gating off (per-tick logits,
  every window executed);
* **streaming delta-gated** — the skip-aware head path: kept windows are
  patched into each stream's effective activation map, so every tick still
  yields class logits while skipped windows never execute.

Records classifier frames/sec for each mode, the masked-over-dense
streaming speedup (the acceptance number: streaming classification must
beat dense on the synthetic low-change scene), and the head's
FLOPs/latency/energy accounting (`analysis.model_streaming_report`) to
``BENCH_model.json`` at the repo root — diff against the batch-frontend
baseline with ``python -m benchmarks.perf_compare --model``.

Two model-zoo lanes ride along: **detection** (the zoo's ``fpca_detect``
arch streaming per-tick per-cell class scores + boxes through the same
skip-aware head path) and **events** (the delta gate's changed blocks as an
address-event stream, moving vs static scene — a zero-event static scene
records the ``None`` fps sentinel, never inf/nan, per the strict-JSON
writer contract).
"""

from __future__ import annotations

import time
from pathlib import Path

import jax
import numpy as np

from benchmarks._util import write_json
from benchmarks.common import Row, time_fn
from repro.core import analysis
from repro.core.curvefit import fit_bucket_model
from repro.core.mapping import FPCASpec, output_dims
from repro.data.pipeline import SyntheticMovingObject
from repro.fpca import DeltaGateConfig, DenseSpec, build_model, telemetry
from repro.fpca import compile as fpca_compile
from repro.configs.fpca_cnn import make_model_program
from repro.serving.fpca_pipeline import FPCAPipeline
from repro.serving.observe import fleet_report
from repro.serving.streaming import StreamServer

BENCH_JSON = Path(__file__).resolve().parents[1] / "BENCH_model.json"
TELEMETRY_JSONL = Path(__file__).resolve().parents[1] / "telemetry_model.jsonl"

# Same operating point as stream_bench: c_o = 32 puts real matmul-bank work
# behind every window, so the masked win measures compute, not dispatch.
H = 160
C_O = 32
N_FRAMES = 48
N_STREAMS = 2
BATCH = 16
GATE = DeltaGateConfig(threshold=0.02, hysteresis=1, keyframe_interval=24)


def _serve(
    pipe: FPCAPipeline, cams: dict, gating: bool, config: str = "cls"
) -> tuple[float, StreamServer]:
    server = StreamServer(pipe, GATE, depth=2, gating=gating)
    for name in cams:
        server.add_stream(name, config)
    ticks = (
        {name: cam.frame_at(t) for name, cam in cams.items()}
        for t in range(N_FRAMES)
    )
    t0 = time.perf_counter()
    for _ in server.run(ticks):
        pass
    return time.perf_counter() - t0, server


def run() -> list[Row]:
    bucket_model = fit_bucket_model(n_pixels=75)
    spec = FPCASpec(image_h=H, image_w=H, out_channels=C_O, kernel=5, stride=5)
    model = make_model_program(
        spec, head=(DenseSpec(64, activation="relu"), DenseSpec(2))
    )
    rng = np.random.default_rng(0)
    kernel = (rng.normal(size=model.frontend.kernel_shape) * 0.2).astype(np.float32)
    head_params = model.init_head(jax.random.PRNGKey(0))

    # batched dense classification through the fused handle
    m = fpca_compile(model, backend="basis", weights=kernel,
                     head_params=head_params, model=bucket_model)
    frames = rng.uniform(0, 1, (BATCH, H, H, 3)).astype(np.float32)
    us_batched = time_fn(lambda: m.run(frames), iters=5)
    fps_batched = BATCH / (us_batched * 1e-6)

    # streaming: dense vs delta-gated, per-tick logits either way
    pipe = FPCAPipeline(bucket_model, backend="basis")
    pipe.register("cls", model, kernel, head_params=head_params)
    cams = {
        f"cam{i}": SyntheticMovingObject((H, H), seed=i + 1)
        for i in range(N_STREAMS)
    }
    _serve(pipe, cams, gating=True)     # warm-up (compiles)
    _serve(pipe, cams, gating=False)
    pipe.reset_bucket_state()
    t_gated, server = _serve(pipe, cams, gating=True)
    t_dense, _ = _serve(pipe, cams, gating=False)

    # scan-segment lane: per-tick logits with the gate AND the skip-aware
    # head inside ONE lax.scan launch per stream (K = N_FRAMES); the probe
    # pass compiles the masked-dense scan and sizes the row bucket for the
    # timed pass (servo-at-segment-boundary semantics)
    frame_stacks = {
        name: np.stack([cam.frame_at(t) for t in range(N_FRAMES)])
        for name, cam in cams.items()
    }

    def _serve_scan(m_bucket=None, config="cls"):
        srv = StreamServer(pipe, GATE, depth=2, gating=True)
        for name in frame_stacks:
            srv.add_stream(name, config)
        t0 = time.perf_counter()
        for name, stack in frame_stacks.items():
            srv.run_segment(name, stack, m_bucket=m_bucket)
        return time.perf_counter() - t0, srv

    _, probe = _serve_scan()
    scan_bucket = max(
        probe.sessions[n]._segment_state.suggested_bucket or 1
        for n in frame_stacks
    )
    _serve_scan(m_bucket=scan_bucket)    # warm-up
    t_scan, _ = _serve_scan(m_bucket=scan_bucket)
    fps_scan = N_FRAMES * N_STREAMS / t_scan

    # telemetry lane: same scan workload with a live session (uploaded by
    # the CI bench-smoke job next to the stream bench's JSONL)
    telemetry.enable(
        TELEMETRY_JSONL, run_labels={"bench": "model_scan_segment"},
    )
    t_scan_tel, tel_server = _serve_scan(m_bucket=scan_bucket)
    fleet = fleet_report(tel_server)
    n_events = telemetry.session().events_written
    telemetry.disable()

    # detection lane: the zoo's fpca_detect arch on the SAME frontend spec
    # and kernel, streamed delta-gated with an event tap attached — per-tick
    # per-coarse-cell class scores + box regression through the skip-aware
    # patched-head path
    det_model = build_model(
        {"arch": "fpca_detect", "spec": spec, "n_classes": 2, "width": 8}
    )
    det_params = det_model.init_head(jax.random.PRNGKey(1))
    pipe.register("det", det_model, kernel, head_params=det_params)
    det_frames = [cams["cam0"].frame_at(t) for t in range(N_FRAMES)]

    def _serve_det(stack):
        srv = StreamServer(pipe, GATE, depth=2, gating=True)
        srv.add_stream("cam0", "det", events=True)
        t0 = time.perf_counter()
        for r in srv.serve("cam0", stack):
            assert r.detections is not None and r.events is not None
        return time.perf_counter() - t0, srv

    _serve_det(det_frames)               # warm-up (compiles)
    t_det, det_srv = _serve_det(det_frames)
    fps_det = N_FRAMES / t_det
    ev = det_srv.event_taps["cam0"].stats
    # event lanes: moving scene vs an all-static scene.  A zero-event lane
    # records the None fps sentinel — the strict-JSON writer (allow_nan
    # off) forbids inf/nan, and 0/t would misread as "measured zero rate"
    events_per_s = ev.events / t_det if ev.events else None
    t_static, static_srv = _serve_det([det_frames[0]] * 8)
    sev = static_srv.event_taps["cam0"].stats
    static_events_per_s = sev.events / t_static if sev.events else None

    n_served = N_FRAMES * N_STREAMS
    fps_gated = n_served / t_gated
    fps_dense = n_served / t_dense
    s = server.stats
    kept_frac = s.windows_kept / s.windows_total
    h_o, w_o = output_dims(spec)
    rep = analysis.model_streaming_report(
        model, list(server.sessions["cam0"].block_masks)
    )

    # quantised int8 lanes: the SAME classifier compiled precision="int8" —
    # LUT-collapsed bucket transfer in the basis frontend + int8 head with
    # exact int32 accumulation, activation scales calibrated on the batched
    # frames' counts.  Parity vs f32 is bounded, not bit-exact (pinned in
    # tests/test_quant.py); the lanes here record the measured numbers.
    from repro.models.quant import logit_parity, quantize_head_params

    model_i8 = model.replace(precision="int8")
    fe_cal = fpca_compile(model.frontend, backend="basis", weights=kernel,
                          model=bucket_model)
    head_params_i8 = quantize_head_params(
        model_i8, head_params, sample_counts=fe_cal.run(frames)
    )
    m_i8 = fpca_compile(model_i8, backend="basis", weights=kernel,
                        head_params=head_params_i8, model=bucket_model)
    us_batched_i8 = time_fn(lambda: m_i8.run(frames), iters=5)
    fps_batched_i8 = BATCH / (us_batched_i8 * 1e-6)
    parity = logit_parity(np.asarray(m.run(frames)), np.asarray(m_i8.run(frames)))

    pipe.register("cls8", model_i8, kernel, head_params=head_params_i8)
    _serve(pipe, cams, gating=True, config="cls8")      # warm-up (compiles)
    pipe.reset_bucket_state()
    t_gated_i8, _ = _serve(pipe, cams, gating=True, config="cls8")
    fps_gated_i8 = n_served / t_gated_i8

    _, probe_i8 = _serve_scan(config="cls8")
    scan_bucket_i8 = max(
        probe_i8.sessions[n]._segment_state.suggested_bucket or 1
        for n in frame_stacks
    )
    _serve_scan(m_bucket=scan_bucket_i8, config="cls8")  # warm-up
    t_scan_i8, _ = _serve_scan(m_bucket=scan_bucket_i8, config="cls8")
    fps_scan_i8 = n_served / t_scan_i8
    head_model = analysis.head_report(model)

    record = {
        "workload": {
            "streams": N_STREAMS, "frames_per_stream": N_FRAMES,
            "batch": BATCH, "image": [H, H, 3],
            "spec": {"kernel": spec.kernel, "stride": spec.stride,
                     "out_channels": spec.out_channels, "binning": spec.binning},
            "windows_per_frame": h_o * w_o,
            "head": [str(layer) for layer in model.head],
            "n_classes": model.n_classes,
            "gate": {"threshold": GATE.threshold, "hysteresis": GATE.hysteresis,
                     "keyframe_interval": GATE.keyframe_interval},
        },
        "backend": "basis (XLA lowering of the Pallas kernel math)",
        "batched_dense": {"us_per_batch": us_batched, "frames_per_s": fps_batched},
        "stream_dense": {"s_total": t_dense, "frames_per_s": fps_dense},
        "stream_masked": {"s_total": t_gated, "frames_per_s": fps_gated},
        "scan_segment": {
            "s_total": t_scan,
            "frames_per_s": fps_scan,
            "segment_length": N_FRAMES,
            "m_bucket": scan_bucket,
            "speedup_vs_per_tick_masked": fps_scan / fps_gated,
        },
        "speedup_masked_vs_dense": fps_gated / fps_dense,
        "kept_window_frac": kept_frac,
        "head": {
            "macs_per_frame": rep["head_macs_per_frame"],
            "flops_per_frame": rep["head_flops_per_frame"],
            "params": rep["head_params"],
            "t_head_per_frame": rep["t_head_total"] / rep["frames"],
            "e_head_per_frame": rep["e_head_total"] / rep["frames"],
        },
        "sensor_model": {
            "energy_vs_dense": rep["energy_vs_dense"],
            "model_energy_vs_dense": rep["model_energy_vs_dense"],
            "model_latency_vs_dense": rep["model_latency_vs_dense"],
            "model_fps_effective": rep["model_fps_effective"],
        },
        "detection": {
            "arch": "fpca_detect",
            "s_total": t_det,
            "frames_per_s": fps_det,
            "grid": [h_o, w_o],
            "n_classes": det_model.detect_classes,
            "head_macs_per_frame": analysis.head_flops(det_model)["macs"],
        },
        "events": {
            "moving_scene": {
                "ticks": ev.ticks, "events": ev.events,
                "events_pos": ev.events_pos, "events_neg": ev.events_neg,
                "events_per_s": events_per_s,
            },
            "static_scene": {
                "ticks": sev.ticks, "events": sev.events,
                "events_per_s": static_events_per_s,
            },
        },
        "telemetry": {
            "jsonl": TELEMETRY_JSONL.name,
            "events": n_events,
            "s_total_enabled": t_scan_tel,
            "enabled_overhead_frac": t_scan_tel / t_scan - 1.0,
            "fleet_report": fleet,
        },
        "quantised_int8": {
            "batched": {
                "us_per_batch": us_batched_i8,
                "frames_per_s": fps_batched_i8,
                "speedup_vs_f32": fps_batched_i8 / fps_batched,
            },
            "stream_masked": {
                "s_total": t_gated_i8,
                "frames_per_s": fps_gated_i8,
                "speedup_vs_f32": fps_gated_i8 / fps_gated,
            },
            "scan_segment": {
                "s_total": t_scan_i8,
                "frames_per_s": fps_scan_i8,
                "m_bucket": scan_bucket_i8,
                "speedup_vs_f32": fps_scan_i8 / fps_scan,
            },
            "parity": {
                "max_abs_divergence": float(parity["max_abs_divergence"]),
                "top1_agreement": float(parity["top1_agreement"]),
            },
            "head_model": {
                "t_head_f32": head_model["t_head_f32"],
                "t_head_int8": head_model["t_head_int8"],
                "e_head_f32": head_model["e_head_f32"],
                "e_head_int8": head_model["e_head_int8"],
                "int8_speedup": head_model["int8_speedup"],
                "int8_energy_ratio": head_model["int8_energy_ratio"],
            },
        },
    }
    write_json(BENCH_JSON, record)

    return [
        ("model_e2e_batched", us_batched,
         f"B={BATCH} {H}x{H} -> {fps_batched:.0f} frames/s fused "
         f"frontend+head (json: {BENCH_JSON.name})"),
        ("model_stream_delta_gated", t_gated / n_served * 1e6,
         f"{N_STREAMS}x{N_FRAMES} frames -> {fps_gated:.0f} frames/s "
         f"kept={kept_frac:.1%} "
         f"speedup_vs_dense={record['speedup_masked_vs_dense']:.2f}x "
         f"(logits every tick)"),
        ("model_stream_dense", t_dense / n_served * 1e6,
         f"{fps_dense:.0f} frames/s"),
        ("model_scan_segment", t_scan / n_served * 1e6,
         f"K={N_FRAMES} lax.scan segments -> {fps_scan:.0f} frames/s "
         f"(bucket {scan_bucket}, "
         f"{fps_scan / fps_gated:.2f}x per-tick masked, logits every tick)"),
        ("model_head_cost", 0.0,
         f"{rep['head_macs_per_frame']/1e6:.2f} MMAC/frame "
         f"({rep['head_params']/1e3:.0f}k params)"),
        ("model_detect_stream", t_det / N_FRAMES * 1e6,
         f"fpca_detect {h_o}x{w_o} grid -> {fps_det:.0f} frames/s "
         f"(scores+boxes every tick)"),
        ("model_event_stream", 0.0,
         f"{ev.events} events/{ev.ticks} ticks "
         f"(+{ev.events_pos}/-{ev.events_neg}); static scene "
         f"{sev.events} events"),
        ("model_e2e_batched_int8", us_batched_i8,
         f"B={BATCH} int8 -> {fps_batched_i8:.0f} frames/s "
         f"({fps_batched_i8 / fps_batched:.2f}x f32, max |dlogit| "
         f"{parity['max_abs_divergence']:.3f}, top-1 agree "
         f"{parity['top1_agreement']:.2f})"),
        ("model_stream_masked_int8", t_gated_i8 / n_served * 1e6,
         f"{fps_gated_i8:.0f} frames/s "
         f"({fps_gated_i8 / fps_gated:.2f}x f32 masked)"),
        ("model_scan_segment_int8", t_scan_i8 / n_served * 1e6,
         f"{fps_scan_i8:.0f} frames/s "
         f"({fps_scan_i8 / fps_scan:.2f}x f32 scan, bucket {scan_bucket_i8})"),
    ]
