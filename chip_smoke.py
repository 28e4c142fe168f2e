#!/usr/bin/env python3
"""Smoke run of the FPCA serving path on a TPU, through the public API.

    python chip_smoke.py               # one chip: every serving phase
    python chip_smoke.py --chips 4     # four chips: the sharded path only

One chip: the paper's ``fpca_cnn`` network at its configured width
(120x120x3 frames, 8-channel 5x5 stride-5 frontend, Dense 64 -> 2 head)
with weights, head parameters and frames drawn from ``--seed``.  The backend
is auto-selected (the Pallas kernel on a TPU).  Phases: a batched run in
f32 and in int8, per-tick streaming, chained device-compiled segments, and
``FPCAPipeline`` / ``StreamServer`` serving (per tick, in segments, and
interleaved on one stream).  The yardstick is the ``reference`` backend on
the same chip at ``highest`` matmul precision: frontend counts within
1 ADC count, the same top-1 class, and per-tick and segment streaming in
agreement.  Every executable that carries the frontend must hold a
compiled Mosaic kernel (``tpu_custom_call``), so interpret mode cannot pass.

``--chips 4``: the same network served by ``FPCAPipeline`` on a 4-way data
mesh (each chip runs the kernel on its own batch shard), a batch and a
multi-camera ``StreamServer``, compared with an unsharded pipeline in the
same process.

Readings printed on the way (wall and compile seconds, disagreements,
device peak memory) are smoke readings, not benchmark numbers.  The last
line of stdout is ``{"ok": true, "device": {...}}``; any failed check exits
non-zero.  Off a TPU the script exits non-zero before doing anything.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
KERNEL_SITES = ("frontend", "model", "segment")


def log(msg: str) -> None:
    print(msg, flush=True)


def expect(ok, msg: str) -> None:
    """A check that also holds under ``python -O``."""
    if not ok:
        raise AssertionError(msg)


class KernelAudit:
    """Records every executable that carries the frontend (with abstract
    copies of its first call's arguments) so the compiled programs can be
    checked for the Mosaic kernel after the phases ran."""

    def __init__(self) -> None:
        self.seen: dict[tuple, tuple] = {}

    def install(self) -> None:
        from repro.fpca.cache import ExecutableCache

        get = ExecutableCache.get
        seen = self.seen

        def spy(cache, key, build):
            fn = get(cache, key, build)
            if getattr(fn, "_fpca_site", None) not in KERNEL_SITES:
                return fn

            def call(*args):
                if key not in seen:
                    seen[key] = (fn.__wrapped__, _abstract(args))
                return fn(*args)

            return call

        ExecutableCache.get = spy

    def check(self, *, backend: str, no_gather: bool = False) -> int:
        n = 0
        for key, (jitted, args) in self.seen.items():
            if backend not in key:
                continue
            hlo = jitted.lower(*args).compile().as_text()
            expect("tpu_custom_call" in hlo, f"no Mosaic kernel in {key[-6:]}")
            if no_gather:
                expect("all-gather" not in hlo, f"batch gathered in {key[-6:]}")
            n += 1
        expect(n > 0, f"no {backend} frontend executable was recorded")
        return n


def _abstract(tree):
    import jax
    import numpy as np

    def leaf(a):
        if isinstance(a, jax.Array) and a.committed:
            return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=a.sharding)
        if isinstance(a, (jax.Array, np.ndarray)):
            return jax.ShapeDtypeStruct(a.shape, a.dtype)
        return a

    return jax.tree.map(leaf, tree)


class Phases:
    """Wall-clock bookkeeping: every phase ends in ``block_until_ready``."""

    def __init__(self) -> None:
        self.seconds: dict[str, float] = {}

    def run(self, name: str, fn):
        import jax

        t0 = time.perf_counter()
        out = jax.block_until_ready(fn())
        self.seconds[name] = time.perf_counter() - t0
        log(f"phase {name}: {self.seconds[name]:.3f} s")
        return out


def _finite(x, shape, what: str):
    import numpy as np

    x = np.asarray(x)
    expect(x.shape == tuple(shape), f"{what}: shape {x.shape} != {tuple(shape)}")
    expect(np.isfinite(x).all(), f"{what}: non-finite values")
    return x


def _network(cfg, seed: int):
    """Model program, fitted sensor model, weights and head params."""
    import jax
    import numpy as np

    from repro import fpca
    from repro.core.curvefit import fit_bucket_model

    model = fpca.build_model(cfg)
    spec = model.spec
    rng = np.random.default_rng(seed)
    weights = {
        "weights": (rng.normal(size=model.frontend.kernel_shape) * 0.2).astype(
            np.float32
        ),
        "bn_offset": rng.integers(0, 16, spec.out_channels).astype(np.float32),
    }
    head = model.init_head(jax.random.PRNGKey(seed))
    bucket = fit_bucket_model(model.frontend.circuit, n_pixels=spec.n_active_pixels)
    return model, bucket, weights, head


def _images(spec, seed: int, batch: int):
    from repro.data.pipeline import SyntheticVWW

    vww = SyntheticVWW((spec.image_h, spec.image_w), seed=seed)
    return vww.batch_at(0, batch)["images"]


def _camera(spec, seed: int):
    from repro.data.pipeline import SyntheticMovingObject

    return SyntheticMovingObject((spec.image_h, spec.image_w), seed=seed)


def _same_stream(a, b, what: str) -> float:
    """Per-tick results of two serving paths: identical counts and gate
    decisions, the same class, and logits equal up to f32 rounding (the
    chip rounds the head's sums differently in differently fused
    programs); returns the largest logit difference."""
    import numpy as np

    expect(len(a) == len(b), f"{what}: {len(a)} vs {len(b)} ticks")
    worst = 0.0
    for x, y in zip(a, b):
        expect(x.kept_windows == y.kept_windows, f"{what}: kept windows differ")
        np.testing.assert_array_equal(
            np.asarray(x.counts), np.asarray(y.counts), err_msg=what
        )
        if x.logits is not None:
            expect(np.argmax(x.logits) == np.argmax(y.logits), f"{what}: top-1")
            np.testing.assert_allclose(
                x.logits, y.logits, rtol=1e-4, atol=1e-4, err_msg=what
            )
            worst = max(worst, float(np.abs(x.logits - y.logits).max()))
    return worst


def _segment_results(seg):
    """Per-tick view of a SegmentResult, shaped like StreamFrameResult."""
    import types

    import numpy as np

    counts, logits = np.asarray(seg.counts), np.asarray(seg.logits)
    return [
        types.SimpleNamespace(
            counts=counts[t], logits=logits[t],
            kept_windows=int(seg.kept_windows[t]),
        )
        for t in range(seg.ticks)
    ]


def single_chip(
    cfg,
    *,
    seed: int = 0,
    batch: int = 32,
    ticks: int = 48,
    segment: int = 16,
    backend: str | None = None,
    audit: KernelAudit | None = None,
) -> dict:
    """Every serving phase of one network on one device; returns readings."""
    import jax
    import numpy as np

    from repro import fpca
    from repro.serving.fpca_pipeline import FPCAPipeline, FrontendRequest
    from repro.serving.streaming import StreamServer

    phases = Phases()
    model, bucket, w, head = _network(cfg, seed)
    spec = model.spec
    h_o, w_o, c_o = model.frontend.out_shape
    images = _images(spec, seed, batch)
    cam = _camera(spec, seed)
    stream = np.stack([cam.frame_at(t) for t in range(ticks)])
    gate = fpca.DeltaGateConfig(threshold=0.02, hysteresis=1, keyframe_interval=16)
    levels = model.frontend.adc.levels
    readings: dict = {}

    m = fpca.compile(model, backend=backend, model=bucket, head_params=head, **w)
    fe = fpca.compile(model.frontend, backend=backend, model=bucket, **w)
    readings["backend"] = m.backend.name
    log(f"backend: {m.backend.name}")

    t0 = time.perf_counter()
    jax.block_until_ready((m.run(images), fe.run(images)))
    readings["first_run_seconds"] = time.perf_counter() - t0
    logits = _finite(phases.run("run_f32", lambda: m.run(images)),
                     (batch, model.n_classes), "f32 logits")
    counts = _finite(phases.run("frontend", lambda: fe.run(images)),
                     (batch, h_o, w_o, c_o), "frontend counts")
    expect(counts.min() >= 0 and counts.max() <= levels - 1,
           "frontend counts outside the ADC range")
    np.testing.assert_array_equal(counts, np.round(counts))

    with jax.default_matmul_precision("highest"):
        ref_m = fpca.compile(model, backend="reference", model=bucket,
                             head_params=head, **w)
        ref_fe = fpca.compile(model.frontend, backend="reference", model=bucket,
                              **w)
        ref_counts, ref_logits = phases.run(
            "reference", lambda: (ref_fe.run(images), ref_m.run(images))
        )
    diff = np.abs(counts - np.asarray(ref_counts))
    readings["count_max_diff"] = float(diff.max())
    readings["count_diff_fraction"] = float((diff > 0).mean())
    readings["logit_max_diff"] = float(np.abs(logits - np.asarray(ref_logits)).max())
    log(f"vs reference: max count diff {diff.max():.0f}, counts differing "
        f"{(diff > 0).mean():.6f}, max |dlogit| {readings['logit_max_diff']:.6g}")
    expect(diff.max() <= 1.0, f"counts differ by {diff.max()} from the reference")
    top1 = np.argmax(logits, -1) == np.argmax(np.asarray(ref_logits), -1)
    expect(top1.all(), f"top-1 disagrees on {int((~top1).sum())} of {batch} frames")

    model8 = model.replace(precision="int8")
    m8 = fpca.compile(model8, backend=backend, model=bucket,
                      head_params=fpca.quantize_head_params(model8, head), **w)
    logits8 = _finite(phases.run("run_int8", lambda: m8.run(images)),
                      (batch, model.n_classes), "int8 logits")
    par = fpca.logit_parity(logits, logits8)
    readings["int8_max_logit_diff"] = float(par["max_abs_divergence"])
    readings["int8_top1_agreement"] = float(par["top1_agreement"])
    log(f"int8 vs f32: max |dlogit| {par['max_abs_divergence']:.4g}, top-1 "
        f"agreement {par['top1_agreement']:.3f}")

    ticked = phases.run("stream", lambda: list(m.stream(stream, gate=gate)))
    expect(len(ticked) == ticks, f"stream served {len(ticked)} of {ticks} ticks")
    for r in ticked:
        _finite(r.counts, (h_o, w_o, c_o), "stream counts")
        _finite(r.logits, (model.n_classes,), "stream logits")

    def chained():
        out, state = [], None
        for i in range(0, ticks, segment):
            seg = m.run_segment(stream[i:i + segment], gate=gate, state=state)
            state = seg.state                 # the passed state was donated
            out += _segment_results(seg)
        return out

    segmented = phases.run("segments", chained)
    readings["stream_vs_segment_logit_diff"] = _same_stream(
        ticked, segmented, "per-tick vs segment stream"
    )
    log(f"per-tick vs segment: counts identical, max |dlogit| "
        f"{readings['stream_vs_segment_logit_diff']:.6g}")

    pipe = FPCAPipeline(bucket, backend=backend)
    pipe.register("vww", model, w["weights"], w["bn_offset"], head_params=head)
    served = phases.run("pipeline_serve", lambda: pipe.serve(
        [FrontendRequest("vww", im) for im in images]
    ))
    served = _finite(np.stack([np.asarray(s) for s in served]),
                     (batch, model.n_classes), "pipeline logits")
    expect((np.argmax(served, -1) == np.argmax(logits, -1)).all(),
           "pipeline top-1 differs from CompiledModel.run")

    server = StreamServer(pipe, gate)
    server.add_stream("cam0", "vww")
    ticks_srv = phases.run("server_run", lambda: [
        r for rs in server.run({"cam0": f} for f in stream) for r in rs
    ])
    _same_stream(ticked, ticks_srv, "StreamServer.run vs stream")
    server.add_stream("cam1", "vww")
    segs_srv = phases.run("server_segments", lambda: list(
        server.serve_segments("cam1", stream, segment_length=segment)
    ))
    _same_stream(ticked, segs_srv, "serve_segments vs stream")
    # one stream, per-tick then segments then per-tick again: the segment
    # resumes from the host mirror, donates its carry, and hands back
    server.add_stream("cam2", "vww")

    def interleaved():
        out = [r for rs in server.run({"cam2": f} for f in stream[:segment])
               for r in rs]
        out += list(server.serve_segments(
            "cam2", stream[segment:ticks - segment], segment_length=segment
        ))
        out += [r for rs in server.run(
            {"cam2": f} for f in stream[ticks - segment:]) for r in rs]
        return out

    mixed = phases.run("server_interleaved", interleaved)
    _same_stream(ticked, mixed, "interleaved per-tick/segment vs stream")

    if audit is not None:
        readings["kernel_executables"] = audit.check(backend="pallas")
        log(f"Mosaic kernel found in {readings['kernel_executables']} "
            "frontend executables")
    readings["phase_seconds"] = phases.seconds
    return readings


def four_chip(
    cfg,
    *,
    seed: int = 0,
    batch: int = 32,
    ticks: int = 24,
    cameras: int = 8,
    backend: str = "pallas",
    audit: KernelAudit | None = None,
) -> dict:
    """Sharded pipeline + multi-camera server vs the unsharded twin."""
    import jax
    import numpy as np

    from repro import fpca
    from repro.launch.mesh import make_host_mesh
    from repro.serving.fpca_pipeline import FPCAPipeline, FrontendRequest
    from repro.serving.streaming import StreamServer

    n = len(jax.devices())
    expect(n >= 4, f"--chips 4 needs four devices, JAX sees {n}")
    phases = Phases()
    model, bucket, w, head = _network(cfg, seed)
    spec = model.spec
    images = _images(spec, seed, batch)
    gate = fpca.DeltaGateConfig(threshold=0.02, hysteresis=1, keyframe_interval=16)
    cams = {f"cam{i}": _camera(spec, seed + i) for i in range(cameras)}
    readings: dict = {}

    def serve(mesh, tag):
        pipe = FPCAPipeline(bucket, backend=backend, mesh=mesh)
        pipe.register("vww", model, w["weights"], w["bn_offset"], head_params=head)
        pipe.register("fe", model.frontend, w["weights"], w["bn_offset"])
        reqs = [FrontendRequest(c, im) for c in ("vww", "fe") for im in images]
        out = phases.run(f"{tag}_serve", lambda: pipe.serve(reqs))
        server = StreamServer(pipe, gate)
        for sid in cams:
            server.add_stream(sid, "vww")
        res = phases.run(f"{tag}_fleet", lambda: [
            r for rs in server.run(
                {sid: cam.frame_at(t) for sid, cam in cams.items()}
                for t in range(ticks)
            ) for r in rs
        ])
        return pipe, out, res

    pipe4, out4, res4 = serve(make_host_mesh(data=4), "sharded")
    _, out1, res1 = serve(None, "unsharded")
    expect(all(h.data_parallelism == 4 for h in pipe4._handles.values()),
           "a pipeline handle is not sharded four ways")
    lg4, lg1 = (np.stack([np.asarray(o) for o in out[:batch]]) for out in (out4, out1))
    ct4, ct1 = (np.stack([np.asarray(o) for o in out[batch:]]) for out in (out4, out1))
    np.testing.assert_array_equal(ct4, ct1, err_msg="sharded vs unsharded counts")
    _finite(lg4, (batch, model.n_classes), "sharded logits")
    expect((np.argmax(lg4, -1) == np.argmax(lg1, -1)).all(),
           "sharded top-1 differs from unsharded")
    readings["batch_logit_max_diff"] = float(np.abs(lg4 - lg1).max())
    expect(len(res4) == len(res1) == ticks * cameras,
           f"fleet served {len(res4)} and {len(res1)} results")
    readings["fleet_logit_max_diff"] = _same_stream(res4, res1, "sharded fleet")
    log(f"sharded vs unsharded: counts identical, max |dlogit| batch "
        f"{readings['batch_logit_max_diff']:.6g}, fleet "
        f"{readings['fleet_logit_max_diff']:.6g}")
    if audit is not None:
        readings["kernel_executables"] = audit.check(backend=backend, no_gather=True)
    readings["phase_seconds"] = phases.seconds
    return readings


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs only the sharded path and its unsharded twin")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    import jax

    if jax.default_backend() != "tpu":
        print(f"chip_smoke: no TPU here (JAX backend {jax.default_backend()!r})",
              file=sys.stderr)
        return 2
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", str(ROOT / ".jax_cache"))
    sys.path.insert(0, str(ROOT / "src"))
    from repro.configs import fpca_cnn

    dev = jax.devices()[0]
    log(f"device: {dev.platform} {dev.device_kind} x{len(jax.devices())}")
    audit = KernelAudit()
    audit.install()
    t0 = time.perf_counter()
    if args.chips == 4:
        readings = four_chip(fpca_cnn.CFG, seed=args.seed, audit=audit)
    else:
        readings = single_chip(fpca_cnn.CFG, seed=args.seed, audit=audit)
    readings["total_seconds"] = time.perf_counter() - t0
    stats = dev.memory_stats() or {}
    readings["peak_bytes_in_use"] = stats.get("peak_bytes_in_use")
    log("readings: " + json.dumps(readings, sort_keys=True))
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices()),
    }}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
