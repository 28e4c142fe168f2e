"""Serve a whole FPCA model — analog frontend + digital CNN head — through
ONE ``fpca.compile()``.

    PYTHONPATH=src python examples/serve_fpca_cnn.py                  # fresh net
    PYTHONPATH=src python examples/serve_fpca_cnn.py --weights m.npz  # trained
    PYTHONPATH=src python examples/serve_fpca_cnn.py --image-h 24 --frames 6

``--weights`` takes the bundle ``examples/train_fpca_cnn.py --export``
writes (the hw-aware trained network); without it a freshly-initialised
network on the same architecture is served (the serving path is identical).

What it demonstrates, end to end:

1. **compile once** — ``fpca.compile(FPCAModelProgram)`` returns a
   ``CompiledModel`` whose ``.run()`` produces class logits from raw frames
   through one fused jit (frontend kernel + jnp head), bit-identical to
   composing a frontend handle with the reference head apply;
2. **reprogram cheaply** — rewriting the NVM planes *or* the head weights
   never recompiles (asserted via ``cache_info()``);
3. **stream with skip-aware classification** — each delta-gated tick patches
   its kept windows into the running effective activation map, so the head
   yields a per-tick class decision even when most windows are skipped;
4. **fleet serving** — the same model program registered into
   ``FPCAPipeline`` / ``StreamServer`` (logits in ``StreamFrameResult``),
   with the head's FLOPs/latency accounted next to the executed-window
   stats by ``analysis.model_streaming_report``.
"""

from __future__ import annotations

import argparse
import json
from pathlib import Path

import jax
import numpy as np

from repro.configs.fpca_cnn import make_model_program
from repro.core import analysis
from repro.core.adc import ADCConfig
from repro.core.fpca_sim import WeightEncoding
from repro.core.mapping import FPCASpec
from repro.data.pipeline import SyntheticMovingObject
from repro.fpca import DeltaGateConfig, FPCAModelProgram, compile as fpca_compile
from repro.serving.fpca_pipeline import FPCAPipeline, FrontendRequest
from repro.serving.streaming import StreamServer


def load_export(path: str) -> tuple[FPCAModelProgram, dict]:
    """Rebuild the model program + parameters train_fpca_cnn.py exported."""
    bundle = np.load(path)
    meta = json.loads(bytes(bundle["meta"]).decode())
    spec = FPCASpec(
        image_h=meta["image_h"], image_w=meta["image_w"],
        out_channels=meta["out_channels"], kernel=meta["kernel"],
        stride=meta["stride"], max_kernel=meta["max_kernel"],
    )
    model = make_model_program(
        spec,
        adc=ADCConfig(bits=meta["adc_bits"]),
        enc=WeightEncoding(n_levels=meta["nvm_levels"]),
        input_scale=meta["input_scale"],
    )
    head_params = []
    i = 0
    while f"head{i}_w" in bundle:
        head_params.append({"w": bundle[f"head{i}_w"], "b": bundle[f"head{i}_b"]})
        i += 1
    out = {
        "kernel": bundle["kernel"],
        "bn_offset": bundle["bn_offset"],
        "head_params": head_params,
    }
    if "quant_scales" in bundle:
        out["quant_scales"] = bundle["quant_scales"]
    return model, out


def fresh_network(image_h: int, seed: int = 0) -> tuple[FPCAModelProgram, dict]:
    spec = FPCASpec(image_h=image_h, image_w=image_h, out_channels=8,
                    kernel=5, stride=5, max_kernel=5)
    model = make_model_program(spec)
    rng = np.random.default_rng(seed)
    kernel = (rng.normal(size=model.frontend.kernel_shape) * 0.2).astype(np.float32)
    return model, {
        "kernel": kernel,
        "bn_offset": np.zeros((spec.out_channels,), np.float32),
        "head_params": model.init_head(jax.random.PRNGKey(seed)),
    }


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--weights", metavar="NPZ",
                    help="bundle from train_fpca_cnn.py --export")
    ap.add_argument("--image-h", type=int, default=60,
                    help="sensor size for the fresh-network path")
    ap.add_argument("--frames", type=int, default=16)
    ap.add_argument("--backend", default=None,
                    help="default: auto-select (the Pallas kernel on a TPU)")
    ap.add_argument("--precision", choices=("f32", "int8"), default="f32",
                    help="int8 serves the calibrated quantised lowering "
                         "(bounded parity vs the f32 reference)")
    args = ap.parse_args()

    if args.weights:
        model, params = load_export(args.weights)
        print(f"loaded trained export {args.weights}")
    else:
        model, params = fresh_network(args.image_h)
        print("serving a freshly-initialised network (pass --weights for the "
              "trained one)")

    serve_model, serve_head = model, params["head_params"]
    if args.precision == "int8":
        from repro.models.quant import quantize_head_params, unpack_act_scales

        serve_model = model.replace(precision="int8")
        act_scales = (unpack_act_scales(model, params["quant_scales"])
                      if "quant_scales" in params else None)
        serve_head = quantize_head_params(
            serve_model, params["head_params"], act_scales=act_scales
        )
        print("precision: int8 "
              + ("(export-calibrated activation scales)" if act_scales
                 else "(data-free full-scale calibration)"))
    spec = model.spec
    print(f"model: {spec.image_h}x{spec.image_w}x{spec.in_channels} "
          f"-> frontend {model.frontend.out_shape} -> head "
          f"{' -> '.join(str(s) for s in model.head_shapes()[1:])} "
          f"({model.n_classes} classes)")

    # 1. compile the WHOLE model once; serve a batch of frames as logits
    m = fpca_compile(
        serve_model, backend=args.backend, weights=params["kernel"],
        bn_offset=params["bn_offset"], head_params=serve_head,
    )
    rng = np.random.default_rng(1)
    batch = rng.uniform(0, 1, (8, spec.image_h, spec.image_w, 3)).astype(np.float32)
    logits = np.asarray(m.run(batch))
    print(f"batched run: {batch.shape[0]} frames -> logits {logits.shape}, "
          f"classes {np.argmax(logits, -1).tolist()}")

    # parity: f32 fused executable is bit-identical to frontend handle +
    # reference head apply; int8 is parity-BOUNDED against that f32 reference
    fe = fpca_compile(model.frontend, backend=args.backend,
                      weights=params["kernel"], bn_offset=params["bn_offset"],
                      model=m.model)
    ref = np.asarray(model.apply_head(params["head_params"], fe.run(batch)))
    if args.precision == "int8":
        from repro.models.quant import logit_parity

        par = logit_parity(ref, logits)
        print(f"parity (int8 vs f32 reference): max |dlogit| "
              f"{par['max_abs_divergence']:.4f}, top-1 agreement "
              f"{par['top1_agreement']:.2f}")
    elif jax.default_backend() == "tpu" and m.backend.name == "pallas":
        # the chip's matmuls round differently from one program to the
        # next, so the kernel is held to the reference backend at highest
        # precision: counts within 1 ADC count, the same top-1 class
        with jax.default_matmul_precision("highest"):
            ref_fe = fpca_compile(model.frontend, backend="reference",
                                  weights=params["kernel"],
                                  bn_offset=params["bn_offset"], model=m.model)
            ref_counts = np.asarray(ref_fe.run(batch))
            ref = np.asarray(model.apply_head(params["head_params"], ref_counts))
        dc = np.abs(np.asarray(fe.run(batch)) - ref_counts).max()
        assert dc <= 1, f"counts differ by {dc} from the reference"
        assert (logits.argmax(-1) == ref.argmax(-1)).all(), "top-1 diverges"
        print(f"parity: counts within {dc:.0f} ADC count of the "
              f"highest-precision reference, same top-1 class")
    else:
        assert np.array_equal(logits, ref), "fused logits diverge from reference"
        print("parity: fused frontend+head jit is bit-identical to the "
              "composed reference")

    # 2. reprogram NVM planes AND head weights: guaranteed zero recompiles
    misses = m.cache_info().misses
    m.reprogram(params["kernel"] * 0.9,
                head_params=jax.tree_util.tree_map(lambda a: a * 1.1,
                                                   params["head_params"]))
    m.run(batch)
    assert m.cache_info().misses == misses, "reprogram must never recompile"
    print(f"reprogram (NVM + head): zero recompiles "
          f"(cache misses still {misses})")
    m.reprogram(params["kernel"], params["bn_offset"], head_params=serve_head)

    # 3. skip-aware streaming classification off the handle
    cam = SyntheticMovingObject((spec.image_h, spec.image_w), seed=3)
    gate = DeltaGateConfig(threshold=0.02, hysteresis=1, keyframe_interval=0)
    h_o, w_o, _ = model.frontend.out_shape
    kept = 0
    for r in m.stream((cam.frame_at(t) for t in range(args.frames)), gate=gate):
        kept += r.kept_windows
        if r.frame_idx < 4 or r.frame_idx == args.frames - 1:
            print(f"  tick {r.frame_idx:3d}: kept {r.kept_windows:3d}/"
                  f"{r.total_windows} windows -> class "
                  f"{r.predicted_class} (logits {np.round(r.logits, 2)})")
    total = args.frames * h_o * w_o
    print(f"stream: executed {kept}/{max(total, 1)} windows "
          f"({kept/max(total, 1):.1%}) with a class decision every tick")

    # 4. fleet path: pipeline + StreamServer, head cost accounted
    pipe = FPCAPipeline(m.model, backend=args.backend)
    pipe.register("vww", serve_model, params["kernel"], params["bn_offset"],
                  head_params=serve_head)
    out = pipe.serve([FrontendRequest("vww", batch[0])])
    print(f"pipeline serve: logits {np.asarray(out[0]).shape} "
          f"(class {int(np.argmax(np.asarray(out[0])))})")
    server = StreamServer(pipe, gate)
    server.add_stream("cam0", "vww")
    session = server.sessions["cam0"]
    for results in server.run({"cam0": cam.frame_at(t)}
                              for t in range(args.frames)):
        pass
    print(f"server: {server.stats.frames} frames, kept "
          f"{server.stats.windows_kept}/{server.stats.windows_total} windows")
    if session.block_masks:
        rep = analysis.model_streaming_report(model, list(session.block_masks))
        print(f"accounting: frontend energy {rep['energy_vs_dense']:.2f}x "
              f"dense, head {rep['head_macs_per_frame']/1e3:.1f} kMAC/frame, "
              f"model fps_effective {rep['model_fps_effective']:.0f}")


if __name__ == "__main__":
    main()
