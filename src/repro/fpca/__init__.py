"""``repro.fpca`` — the unified compile/execute API for the FPCA frontend.

One program spec, explicit executables, pluggable backends::

    from repro import fpca
    from repro.core.mapping import FPCASpec

    program = fpca.FPCAProgram(
        spec=FPCASpec(image_h=96, image_w=96, out_channels=8, kernel=5,
                      stride=5),
        gate=fpca.DeltaGateConfig(threshold=0.02),
    )
    fe = fpca.compile(program, backend="basis", weights=kernel)
    counts = fe.run(batch)                  # fused serving call
    fe.reprogram(new_kernel)                # NVM rewrite — zero recompiles
    for result in fe.stream(camera_frames):  # delta-gated continuous vision
        ...

Layer map:

* :mod:`repro.fpca.program`    — :class:`FPCAProgram` (the one validated
  spec), :class:`FPCAModelProgram` (frontend + digital CNN head — the
  paper's whole workload as one compileable model, served as class logits
  by :class:`CompiledModel`) + stable :func:`spec_signature`;
* :mod:`repro.fpca.backends`   — the :class:`Backend` registry
  (``reference`` / ``pallas`` / ``basis`` built in, third parties register
  via :func:`register_backend`);
* :mod:`repro.fpca.executable` — :func:`compile` and
  :class:`CompiledFrontend` (bounded executable LRU, sticky region-skip
  buckets, mesh sharding, stats);
* :mod:`repro.fpca.cache`      — the introspectable
  :class:`ExecutableCache` / :class:`CacheInfo`;
* :mod:`repro.fpca.zoo`        — the model-zoo meta-architecture registry
  (:func:`register_arch` / :func:`build_model`): config-driven construction
  of classifier and detection model programs over
  :class:`repro.models.heads.HeadGraph` head graphs;
* :mod:`repro.fpca.telemetry`  — the process-wide metrics registry every
  stats object reports into, span traces
  (``telemetry.enable(jsonl_path=...)``) and opt-in profiler annotations.

The batch scheduler (:class:`repro.serving.fpca_pipeline.FPCAPipeline`) and
the streaming fleet server (:class:`repro.serving.streaming.StreamServer`)
are thin orchestration layers over :class:`CompiledFrontend`.
"""

from __future__ import annotations

from repro.core.adc import ADCConfig
from repro.core.device_models import CircuitParams
from repro.core.fpca_sim import WeightEncoding
from repro.core.mapping import FPCASpec
from repro.fpca.backends import (
    Backend,
    available_backends,
    default_backend_name,
    get_backend,
    register_backend,
)
from repro.fpca import telemetry
from repro.fpca.cache import CacheInfo, CacheInfoVerbose, ExecutableCache
from repro.fpca.executable import (
    CompiledFrontend,
    CompiledModel,
    FrontendStats,
    SegmentResult,
    SegmentState,
    compile,
)
from repro.fpca.program import (
    ActivationSpec,
    ConvSpec,
    DeltaGateConfig,
    DenseSpec,
    FPCAModelProgram,
    FPCAProgram,
    GateControllerConfig,
    PoolSpec,
    ProgrammedConfig,
    ProgrammedModel,
    spec_signature,
)
from repro.models.heads import (
    AddSpec,
    ConcatSpec,
    DetectSpec,
    Detections,
    GlobalPoolSpec,
    HeadGraph,
    Node,
)
from repro.models.quant import (
    calibrate_head_scales,
    logit_parity,
    quantize_head_params,
)
from repro.fpca.zoo import available_archs, build_model, register_arch

__all__ = [
    # program spec
    "FPCAProgram",
    "ProgrammedConfig",
    "DeltaGateConfig",
    "GateControllerConfig",
    "spec_signature",
    # multi-layer model programs (frontend + digital CNN head)
    "FPCAModelProgram",
    "ProgrammedModel",
    "ConvSpec",
    "PoolSpec",
    "DenseSpec",
    "ActivationSpec",
    "CompiledModel",
    # quantised int8 serving (precision="int8" on FPCAModelProgram)
    "quantize_head_params",
    "calibrate_head_scales",
    "logit_parity",
    # model zoo (meta-arch registry + head graphs + detections)
    "register_arch",
    "build_model",
    "available_archs",
    "HeadGraph",
    "Node",
    "AddSpec",
    "ConcatSpec",
    "GlobalPoolSpec",
    "DetectSpec",
    "Detections",
    # re-exported building blocks of a program
    "FPCASpec",
    "CircuitParams",
    "ADCConfig",
    "WeightEncoding",
    # compile/execute
    "compile",
    "CompiledFrontend",
    "FrontendStats",
    "ExecutableCache",
    "CacheInfo",
    "CacheInfoVerbose",
    # observability (metrics registry, span traces, device hooks)
    "telemetry",
    # device-compiled streaming segments
    "SegmentState",
    "SegmentResult",
    # backend registry
    "Backend",
    "register_backend",
    "get_backend",
    "available_backends",
    "default_backend_name",
]
