"""Declarative FPCA program spec — the single source of truth for "what is
programmed into the array".

The paper's headline is *field-programmability*: one pixel array is
dynamically reprogrammed (weights, kernel / channel / stride geometry)
without refabrication.  :class:`FPCAProgram` is that statement as a single
validated dataclass: everything that is **static to a compiled executable**
(sensor geometry, circuit constants, ADC precision, NVM weight encoding)
plus the optional streaming-control plane (delta gate, threshold servo)
composed into one spec with a stable :meth:`~FPCAProgram.signature`.

The split the API enforces:

* the **program** (this module) pins the compiled artifact — two programs
  with equal signatures share one executable;
* the **weights** (NVM conductance planes) enter traced — reprogramming them
  (:meth:`repro.fpca.CompiledFrontend.reprogram`) never recompiles.  That is
  the paper's field-programmability as an API contract, and it is why
  ``kernel`` / ``bn_offset`` are *not* program fields: they live in
  :class:`ProgrammedConfig` (a program bound to weights).

Signatures are **versioned primitive tuples** (ints / floats / strs only, no
dataclass instances), so they are stable across refactors of the config
classes themselves — a golden test pins them, because silently changing a
signature silently invalidates every warm executable cache in a fleet.
"""

from __future__ import annotations

import dataclasses
from typing import Any

import jax

from repro.core.adc import ADCConfig
from repro.core.device_models import CircuitParams
from repro.core.fpca_sim import WeightEncoding
from repro.core.mapping import FPCASpec, output_dims

__all__ = [
    "DeltaGateConfig",
    "GateControllerConfig",
    "FPCAProgram",
    "ProgrammedConfig",
    "spec_signature",
    # multi-layer model programs (frontend + digital CNN head)
    "ConvSpec",
    "PoolSpec",
    "DenseSpec",
    "ActivationSpec",
    "FPCAModelProgram",
    "ProgrammedModel",
]

# Bump when the *meaning* of a signature field changes; appending new fields
# keeps old-version tuples distinct by construction.
_SIG_VERSION = "repro.fpca/1"
_MODEL_SIG_VERSION = "repro.fpca.model/1"


@dataclasses.dataclass(frozen=True)
class DeltaGateConfig:
    """Temporal delta gate knobs (per stream, or per config of a stream)."""

    threshold: float = 0.02      # mean |Δ| per block that counts as "changed"
    hysteresis: int = 1          # frames a block stays live after its change
    keyframe_interval: int = 30  # full-frame refresh period (0 = never)


@dataclasses.dataclass(frozen=True)
class GateControllerConfig:
    """Closed-loop gate-threshold servo knobs (per stream).

    ``target`` is the budget: the kept-window fraction (``metric="keep"``)
    or the executed-energy fraction of a dense readout (``metric="energy"``)
    the stream should settle at.  The servo error is measured *relative to
    the target* — ``(ema - target) / target``, clipped to
    ``[err_low, err_high]`` — so a 5% budget and a 50% budget servo with the
    same gains, and a saturated scene (observation pinned at 0 or 1) applies
    a bounded, steady corrective step instead of a runaway one.

    Gains are in nats of log-threshold per unit of *relative* error;
    ``max_step`` bounds the per-tick actuation.  The integrator **leaks**
    (``leak`` per tick) and is clamped to ``±windup``, and it only
    accumulates while the actuator is unsaturated — three layers of
    anti-windup, because the gate's block statistics give the plant a hard
    cliff (a threshold above every block delta keeps nothing) that a plain
    PI loop winds up against.
    """

    target: float = 0.15
    metric: str = "keep"            # "keep" | "energy"
    ema_alpha: float = 0.4          # EMA weight of the newest observation
    kp: float = 0.35                # proportional gain  [nats / unit rel-error]
    ki: float = 0.03                # integral gain      [nats / unit rel-error]
    max_step: float = 0.4           # |Δ ln threshold| bound per tick [nats]
    leak: float = 0.85              # integrator decay per tick
    windup: float = 2.0             # |integrator| clamp [rel-error ticks]
    err_low: float = -1.0           # rel-error clip (0 kept = exactly -1)
    err_high: float = 3.0
    deadband: float = 0.0           # |rel error| below which the servo holds
    min_threshold: float = 1e-4
    max_threshold: float = 1.0
    history_len: int = 512          # ticks of trajectory retained (no leak)

    def __post_init__(self) -> None:
        if not 0.0 < self.target <= 1.0:
            raise ValueError("target must be in (0, 1]")
        if self.metric not in ("keep", "energy"):
            raise ValueError(f"unknown metric {self.metric!r}")
        if not 0.0 < self.ema_alpha <= 1.0:
            raise ValueError("ema_alpha must be in (0, 1]")
        if self.max_step <= 0.0:
            raise ValueError("max_step must be > 0")
        if not 0.0 <= self.leak <= 1.0:
            raise ValueError("leak must be in [0, 1]")
        if self.err_low >= self.err_high:
            raise ValueError("need err_low < err_high")
        if not 0.0 < self.min_threshold <= self.max_threshold:
            raise ValueError("need 0 < min_threshold <= max_threshold")
        if self.history_len < 1:
            raise ValueError("history_len must be >= 1")


def spec_signature(
    spec: FPCASpec, out_channels: int, adc: ADCConfig, enc: WeightEncoding
) -> tuple:
    """Hashable compiled-kernel signature, as a versioned primitive tuple.

    Everything that is *static* to a jitted executable: the spec pins patch
    geometry, ``out_channels`` the weight-plane width, adc/enc the epilogue
    constants.  Weights and BN offsets enter traced, so reprogramming the
    NVM planes does NOT change the signature (no recompile — the point of
    field-programmability).

    The tuple contains only primitives (never the dataclass instances), so
    adding a method or reordering fields on :class:`FPCASpec` /
    :class:`ADCConfig` / :class:`WeightEncoding` cannot silently change it;
    ``tests/test_fpca_api.py`` pins golden values.
    """
    return (
        _SIG_VERSION,
        ("spec", int(spec.image_h), int(spec.image_w), int(spec.out_channels),
         int(spec.kernel), int(spec.stride), int(spec.max_kernel),
         int(spec.in_channels), int(spec.padding), int(spec.binning),
         int(spec.skip_block)),
        ("out_channels", int(out_channels)),
        ("adc", int(adc.bits), float(adc.v_ref)),
        ("enc", int(enc.n_levels), float(enc.w_scale)),
    )


@dataclasses.dataclass(frozen=True)
class FPCAProgram:
    """One validated FPCA array program: the canonical configuration object.

    Composes everything the repo previously scattered across
    ``FPCAFrontendConfig`` (core) and the pipeline/server keyword soup:

    * ``spec``        — sensor + convolution geometry (:class:`FPCASpec`);
    * ``circuit``     — analog circuit constants the bucket model is fitted
      against;
    * ``adc`` / ``enc`` — SS-ADC precision and NVM weight encoding (the
      fused-kernel epilogue constants);
    * ``out_channels`` — programmed weight-plane width; defaults to
      ``spec.out_channels`` but may differ (e.g. a channel-stacked
      multi-config executable);
    * ``gate`` / ``controller`` — optional streaming control plane (temporal
      delta gate and its closed-loop threshold servo).  These are *runtime*
      knobs: they are deliberately **excluded** from :meth:`signature`, so
      retuning a gate never invalidates a compiled executable.

    Weights are not here: a program is the refabrication-free part of the
    paper's story, weights are the cheap NVM rewrite
    (:meth:`repro.fpca.CompiledFrontend.reprogram`).
    """

    spec: FPCASpec
    circuit: CircuitParams = CircuitParams()
    adc: ADCConfig = ADCConfig()
    enc: WeightEncoding = WeightEncoding()
    out_channels: int | None = None
    gate: DeltaGateConfig | None = None
    controller: GateControllerConfig | None = None

    def __post_init__(self) -> None:
        if self.out_channels is None:
            object.__setattr__(self, "out_channels", self.spec.out_channels)
        if int(self.out_channels) < 1:
            raise ValueError("out_channels must be >= 1")
        if self.controller is not None and not isinstance(
            self.controller, GateControllerConfig
        ):
            raise TypeError("controller must be a GateControllerConfig")
        if self.gate is not None and not isinstance(self.gate, DeltaGateConfig):
            raise TypeError("gate must be a DeltaGateConfig")

    # -- derived geometry ----------------------------------------------------
    @property
    def out_shape(self) -> tuple[int, int, int]:
        h_o, w_o = output_dims(self.spec)
        return (h_o, w_o, int(self.out_channels))

    @property
    def kernel_shape(self) -> tuple[int, int, int, int]:
        """Shape of the float kernel this program accepts: (c_o, k, k, c_i)."""
        s = self.spec
        return (int(self.out_channels), s.kernel, s.kernel, s.in_channels)

    # -- identity ------------------------------------------------------------
    def signature(self) -> tuple:
        """Stable compile signature of this program (primitive tuple).

        Extends :func:`spec_signature` with the circuit constants (they are
        baked into the compiled executable through the fitted bucket model).
        ``gate`` / ``controller`` / weights are runtime state and excluded —
        reprogramming any of them must never recompile.  Cached on first
        call: serving layers key handle lookups on it per tick.
        """
        sig = self.__dict__.get("_signature")
        if sig is None:
            circuit = tuple(
                (f.name, float(getattr(self.circuit, f.name)))
                for f in dataclasses.fields(self.circuit)
            )
            sig = spec_signature(
                self.spec, int(self.out_channels), self.adc, self.enc
            ) + (("circuit",) + circuit,)
            object.__setattr__(self, "_signature", sig)
        return sig

    def fanout_signature(self) -> tuple:
        """Compile signature with the channel width normalised out.

        Two programs may fan out into one channel-stacked fused call (their
        NVM planes concatenated, one launch) iff these match: the stacked
        executable serves a single adc/enc/circuit epilogue, so anything
        beyond ``out_channels`` differing would silently mis-serve one of
        them.
        """
        return self.replace(out_channels=1).signature()

    def replace(self, **kw: Any) -> "FPCAProgram":
        return dataclasses.replace(self, **kw)


@dataclasses.dataclass(frozen=True)
class ProgrammedConfig:
    """A program bound to NVM weights — one named, field-programmed state.

    What a physical FPCA holds at any instant: the compiled-artifact spec
    (:class:`FPCAProgram`) plus the conductance planes currently written to
    the weight die.  Registered into :class:`repro.serving.FPCAPipeline`
    under ``name``; the deprecated ``FrontendConfig`` alias forwards here.
    """

    name: str
    program: FPCAProgram
    kernel: jax.Array               # (c_o, k, k, c_i) float weights
    bn_offset: jax.Array            # (c_o,) counts

    @property
    def spec(self) -> FPCASpec:
        return self.program.spec

    @property
    def out_channels(self) -> int:
        return int(self.program.out_channels)

    @property
    def out_shape(self) -> tuple[int, int, int]:
        return self.program.out_shape


# ---------------------------------------------------------------------------
# Multi-layer model programs: analog frontend + digital CNN head
# ---------------------------------------------------------------------------
#
# The paper's workload is never the frontend alone — it is a CNN whose FIRST
# layer is the FPCA array (§1/§5, VWW-class classification).  A model program
# promotes the spec from one layer to that whole network: the FPCAProgram
# frontend stage plus a validated sequence of digital stages, compiled behind
# the same `fpca.compile()` with the same split — layer *specs* are static to
# the executable (they extend the signature), trained *parameters* enter
# traced (reprogramming them never recompiles).

_ACTIVATIONS = ("relu", "gelu", "silu", "tanh", "relu6")


def _check_activation(act: str | None) -> None:
    if act is not None and act not in _ACTIVATIONS:
        raise ValueError(
            f"unknown activation {act!r}; available: {_ACTIVATIONS}"
        )


def _apply_activation(act: str | None, x):
    import jax.nn
    import jax.numpy as jnp

    if act is None:
        return x
    return {
        "relu": jax.nn.relu,
        "gelu": jax.nn.gelu,
        "silu": jax.nn.silu,
        "tanh": jnp.tanh,
        "relu6": jax.nn.relu6,
    }[act](x)


@dataclasses.dataclass(frozen=True)
class ConvSpec:
    """One digital convolution stage of a model head (NHWC, biased).

    ``groups`` splits the input and output channels into that many
    independent convolutions (``lax.conv_general_dilated``'s
    ``feature_group_count``); ``groups == in_channels == out_channels`` is a
    depthwise convolution.  Weights are ``(out_channels, k, k, in_channels
    // groups)``."""

    out_channels: int
    kernel: int
    stride: int = 1
    padding: str = "VALID"          # "VALID" | "SAME"
    activation: str | None = "relu"
    groups: int = 1

    def __post_init__(self) -> None:
        if self.out_channels < 1 or self.kernel < 1 or self.stride < 1:
            raise ValueError("conv out_channels/kernel/stride must be >= 1")
        if self.padding not in ("VALID", "SAME"):
            raise ValueError(f"padding must be VALID or SAME, got {self.padding!r}")
        if self.groups < 1:
            raise ValueError(f"conv groups must be >= 1, got {self.groups}")
        if self.out_channels % self.groups:
            raise ValueError(
                f"conv groups {self.groups} do not divide out_channels "
                f"{self.out_channels}"
            )
        _check_activation(self.activation)

    def weight_shape(self, c_in: int, where: str) -> tuple[int, int, int, int]:
        """``(out_channels, k, k, c_in // groups)`` for an input of ``c_in``
        channels; ``where`` names the stage in the divisibility error."""
        if c_in % self.groups:
            raise ValueError(
                f"{where}: conv groups {self.groups} do not divide input "
                f"channels {c_in}"
            )
        return (self.out_channels, self.kernel, self.kernel, c_in // self.groups)

    def _sig(self) -> tuple:
        sig = ("conv", int(self.out_channels), int(self.kernel),
               int(self.stride), self.padding, self.activation or "")
        # appended only for grouped convs, so every ungrouped signature
        # stays byte-identical (golden-pinned)
        return sig + (("groups", int(self.groups)),) if self.groups != 1 else sig


@dataclasses.dataclass(frozen=True)
class PoolSpec:
    """Spatial pooling stage (``kind``: "max" | "avg")."""

    size: int
    stride: int | None = None       # None = size (non-overlapping)
    kind: str = "max"

    def __post_init__(self) -> None:
        if self.size < 1 or (self.stride is not None and self.stride < 1):
            raise ValueError("pool size/stride must be >= 1")
        if self.kind not in ("max", "avg"):
            raise ValueError(f"pool kind must be max or avg, got {self.kind!r}")

    def _sig(self) -> tuple:
        s = self.size if self.stride is None else self.stride
        return ("pool", self.kind, int(self.size), int(s))


@dataclasses.dataclass(frozen=True)
class DenseSpec:
    """Fully-connected stage (flattens a spatial input); the final stage of
    every head is a DenseSpec — its ``features`` are the class logits."""

    features: int
    activation: str | None = None

    def __post_init__(self) -> None:
        if self.features < 1:
            raise ValueError("dense features must be >= 1")
        _check_activation(self.activation)

    def _sig(self) -> tuple:
        return ("dense", int(self.features), self.activation or "")


@dataclasses.dataclass(frozen=True)
class ActivationSpec:
    """A bare nonlinearity stage (for heads that separate it from conv/dense)."""

    fn: str = "relu"

    def __post_init__(self) -> None:
        _check_activation(self.fn)

    def _sig(self) -> tuple:
        return ("act", self.fn)


LayerSpec = ConvSpec | PoolSpec | DenseSpec | ActivationSpec


@dataclasses.dataclass(frozen=True)
class FPCAModelProgram:
    """One validated multi-layer model: FPCA frontend + digital CNN head.

    * ``frontend``     — the analog first layer (:class:`FPCAProgram`);
    * ``head``         — the digital stages applied to the frontend's SS-ADC
      counts, in order (conv / pool / dense / activation specs).  The last
      stage must be a :class:`DenseSpec` — its features are the class logits;
    * ``input_scale``  — counts -> activation-unit scale applied before the
      head (a trained network exports its digital gain calibration here,
      ``adc.lsb * gain``); compiled into the executable, hence in the
      signature.

    The program/weights split is the frontend's, extended: layer specs are
    static to the compiled executable (signature), trained parameters (NVM
    planes AND head weights) enter traced — reprogramming either never
    recompiles (:meth:`repro.fpca.CompiledModel.reprogram`).

    ``head`` may alternatively be a :class:`repro.models.heads.HeadGraph`
    (residual / multi-branch / detection topologies from the model zoo,
    :mod:`repro.fpca.zoo`); graph heads extend the signature under a
    distinct ``"head_graph"`` tag, so every chain-head signature stays
    byte-identical.  ``arch`` is the registered zoo name this program was
    built under (``None`` for hand-rolled programs) — a telemetry label
    only, deliberately **excluded** from :meth:`signature`.

    ``precision`` selects the digital-head lowering: ``"f32"`` (the
    bit-exact reference) or ``"int8"`` — per-channel symmetric int8
    weights, calibrated int8 activations and int32 accumulation
    (:mod:`repro.models.quant`), parity-bounded against f32.  It is a
    *compile* option (in the signature: the two lowerings are distinct
    executables), but the quantised parameters — scales included — enter
    traced, so :meth:`repro.fpca.CompiledModel.reprogram` stays
    zero-recompile either way.
    """

    frontend: FPCAProgram
    head: Any
    input_scale: float = 1.0
    arch: str | None = None
    precision: str = "f32"

    def __post_init__(self) -> None:
        if not isinstance(self.frontend, FPCAProgram):
            raise TypeError("frontend must be an FPCAProgram")
        if self.precision not in ("f32", "int8"):
            raise ValueError(
                f"unknown precision {self.precision!r}; available: "
                f"('f32', 'int8')"
            )
        from repro.models.heads import HeadGraph

        if isinstance(self.head, HeadGraph):
            if not float(self.input_scale) > 0.0:
                raise ValueError("input_scale must be > 0")
            # validates node geometry against the frontend's output shape
            self.head.shapes(self.frontend.out_shape)
        else:
            self._validate_chain()
        if self.precision == "int8":
            from repro.models.quant import check_int8_lowering

            check_int8_lowering(self)

    def _validate_chain(self) -> None:
        object.__setattr__(self, "head", tuple(self.head))
        if not self.head:
            raise ValueError("model head needs at least one layer spec")
        for layer in self.head:
            if not isinstance(layer, (ConvSpec, PoolSpec, DenseSpec, ActivationSpec)):
                raise TypeError(f"unknown head layer spec {layer!r}")
        if not isinstance(self.head[-1], DenseSpec):
            raise ValueError(
                "the last head stage must be a DenseSpec (the class logits)"
            )
        if not float(self.input_scale) > 0.0:
            raise ValueError("input_scale must be > 0")
        self.head_shapes()   # validates the layer geometry chains

    # -- derived geometry ----------------------------------------------------
    @property
    def is_graph_head(self) -> bool:
        from repro.models.heads import HeadGraph

        return isinstance(self.head, HeadGraph)

    def head_shapes(self) -> list[tuple[int, ...]]:
        """Output shape after each head stage (index 0 = frontend output)."""
        if self.is_graph_head:
            raise TypeError(
                "head_shapes() is for chain heads; a HeadGraph head exposes "
                "per-node shapes via "
                "model.head.shapes(model.frontend.out_shape)"
            )
        shapes: list[tuple[int, ...]] = [self.frontend.out_shape]
        for i, layer in enumerate(self.head):
            cur = shapes[-1]
            if isinstance(layer, ConvSpec):
                if len(cur) != 3:
                    raise ValueError(
                        f"head[{i}]: conv needs a spatial (h, w, c) input, "
                        f"got shape {cur}"
                    )
                h, w, c = cur
                layer.weight_shape(c, f"head[{i}]")
                if layer.padding == "SAME":
                    h_o = -(-h // layer.stride)
                    w_o = -(-w // layer.stride)
                else:
                    if layer.kernel > h or layer.kernel > w:
                        raise ValueError(
                            f"head[{i}]: conv kernel {layer.kernel} exceeds "
                            f"input {h}x{w}"
                        )
                    h_o = (h - layer.kernel) // layer.stride + 1
                    w_o = (w - layer.kernel) // layer.stride + 1
                shapes.append((h_o, w_o, layer.out_channels))
            elif isinstance(layer, PoolSpec):
                if len(cur) != 3:
                    raise ValueError(
                        f"head[{i}]: pool needs a spatial (h, w, c) input, "
                        f"got shape {cur}"
                    )
                h, w, c = cur
                if layer.size > h or layer.size > w:
                    raise ValueError(
                        f"head[{i}]: pool size {layer.size} exceeds input "
                        f"{h}x{w}"
                    )
                s = layer.size if layer.stride is None else layer.stride
                shapes.append(((h - layer.size) // s + 1,
                               (w - layer.size) // s + 1, c))
            elif isinstance(layer, DenseSpec):
                shapes.append((layer.features,))
            else:                       # ActivationSpec: shape-preserving
                shapes.append(cur)
        return shapes

    @property
    def n_classes(self) -> int:
        if self.is_graph_head:
            return int(self.head.n_classes)
        return int(self.head[-1].features)

    @property
    def head_out_shape(self) -> tuple[int, ...]:
        """Per-example output shape of the head: ``(n_classes,)`` for chain
        classifiers, the graph output shape (e.g. ``(gh, gw, C + 4)`` for a
        detection head) otherwise."""
        if self.is_graph_head:
            return tuple(self.head.out_shape(self.frontend.out_shape))
        return (self.n_classes,)

    @property
    def output_kind(self) -> str:
        """``"logits"`` (classifier) or ``"detections"`` (per-cell maps)."""
        return self.head.output_kind if self.is_graph_head else "logits"

    @property
    def detect_classes(self) -> int | None:
        """Class count of a detection head (``None`` for classifiers) — the
        split point :class:`repro.models.heads.Detections` needs."""
        return self.n_classes if self.output_kind == "detections" else None

    @property
    def spec(self) -> FPCASpec:
        return self.frontend.spec

    @property
    def out_channels(self) -> int:
        return int(self.frontend.out_channels)

    # -- parameters ----------------------------------------------------------
    def init_head(self, key: jax.Array) -> list[dict]:
        """Fresh head parameters: one dict per stage (``{}`` for
        parameterless pool/activation stages) — the pytree
        :meth:`apply_head` consumes and :class:`ProgrammedModel` binds.
        Graph heads return a dict keyed by node name instead."""
        if self.is_graph_head:
            return self.head.init(key, self.frontend.out_shape)
        from repro.models.layers import init_conv2d, init_linear

        params: list[dict] = []
        shapes = self.head_shapes()
        keys = jax.random.split(key, len(self.head))
        for i, layer in enumerate(self.head):
            cur = shapes[i]
            if isinstance(layer, ConvSpec):
                params.append(
                    init_conv2d(keys[i], cur[-1], layer.out_channels, layer.kernel,
                                groups=layer.groups)
                )
            elif isinstance(layer, DenseSpec):
                d_in = 1
                for d in cur:
                    d_in *= int(d)
                params.append(init_linear(keys[i], d_in, layer.features))
            else:
                params.append({})
        return params

    def bind_head_params(self, params: Any) -> Any:
        """Validate + coerce a head parameter pytree for serving — the
        single binding path used by
        :meth:`repro.fpca.CompiledModel.reprogram` and
        :meth:`repro.serving.FPCAPipeline.register`, so a stage-count or
        weight-shape mismatch fails at the call site, not inside a jitted
        trace.

        ``precision="f32"`` binds one f32 dict per stage.  With
        ``precision="int8"`` an already-quantised pytree (``w_q`` leaves,
        e.g. calibrated at export time) is validated and bound as-is; a
        plain f32 pytree is quantised on the spot with the data-free
        full-scale calibration (:func:`repro.models.quant.
        quantize_head_params` — pass explicit ``act_scales`` there for a
        data-calibrated bundle)."""
        if self.precision == "int8":
            from repro.models import quant

            if quant.is_quantized_params(params):
                return quant.bind_quant_head_params(self, params)
            return quant.quantize_head_params(self, params)
        return self._bind_f32(params)

    def _bind_f32(self, params: Any) -> Any:
        """The f32 binding path (also the pre-quantisation validator)."""
        if self.is_graph_head:
            return self.head.bind(params, self.frontend.out_shape)
        import jax.numpy as jnp

        bound = [
            jax.tree_util.tree_map(lambda a: jnp.asarray(a, jnp.float32), dict(p))
            for p in params
        ]
        if len(bound) != len(self.head):
            raise ValueError(
                f"head has {len(self.head)} stages but got {len(bound)} "
                f"parameter entries"
            )
        shapes = self.head_shapes()
        for i, (layer, p) in enumerate(zip(self.head, bound)):
            cur = shapes[i]
            if isinstance(layer, ConvSpec):
                want = {"w": layer.weight_shape(cur[-1], f"head[{i}]"),
                        "b": (layer.out_channels,)}
            elif isinstance(layer, DenseSpec):
                d_in = 1
                for d in cur:
                    d_in *= int(d)
                want = {"w": (d_in, layer.features), "b": (layer.features,)}
            else:
                want = {}
            got = {k: tuple(v.shape) for k, v in p.items()}
            if got != want:
                raise ValueError(
                    f"head[{i}] ({type(layer).__name__}): parameter shapes "
                    f"{got} do not match expected {want}"
                )
        return bound

    def apply_head(self, params, counts):
        """The reference head: SS-ADC counts ``(b, h_o, w_o, c_o)`` ->
        logits ``(b, n_classes)``, pure jnp ops (:mod:`repro.models.layers`).

        This function IS the numerics contract: the fused executable
        (:meth:`repro.fpca.CompiledModel.run`) traces exactly these ops after
        the frontend, so its logits are bit-identical to composing a
        frontend handle with this apply.

        With ``precision="int8"`` the contract is instead the quantised
        lowering (:func:`repro.models.quant.apply_head_int8`): same dispatch
        site, so every executable — fused model jit, head jit, patched
        streaming head, in-scan segment head — serves the int8 path.
        """
        import jax.numpy as jnp

        from repro.models.layers import avg_pool2d, conv2d, linear, max_pool2d

        if self.precision == "int8":
            from repro.models.quant import apply_head_int8

            return apply_head_int8(self, params, counts)
        if self.is_graph_head:
            x = jnp.asarray(counts, jnp.float32) * jnp.float32(self.input_scale)
            return self.head.apply(params, x)
        if len(params) != len(self.head):
            raise ValueError(
                f"head has {len(self.head)} stages but got {len(params)} "
                f"parameter entries"
            )
        x = jnp.asarray(counts, jnp.float32) * jnp.float32(self.input_scale)
        for layer, p in zip(self.head, params):
            if isinstance(layer, ConvSpec):
                x = _apply_activation(
                    layer.activation,
                    conv2d(p, x, layer.stride, layer.padding, layer.groups),
                )
            elif isinstance(layer, PoolSpec):
                pool = max_pool2d if layer.kind == "max" else avg_pool2d
                x = pool(x, layer.size, layer.stride)
            elif isinstance(layer, DenseSpec):
                if x.ndim > 2:
                    x = x.reshape(x.shape[0], -1)
                x = _apply_activation(layer.activation, linear(p, x))
            else:
                x = _apply_activation(layer.fn, x)
        return x

    # -- identity ------------------------------------------------------------
    def signature(self) -> tuple:
        """Stable model compile signature: a versioned primitive tuple
        extending the frontend's (golden-pinned in
        ``tests/test_fpca_model.py``).  Head *specs* and ``input_scale`` are
        compiled in; head *parameters* (like NVM weights) are runtime state
        and excluded — reprogramming them never recompiles."""
        sig = self.__dict__.get("_signature")
        if sig is None:
            if self.is_graph_head:
                head_sig = ("head_graph",) + self.head._sig_entries()
            else:
                head_sig = ("head",) + tuple(
                    layer._sig() for layer in self.head
                )
            sig = (
                (_MODEL_SIG_VERSION,)
                + self.frontend.signature()
                + (head_sig, ("input_scale", float(self.input_scale)))
            )
            if self.precision != "f32":
                # appended only off the f32 default, so every pre-existing
                # f32 signature stays byte-identical (golden-pinned)
                sig = sig + (("precision", self.precision),)
            object.__setattr__(self, "_signature", sig)
        return sig

    def replace(self, **kw: Any) -> "FPCAModelProgram":
        return dataclasses.replace(self, **kw)


@dataclasses.dataclass(frozen=True)
class ProgrammedModel:
    """A model program bound to its trained parameters — NVM planes for the
    analog frontend plus the head weight pytree, the way
    :class:`ProgrammedConfig` binds a frontend program to NVM weights.

    Registered into :class:`repro.serving.FPCAPipeline` under ``name``;
    ``program`` exposes the *frontend* program so every spec-bucketing /
    channel-stacking path treats a model config exactly like a frontend one.
    """

    name: str
    model: FPCAModelProgram
    kernel: jax.Array               # (c_o, k, k, c_i) float NVM weights
    bn_offset: jax.Array            # (c_o,) counts
    head_params: Any                # pytree matching model.init_head()

    @property
    def program(self) -> FPCAProgram:
        return self.model.frontend

    @property
    def spec(self) -> FPCASpec:
        return self.model.frontend.spec

    @property
    def out_channels(self) -> int:
        return int(self.model.frontend.out_channels)

    @property
    def out_shape(self) -> tuple[int, int, int]:
        return self.model.frontend.out_shape
