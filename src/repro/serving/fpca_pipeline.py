"""Batched multi-spec FPCA frontend serving pipeline.

The paper's headline claim is *field-programmability*: one pixel array serves
many (kernel, stride, channel, binning) configurations.  This module is the
serving-side counterpart — a reconfiguration scheduler that accepts a
heterogeneous stream of frontend requests, buckets them by their compile
signature, and drives each bucket through one fused batched call.

Since the :mod:`repro.fpca` redesign the pipeline is a **thin orchestration
layer over explicit executables**: every distinct compile signature gets one
:class:`repro.fpca.CompiledFrontend` handle (all handles share ONE bounded
:class:`repro.fpca.ExecutableCache`, so the total number of live jitted
executables stays bounded across every registered configuration), and the
batch padding / mesh sharding / sticky region-skip buckets / zero-kept
short-circuit all live behind the handle.  What remains here is pure
scheduling:

1. every request names a registered *configuration* (an
   :class:`repro.fpca.ProgrammedConfig` — a program plus programmed NVM
   weights, what a physical FPCA would hold in its weight die) and carries
   one frame;
2. requests are grouped by configuration; each group's frames are stacked
   into one ``(B, H, W, c_i)`` batch;
3. each group runs through its signature's handle — configurations sharing
   (spec, c_o, adc, enc) share one handle and therefore one executable,
   because weights enter traced: reprogramming NVM planes never recompiles;
4. results are un-padded and scattered back to the original request order.

With ``cross_config_batching=True``, request groups whose configurations
share a compile signature are additionally merged into ONE executable call
by stacking their NVM weight planes along the channel axis (each request's
counts are sliced from its configuration's channel range).

Entry points: :meth:`FPCAPipeline.serve` (request mix), and
:meth:`FPCAPipeline.run_config_batch` — the low-level non-blocking call the
streaming server (:mod:`repro.serving.streaming`) dispatches through.
:meth:`FPCAPipeline.submit` is a deprecation shim forwarding to ``serve``.
"""

from __future__ import annotations

import dataclasses
import warnings
import weakref
from typing import Any, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro import fpca as _fpca
from repro.fpca import telemetry
from repro.core.adc import ADCConfig
from repro.core.curvefit import BucketCurvefitModel, fit_bucket_model
from repro.core.device_models import CircuitParams
from repro.core.fpca_sim import WeightEncoding
from repro.core.mapping import FPCASpec, active_window_mask, output_dims
from repro.fpca.cache import ExecutableCache
from repro.models.heads import Detections
from repro.fpca.executable import (
    _USE_PROGRAM,
    CompiledFrontend,
    CompiledModel,
    SegmentResult,
)
from repro.fpca.program import (
    FPCAModelProgram,
    FPCAProgram,
    ProgrammedConfig,
    ProgrammedModel,
    spec_signature,
)

__all__ = [
    "FrontendRequest",
    "FrontendConfig",
    "PipelineStats",
    "FPCAPipeline",
    "CalibrationKeyError",
    "spec_signature",
]


class CalibrationKeyError(ValueError):
    """A calibration handed to :class:`FPCAPipeline` as a plain
    :class:`BucketCurvefitModel` is implicitly keyed to the **default**
    :class:`CircuitParams` — serving a program that carries a custom circuit
    from it would silently pair the wrong physics with the wrong program
    (either by mis-using the supplied calibration or by quietly refitting and
    ignoring it).  Key calibrations explicitly as
    ``{(circuit, n_pixels): model}`` to serve custom-circuit programs."""


def __getattr__(name: str) -> Any:
    if name == "FrontendConfig":
        warnings.warn(
            "FrontendConfig is deprecated; use repro.fpca.ProgrammedConfig "
            "(an FPCAProgram bound to NVM weights)",
            DeprecationWarning,
            stacklevel=2,
        )
        return ProgrammedConfig
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")


@dataclasses.dataclass(frozen=True)
class FrontendRequest:
    """One frame for one registered configuration."""

    config: str                     # registered configuration name
    image: Any                      # (H, W, c_i) float in [0, 1]
    block_mask: np.ndarray | None = None   # region skipping (§3.4.5)


class PipelineStats(telemetry.StatsView):
    """Fleet-level serving counters — registry cells, single-sourced.

    Fields:

    * ``requests``       — frames accepted by :meth:`FPCAPipeline.serve`
    * ``batches``        — fused kernel invocations (fed by the handles'
      ``runs`` cells through the parent chain)
    * ``merged_groups``  — cross-config channel-stacked batches
    * ``fanout_batches`` — multi-config stream fan-out calls
    * ``windows_total`` / ``windows_executed`` / ``launches_skipped`` /
      ``bucket_switches`` / ``bucket_shrinks_deferred`` / ``segments`` /
      ``segment_ticks`` — parent-chained from every owned handle's
      :class:`repro.fpca.executable.FrontendStats`: the handle increments
      ONE cell and the delta lands here too, replacing the old before/after
      delta-mirroring (which double-counted by construction if a call path
      mirrored twice, and missed direct handle use entirely).
    * ``h2d_bytes``      — parent-chained likewise: host bytes the handles
      copied to the device (host image batches, keep grids)

    ``cache_hits`` / ``cache_misses`` / ``evictions`` are **derived** reads
    of the shared :class:`repro.fpca.ExecutableCache` — the same counters
    ``cache_info()`` reports, never a copy that can drift.
    """

    _PREFIX = "fpca_pipeline"
    _FIELDS = (
        "requests",
        "batches",
        "merged_groups",
        "fanout_batches",
        "windows_total",
        "windows_executed",
        "launches_skipped",
        "bucket_switches",
        "bucket_shrinks_deferred",
        "segments",
        "segment_ticks",
        "h2d_bytes",
    )
    _DERIVED = ("cache_hits", "cache_misses", "evictions")

    __slots__ = ("_cache_ref",)

    def __init__(self, cache: ExecutableCache | None = None,
                 labels: dict | None = None):
        super().__init__(labels=labels)
        object.__setattr__(
            self, "_cache_ref",
            weakref.ref(cache) if cache is not None else None,
        )

    def _cache(self) -> ExecutableCache | None:
        ref = object.__getattribute__(self, "_cache_ref")
        return ref() if ref is not None else None

    @property
    def cache_hits(self) -> int:
        c = self._cache()
        return c.hits if c is not None else 0

    @property
    def cache_misses(self) -> int:
        c = self._cache()
        return c.misses if c is not None else 0

    @property
    def evictions(self) -> int:
        c = self._cache()
        return c.evictions if c is not None else 0


class FPCAPipeline:
    """Spec-bucketed reconfiguration scheduler over compiled FPCA handles.

    Args:
      model: fitted :class:`BucketCurvefitModel` (or dict keyed by
        ``n_active_pixels``, or by ``(CircuitParams, n_active_pixels)`` for
        custom-circuit programs); entries without an explicit circuit key
        are taken as default-``CircuitParams`` calibrations.  Missing
        entries are fitted on demand against the registering program's
        circuit (a one-off ~seconds cost per (circuit, pixel count), as a
        deployment would calibrate once).
      backend: any name registered in :mod:`repro.fpca.backends` —
        ``"pallas"`` (TPU kernel), ``"basis"`` (XLA lowering of the same
        math; the fast path on CPU hosts), ``"reference"`` (dense oracle), or
        a third-party registration.  ``None`` (default) auto-selects by
        platform via :func:`repro.fpca.default_backend_name`.
      mesh: optional ``jax.sharding.Mesh`` — batches are sharded over its
        data axes for data-parallel serving; batch padding also rounds up to
        the data-axis extent.
      cache_capacity: bound on simultaneously-held jitted executables,
        shared across ALL registered configurations (one
        :class:`repro.fpca.ExecutableCache` backs every handle).
      cross_config_batching: merge request groups whose configurations share
        a compile signature into one channel-stacked executable call (see
        module docstring).  Off by default: the per-config path preserves the
        exact reprogram-without-recompile executable reuse the base tests pin.
      bucket_patience: sticky-bucket hysteresis for the region-skip row
        buckets (held per handle; a bucket grows immediately but only
        shrinks after ``bucket_patience`` consecutive under-full batches,
        cutting executable-cache switches on busy streams).  The default
        ``1`` is the stateless behaviour.  Trade-off: a deferred shrink
        serves an up-to-2x-oversized row bucket for up to
        ``bucket_patience`` ticks, so hysteresis pays off where a switch is
        expensive (a recompile on a real-TPU serving path) and can *cost*
        throughput where switches are cheap (warm-cache CPU hosts — see the
        flap-vs-sticky numbers in ``BENCH_stream.json``).
    """

    def __init__(
        self,
        model: BucketCurvefitModel | dict[int, BucketCurvefitModel] | None = None,
        *,
        adc: ADCConfig | None = None,
        enc: WeightEncoding | None = None,
        backend: str | None = None,
        interpret: bool | None = None,
        cache_capacity: int = 8,
        mesh: jax.sharding.Mesh | None = None,
        cross_config_batching: bool = False,
        bucket_patience: int = 1,
    ):
        self._backend = _fpca.get_backend(
            backend if backend is not None else _fpca.default_backend_name()
        )
        self.backend = self._backend.name
        self.adc = adc or ADCConfig()
        self.enc = enc or WeightEncoding()
        self.interpret = interpret
        self.mesh = mesh
        self.cross_config_batching = cross_config_batching
        if bucket_patience < 1:
            raise ValueError("bucket_patience must be >= 1")
        self.bucket_patience = bucket_patience
        # fitted bucket models keyed by (circuit, n_active_pixels): programs
        # registering a custom circuit get a model fitted against THAT
        # circuit (matching fpca.compile), not the default calibration.
        # Models passed in here are taken as default-CircuitParams
        # calibrations unless keyed by an explicit (circuit, n_pixels) tuple.
        default_circuit = CircuitParams()
        self._models: dict[tuple[CircuitParams, int], BucketCurvefitModel] = {}
        # keys that came in WITHOUT an explicit circuit: these are trusted
        # only for default-circuit programs (see CalibrationKeyError)
        self._implicitly_keyed: set[tuple[CircuitParams, int]] = set()
        if isinstance(model, BucketCurvefitModel):
            key = (default_circuit, model.n_pixels)
            self._models[key] = model
            self._implicitly_keyed.add(key)
        elif isinstance(model, dict):
            for k, v in model.items():
                key = k if isinstance(k, tuple) else (default_circuit, k)
                self._models[key] = v
                if not isinstance(k, tuple):
                    self._implicitly_keyed.add(key)
        self._configs: dict[str, ProgrammedConfig | ProgrammedModel] = {}
        # one CompiledFrontend per compile signature, all sharing one bounded
        # executable cache — reprogramming weights never recompiles, and the
        # total live-executable count stays bounded across configurations
        self._handles: dict[tuple, CompiledFrontend] = {}
        self._cache = ExecutableCache(cache_capacity)
        # channel-stacked (kernel, bn, program) per fan-out tuple: configs are
        # immutable once registered, so the concat is paid once, not per tick
        self._stacked: dict[
            tuple[str, ...], tuple[jax.Array, jax.Array, FPCAProgram]
        ] = {}
        # handle stats parent-chain into these cells; cache counters are
        # derived reads of self._cache — nothing is mirrored by hand
        self.stats = PipelineStats(cache=self._cache)

    # -- configuration registry ----------------------------------------------
    def register(
        self,
        name: str,
        spec: FPCASpec | FPCAProgram | FPCAModelProgram,
        kernel: jax.Array,
        bn_offset: jax.Array | None = None,
        *,
        head_params: Any | None = None,
    ) -> ProgrammedConfig | ProgrammedModel:
        """Program one FPCA configuration (idempotent per unique name).

        ``spec`` may be a bare :class:`FPCASpec` (wrapped into a program with
        this pipeline's adc/enc), a full :class:`repro.fpca.FPCAProgram`, or
        an :class:`repro.fpca.FPCAModelProgram` — a whole model (frontend +
        digital CNN head) whose trained ``head_params`` bind here the way the
        NVM ``kernel`` does.  Model configurations serve class *logits*
        through :meth:`serve`, stack channels with frontend configurations
        that share a compile signature, and get the skip-aware per-tick head
        in :class:`repro.serving.StreamServer`.
        """
        if name in self._configs:
            raise ValueError(f"config {name!r} already registered")
        c_o = int(kernel.shape[0])
        if isinstance(spec, FPCAModelProgram):
            if int(spec.out_channels) != c_o:
                raise ValueError(
                    f"kernel has {c_o} output channels; model program for "
                    f"{name!r} specifies {spec.out_channels}"
                )
            if head_params is None:
                raise ValueError(
                    f"model program {name!r} needs head_params= (the trained "
                    f"head pytree; see FPCAModelProgram.init_head)"
                )
            if bn_offset is None:
                bn_offset = jnp.zeros((c_o,), jnp.float32)
            mcfg = ProgrammedModel(
                name=name,
                model=spec,
                kernel=jnp.asarray(kernel, jnp.float32),
                bn_offset=jnp.asarray(bn_offset, jnp.float32),
                head_params=spec.bind_head_params(head_params),
            )
            self._configs[name] = mcfg
            return mcfg
        if head_params is not None:
            raise ValueError("head_params= needs an FPCAModelProgram")
        if isinstance(spec, FPCAProgram):
            if int(spec.out_channels) != c_o:
                raise ValueError(
                    f"kernel has {c_o} output channels; program for "
                    f"{name!r} specifies {spec.out_channels}"
                )
            program = spec
        else:
            program = FPCAProgram(
                spec=spec, adc=self.adc, enc=self.enc, out_channels=c_o
            )
        if bn_offset is None:
            bn_offset = jnp.zeros((c_o,), jnp.float32)
        cfg = ProgrammedConfig(
            name=name,
            program=program,
            kernel=jnp.asarray(kernel, jnp.float32),
            bn_offset=jnp.asarray(bn_offset, jnp.float32),
        )
        self._configs[name] = cfg
        return cfg

    @property
    def cache_size(self) -> int:
        return len(self._cache)

    def cache_info(self, verbose: bool = False):
        """Counters of the shared executable cache (all handles);
        ``verbose=True`` adds per-key hit/miss splits, LRU-ordered resident
        keys and the bounded eviction log."""
        return self._cache.info(verbose)

    def _model_for(self, program: FPCAProgram) -> BucketCurvefitModel:
        key = (program.circuit, program.spec.n_active_pixels)
        if key not in self._models:
            implicit_key = (CircuitParams(), key[1])
            if implicit_key in self._implicitly_keyed:
                raise CalibrationKeyError(
                    f"this pipeline holds a calibration for "
                    f"n_pixels={key[1]} passed as a plain "
                    f"BucketCurvefitModel (implicitly a default-CircuitParams "
                    f"calibration), but the program being served carries a "
                    f"custom CircuitParams — refusing to guess which physics "
                    f"it was fitted against.  Pass calibrations keyed "
                    f"explicitly as {{(circuit, n_pixels): model}}."
                )
            self._models[key] = fit_bucket_model(
                program.circuit, n_pixels=key[1]
            )
        return self._models[key]

    def handle_for(
        self, program: FPCAProgram | FPCASpec, out_channels: int | None = None
    ) -> CompiledFrontend:
        """The shared :class:`CompiledFrontend` serving one compile signature.

        Created lazily, keyed by ``program.signature()`` (a bare spec is
        wrapped with this pipeline's adc/enc); handles never hold weights
        (requests supply them per call through ``run_weighted``), so
        configurations sharing a signature genuinely share the executable.
        """
        if isinstance(program, FPCASpec):
            program = FPCAProgram(
                spec=program, adc=self.adc, enc=self.enc,
                out_channels=out_channels,
            )
        elif out_channels is not None and int(out_channels) != int(
            program.out_channels
        ):
            program = program.replace(out_channels=int(out_channels))
        key = program.signature()
        handle = self._handles.get(key)
        if handle is None:
            handle = CompiledFrontend(
                program,
                backend=self._backend,
                model=self._model_for(program),
                mesh=self.mesh,
                cache=self._cache,
                bucket_patience=self.bucket_patience,
                interpret=self.interpret,
                stats_parent=self.stats,
            )
            self._handles[key] = handle
        return handle

    def model_handle_for(self, model: FPCAModelProgram) -> CompiledModel:
        """The shared :class:`repro.fpca.CompiledModel` serving one model
        compile signature (lazily created, same dict as the frontend
        handles — model signatures extend frontend ones so the key spaces
        are disjoint by construction).  Handles hold no parameters; every
        call supplies the programmed NVM planes and head pytree."""
        key = model.signature()
        handle = self._handles.get(key)
        if handle is None:
            handle = CompiledModel(
                model,
                backend=self._backend,
                model=self._model_for(model.frontend),
                mesh=self.mesh,
                cache=self._cache,
                bucket_patience=self.bucket_patience,
                interpret=self.interpret,
                stats_parent=self.stats,
            )
            self._handles[key] = handle
        return handle  # type: ignore[return-value]

    def reset_bucket_state(self) -> None:
        """Forget all sticky row-bucket state (counters in ``stats`` remain).

        Benchmarks use this to make repeated serves of one scene evolve their
        bucket sequence identically (so a timed pass replays only executables
        the warm-up pass already compiled)."""
        for handle in self._handles.values():
            handle.reset_bucket_state()

    # -- scheduling ----------------------------------------------------------
    def group_requests(
        self, requests: Sequence[FrontendRequest]
    ) -> dict[str, list[int]]:
        """Request indices bucketed by configuration (insertion-ordered)."""
        groups: dict[str, list[int]] = {}
        for i, req in enumerate(requests):
            if req.config not in self._configs:
                raise KeyError(f"unknown config {req.config!r}")
            groups.setdefault(req.config, []).append(i)
        return groups

    def _run_batch(
        self,
        program: FPCAProgram,
        kernel: jax.Array,
        bn_offset: jax.Array,
        images: jax.Array,
        window_keep: np.ndarray | None = None,
        *,
        handle: CompiledFrontend | None = None,
        head_params: Any | None = None,
    ) -> jax.Array:
        """One fused handle call.  No counter mirroring happens here: the
        handle's stats cells are parent-chained into ``self.stats`` (handle
        ``runs`` land in ``batches``; window/launch/bucket/segment counters
        share names), and the cache counters are derived reads of the shared
        cache — the single-source fix for the old double-mirroring risk.

        With an explicit :class:`CompiledModel` ``handle`` (and its
        ``head_params``), the call serves class logits through the fused
        frontend+head executable instead of SS-ADC counts.
        """
        if handle is None:
            handle = self.handle_for(program, int(kernel.shape[0]))
        if head_params is not None:
            counts = handle.run_weighted(
                kernel, bn_offset, images, window_keep, head_params=head_params
            )
        else:
            counts = handle.run_weighted(kernel, bn_offset, images, window_keep)
        return counts

    def run_config_batch(
        self,
        name: str | Sequence[str],
        images: Any,
        window_keep: np.ndarray | None = None,
    ) -> jax.Array:
        """Non-blocking fused call for a frame batch of registered config(s).

        With a single config name, returns ``(b, h_o, w_o, c_o)`` SS-ADC
        counts, dispatched but not blocked on — the streaming server's
        double-buffered loop lives on this method.  ``window_keep`` rows
        belonging to skipped windows come back as exact zeros without having
        been computed.

        With a *sequence* of config names (multi-config fan-out: one camera
        feeding several programmed configurations), every named config must
        share the first one's :class:`FPCASpec`; their NVM weight planes are
        stacked along the channel axis and the whole fan-out runs as ONE
        fused call — the cross-config channel stacking of
        :meth:`_submit_merged`, reused per streaming tick.  Returns
        ``(b, h_o, w_o, sum(c_o))``; slice per-config channel ranges with
        :meth:`config_channel_slices`.
        """
        names = [name] if isinstance(name, str) else list(name)
        if not names:
            raise ValueError("need at least one config name")
        for n in names:
            if n not in self._configs:
                raise KeyError(f"unknown config {n!r}")
        cfgs = [self._configs[n] for n in names]
        spec = cfgs[0].spec
        for cfg in cfgs[1:]:
            if cfg.spec != spec:
                raise ValueError(
                    f"multi-config fan-out requires a shared spec: config "
                    f"{cfg.name!r} differs from {cfgs[0].name!r}"
                )
        images = jnp.asarray(images, jnp.float32)
        want = (spec.image_h, spec.image_w, spec.in_channels)
        if images.ndim != 4 or images.shape[1:] != want:
            raise ValueError(
                f"expected (b, {want[0]}, {want[1]}, {want[2]}) batch for "
                f"config {names[0]!r}, got {images.shape}"
            )
        if len(cfgs) == 1:
            cfg = cfgs[0]
            return self._run_batch(
                cfg.program, cfg.kernel, cfg.bn_offset, images, window_keep
            )
        kernel, bn, stacked_program = self._stacked_planes(names, cfgs)
        batches_before = self.stats.batches
        counts = self._run_batch(stacked_program, kernel, bn, images, window_keep)
        # a zero-kept tick short-circuits inside the handle: only count the
        # fan-outs that actually launched a stacked call
        self.stats.fanout_batches += self.stats.batches - batches_before
        return counts

    def run_config_segment(
        self,
        name: str,
        frames: Any,
        *,
        state: Any | None = None,
        gate: Any = _USE_PROGRAM,
        m_bucket: int | None = None,
        early_exit: int | None = None,
    ) -> SegmentResult:
        """Serve K streaming ticks of one registered configuration as ONE
        device-compiled segment (``jax.lax.scan`` — see
        :meth:`repro.fpca.CompiledFrontend.run_segment`).

        ``frames`` is ``(K, H, W, c_i)``; ``state`` threads the previous
        segment's :attr:`SegmentResult.state`.  Model configurations serve
        per-tick logits through the in-scan skip-aware head.  Handle
        counters (including the in-scan zero-kept launch skips and the
        ``segments`` / ``segment_ticks`` pair) land in ``stats`` through the
        parent chain — single-sourced, never mirrored.
        """
        cfg = self._configs.get(name)
        if cfg is None:
            raise KeyError(f"unknown config {name!r}")
        if isinstance(cfg, ProgrammedModel):
            handle: CompiledFrontend = self.model_handle_for(cfg.model)
        else:
            handle = self.handle_for(cfg.program, int(cfg.kernel.shape[0]))
        kwargs: dict[str, Any] = dict(
            state=state, gate=gate, m_bucket=m_bucket, early_exit=early_exit
        )
        if isinstance(cfg, ProgrammedModel):
            seg = handle.run_segment_weighted(
                cfg.kernel, cfg.bn_offset, frames,
                head_params=cfg.head_params, **kwargs,
            )
        else:
            seg = handle.run_segment_weighted(
                cfg.kernel, cfg.bn_offset, frames, **kwargs
            )
        return seg

    def _stacked_planes(
        self, names: Sequence[str], cfgs: Sequence[ProgrammedConfig]
    ) -> tuple[jax.Array, jax.Array, FPCAProgram]:
        """Channel-stacked (kernel, bn, program) for one fan-out tuple.

        Cached per tuple — configs are immutable once registered, so the
        concat (and the compile-signature compatibility check: one stacked
        launch serves ONE adc/enc/circuit epilogue) is paid once, not per
        tick.
        """
        key = tuple(names)
        stacked = self._stacked.get(key)
        if stacked is None:
            base = cfgs[0].program.fanout_signature()
            for cfg in cfgs[1:]:
                if cfg.program.fanout_signature() != base:
                    raise ValueError(
                        f"multi-config fan-out requires a shared spec and "
                        f"compile signature (adc/enc/circuit): config "
                        f"{cfg.name!r} differs from {cfgs[0].name!r}"
                    )
            kernel = jnp.concatenate([c.kernel for c in cfgs], axis=0)
            stacked = self._stacked[key] = (
                kernel,
                jnp.concatenate([c.bn_offset for c in cfgs], axis=0),
                cfgs[0].program.replace(out_channels=int(kernel.shape[0])),
            )
        return stacked

    def config_channel_slices(
        self, names: Sequence[str]
    ) -> list[tuple[str, int, int]]:
        """Per-config ``(name, lo, hi)`` channel ranges of a stacked fan-out
        call (the channel order :meth:`run_config_batch` concatenates in)."""
        slices: list[tuple[str, int, int]] = []
        lo = 0
        for n in names:
            c_o = int(self._configs[n].kernel.shape[0])
            slices.append((n, lo, lo + c_o))
            lo += c_o
        return slices

    def _group_window_keep(
        self, cfg: ProgrammedConfig, reqs: list[FrontendRequest]
    ) -> np.ndarray | None:
        """Stacked per-window keep grid for a request group (None = dense)."""
        if all(r.block_mask is None for r in reqs):
            return None
        h_o, w_o = output_dims(cfg.spec)
        return np.stack(
            [
                active_window_mask(cfg.spec, r.block_mask)
                if r.block_mask is not None
                else np.ones((h_o, w_o), bool)
                for r in reqs
            ]
        )

    def _check_geometry(
        self, name: str, requests: Sequence[FrontendRequest], idxs: list[int]
    ) -> None:
        cfg = self._configs[name]
        want_shape = (cfg.spec.image_h, cfg.spec.image_w, cfg.spec.in_channels)
        for i in idxs:
            got = np.shape(requests[i].image)
            if got != want_shape:
                raise ValueError(
                    f"request {i}: frame shape {got} does not match config "
                    f"{name!r} sensor geometry {want_shape}"
                )

    def serve(self, requests: Sequence[FrontendRequest]) -> list[jax.Array]:
        """Serve a heterogeneous request mix; results in request order.

        Returns one SS-ADC count map ``(h_o, w_o, c_o)`` per request — or,
        for requests naming a **model** configuration
        (:class:`repro.fpca.ProgrammedModel`), the ``(n_classes,)`` class
        logits of the fused frontend+head executable.
        """
        with telemetry.span("serve"):
            results: list[jax.Array | None] = [None] * len(requests)
            groups = self.group_requests(requests)
            self.stats.requests += len(requests)
            merged: dict[tuple, list[str]] = {}
            for name in groups:
                cfg = self._configs[name]
                key = (
                    cfg.program.signature()
                    if self.cross_config_batching
                    else (name,)
                )
                merged.setdefault(key, []).append(name)
            for names in merged.values():
                if len(names) == 1:
                    self._submit_group(
                        names[0], groups[names[0]], requests, results
                    )
                else:
                    self._submit_merged(names, groups, requests, results)
            return results  # type: ignore[return-value]

    def submit(self, requests: Sequence[FrontendRequest]) -> list[jax.Array]:
        """Deprecation shim for :meth:`serve` (the pre-``repro.fpca`` name)."""
        warnings.warn(
            "FPCAPipeline.submit is deprecated; use FPCAPipeline.serve "
            "(same semantics) or compile an explicit handle via "
            "repro.fpca.compile",
            DeprecationWarning,
            stacklevel=2,
        )
        return self.serve(requests)

    def _submit_group(
        self,
        name: str,
        idxs: list[int],
        requests: Sequence[FrontendRequest],
        results: list,
    ) -> None:
        cfg = self._configs[name]
        self._check_geometry(name, requests, idxs)
        images = jnp.stack(
            [jnp.asarray(requests[i].image, jnp.float32) for i in idxs]
        )
        window_keep = self._group_window_keep(cfg, [requests[i] for i in idxs])
        dc = None
        if isinstance(cfg, ProgrammedModel):
            # whole-model config: ONE fused frontend+head jit -> logits
            counts = self._run_batch(
                cfg.program, cfg.kernel, cfg.bn_offset, images, window_keep,
                handle=self.model_handle_for(cfg.model),
                head_params=cfg.head_params,
            )
            dc = cfg.model.detect_classes
        else:
            counts = self._run_batch(
                cfg.program, cfg.kernel, cfg.bn_offset, images, window_keep
            )
        for j, i in enumerate(idxs):
            results[i] = (
                Detections.from_raw(counts[j], dc)
                if dc is not None
                else counts[j]
            )

    def _submit_merged(
        self,
        names: list[str],
        groups: dict[str, list[int]],
        requests: Sequence[FrontendRequest],
        results: list,
    ) -> None:
        """Cross-config batching: configs sharing a compile signature run as
        ONE call with their NVM weight planes stacked along the channel axis;
        each request's counts are sliced from its config's channel range.

        Model configurations stack exactly like frontend ones (the stacked
        launch serves the shared analog epilogue); their digital heads then
        run per config on the sliced channel range — each request of a model
        config resolves to class logits, bit-identical to serving that
        config alone.
        """
        cfgs = [self._configs[n] for n in names]
        for name in names:
            self._check_geometry(name, requests, groups[name])
        kernel, bn, program = self._stacked_planes(names, cfgs)
        idxs = [i for n in names for i in groups[n]]
        images = jnp.stack(
            [jnp.asarray(requests[i].image, jnp.float32) for i in idxs]
        )
        window_keep = self._group_window_keep(
            cfgs[0], [requests[i] for i in idxs]
        )
        counts = self._run_batch(program, kernel, bn, images, window_keep)
        self.stats.merged_groups += 1
        offsets = np.cumsum([0] + [int(c.kernel.shape[0]) for c in cfgs])
        row = 0
        for g, (name, cfg) in enumerate(zip(names, cfgs)):
            lo, hi = int(offsets[g]), int(offsets[g + 1])
            rows = groups[name]
            if isinstance(cfg, ProgrammedModel):
                handle = self.model_handle_for(cfg.model)
                logits = handle.head_logits(
                    counts[row : row + len(rows), ..., lo:hi],
                    head_params=cfg.head_params,
                )
                dc = cfg.model.detect_classes
                for j, i in enumerate(rows):
                    results[i] = (
                        Detections.from_raw(logits[j], dc)
                        if dc is not None
                        else logits[j]
                    )
                row += len(rows)
            else:
                for i in rows:
                    results[i] = counts[row, ..., lo:hi]
                    row += 1
