"""``compile()`` and :class:`CompiledFrontend` — the explicit executable
handle of the unified FPCA API.

The paper's programming model, as an API contract::

    program = FPCAProgram(spec=FPCASpec(...))      # what to fabricate-free
    fe = fpca.compile(program, backend="basis")    # compile the array once
    fe.reprogram(kernel)                           # cheap NVM rewrite
    counts = fe.run(batch)                         # fused serving call
    fe.reprogram(other_kernel)                     # STILL zero recompiles
    for result in fe.stream(frames):               # delta-gated streaming
        ...

``compile()`` fits (or accepts) the calibrated bucket model, resolves the
backend from the registry and returns a handle that owns everything that
used to be implicit module / scheduler state: the bounded LRU of jitted
executables (introspectable via :meth:`CompiledFrontend.cache_info`), the
sticky region-skip row buckets, batch padding + mesh sharding, and the
executed-window accounting (:attr:`CompiledFrontend.stats`).

Reprogramming is guaranteed recompile-free because weights enter every
executable *traced* while the cache key is the program's
:meth:`~repro.fpca.FPCAProgram.signature` (which excludes weights by
construction) — asserted by the API test suite via ``cache_info()``.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Iterable, Iterator

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import gating
from repro.core.curvefit import BucketCurvefitModel, fit_bucket_model
from repro.core.mapping import FPCASpec, active_window_mask, output_dims
from repro.fpca import telemetry
from repro.fpca.backends import (
    Backend,
    data_parallel,
    default_backend_name,
    get_backend,
)
from repro.fpca.cache import CacheInfo, CacheInfoVerbose, ExecutableCache
from repro.fpca.program import FPCAProgram
from repro.kernels.fpca_conv.ops import StickyBucket, segment_bucket
from repro.launch.mesh import data_axes, data_extent

__all__ = [
    "FrontendStats",
    "SegmentState",
    "SegmentResult",
    "CompiledFrontend",
    "CompiledModel",
    "compile",
]

_USE_PROGRAM = object()   # stream() sentinel: "inherit from program"

# Model-side workload accounting, broken out per zoo architecture (the
# ``arch`` stamp on FPCAModelProgram; "custom" for hand-rolled programs).
# These are fleet-global labeled families — fleet_report()'s "workloads"
# table and the Prometheus render split classifier vs detection vs event
# traffic from them without any per-handle plumbing.
_C_MODEL_RUNS = telemetry.registry().counter(
    "fpca_model_runs_total",
    "model-side executable dispatches (fused, patched or segment)",
    ("arch",), max_label_sets=64,
)
_C_MODEL_FRAMES = telemetry.registry().counter(
    "fpca_model_frames_total",
    "frames/ticks served by model-side dispatches",
    ("arch",), max_label_sets=64,
)


class FrontendStats(telemetry.StatsView):
    """Per-handle serving counters (all monotonic) — thin views over
    :mod:`repro.fpca.telemetry` registry cells.

    Fields (in ``snapshot()`` order):

    * ``runs``              — fused executable invocations
    * ``reprograms``        — NVM weight rewrites
    * ``windows_total``     — windows submitted (incl. batch padding)
    * ``windows_executed``  — windows that actually reached the kernel
    * ``launches_skipped``  — all-skipped ticks that launched no kernel
      (per-tick short-circuits AND in-scan zero-kept ticks of compiled
      segments)
    * ``bucket_switches``   — served bucket-size transitions
    * ``bucket_shrinks_deferred`` — flap events sticky hysteresis absorbed
    * ``segments``          — device-compiled segment launches
    * ``segment_ticks``     — ticks served from inside those launches
    * ``h2d_bytes``         — ``nbytes`` of the host arrays a weighted call
      copies to the device (a host image batch, the keep grid)

    When the handle is owned by a :class:`repro.serving.FPCAPipeline` the
    cells are parent-chained into the pipeline's ``PipelineStats`` (same
    field names), so every increment lands in exactly one place and the
    fleet totals can never drift from the per-handle counters.
    """

    _PREFIX = "fpca_frontend"
    # fleet wiring: a handle run is one pipeline batch; reprograms stay
    # per-handle (no pipeline-level counterpart)
    _PARENT_MAP = {"runs": "batches", "reprograms": None}
    _FIELDS = (
        "runs",
        "reprograms",
        "windows_total",
        "windows_executed",
        "launches_skipped",
        "bucket_switches",
        "bucket_shrinks_deferred",
        "segments",
        "segment_ticks",
        "h2d_bytes",
    )


@dataclasses.dataclass
class SegmentState:
    """Carry threaded between :meth:`CompiledFrontend.run_segment` calls.

    The first four fields are the device-resident delta-gate state
    (:class:`repro.core.gating.GateCarry`); model segments add the effective
    activation map and previous logits.  ``suggested_bucket`` is a host-side
    hint — the compacted-row bucket the finished segment's kept counts size
    for the next one (:func:`repro.kernels.fpca_conv.ops.segment_bucket`).
    Treat instances as opaque: thread the ``state`` of one
    :class:`SegmentResult` into the next call.  Segments donate their carry
    by default, so the state passed in is dead after the call; running from
    it again raises ``ValueError`` (pass ``donate=False`` to keep it).
    """

    has_prev: Any
    prev_eff: Any
    age: Any
    frame_idx: Any
    eff: Any | None = None           # model segments: effective activation map
    logits: Any | None = None        # model segments: previous logits
    suggested_bucket: int | None = None

    def carry(self, model: bool) -> tuple:
        if any(
            isinstance(v, jax.Array) and v.is_deleted()
            for v in (self.has_prev, self.prev_eff, self.age, self.frame_idx,
                      self.eff, self.logits)
        ):
            raise ValueError(
                "this SegmentState was donated to an earlier run_segment "
                "call: thread the state that call returned, or pass "
                "donate=False to run from one state more than once"
            )
        c = (
            jnp.asarray(self.has_prev, bool),
            jnp.asarray(self.prev_eff, jnp.float32),
            jnp.asarray(self.age, jnp.int32),
            jnp.asarray(self.frame_idx, jnp.int32),
        )
        if model:
            if self.eff is None or self.logits is None:
                raise ValueError(
                    "model segment needs a state carrying (eff, logits) — "
                    "thread the state a CompiledModel.run_segment returned"
                )
            c += (
                jnp.asarray(self.eff, jnp.float32),
                jnp.asarray(self.logits, jnp.float32),
            )
        return c


@dataclasses.dataclass
class SegmentResult:
    """Outputs of one device-compiled streaming segment.

    Per-tick arrays span the full compiled ``length`` K; with early exit
    only the first ``ticks`` entries are meaningful (``counts`` rows past
    ``ticks`` are zeros, ``kept_windows`` zeros, masks False).  ``counts``
    (and ``logits``) stay unrealised device arrays so callers can overlap
    the next segment's host work; the small per-tick bookkeeping arrays are
    realised eagerly for stats and the boundary servo.
    """

    counts: Any                      # (K, h_o, w_o, c_o) device array
    block_masks: np.ndarray          # (K, bh, bw) bool
    kept_windows: np.ndarray         # (K,) int
    keyframes: np.ndarray            # (K,) bool
    rows_executed: np.ndarray        # (K,) int — compacted rows per tick
    ticks: int                       # ticks actually executed (== K, or fewer
    #                                  when early_exit stopped on a quiet scene)
    length: int                      # compiled segment length K
    first_frame_idx: int             # stream frame index of tick 0
    gated: bool
    state: SegmentState
    logits: Any | None = None        # model segments: (K,) + head_out_shape
    detect_classes: int | None = None  # detection segments: class count

    def detections(self) -> list:
        """Per-tick :class:`repro.models.heads.Detections` of a detection
        segment (first ``ticks`` entries; raises for classifier segments)."""
        if self.detect_classes is None:
            raise ValueError(
                "not a detection segment: this model's head emits logits"
            )
        from repro.models.heads import Detections

        raw = np.asarray(self.logits)[: self.ticks]
        return [Detections.from_raw(r, self.detect_classes) for r in raw]


def _round_up_pow2(n: int) -> int:
    return 1 << (n - 1).bit_length()


class CompiledFrontend:
    """An explicitly-held FPCA executable: one program, one backend, weights
    swappable without recompiling.

    Construct via :func:`compile`.  The handle is the unit every serving
    layer now composes over: :class:`repro.serving.FPCAPipeline` keeps one
    per distinct compile signature (sharing one :class:`ExecutableCache`),
    and :meth:`stream` gives single-camera continuous vision without any
    scheduler at all.
    """

    def __init__(
        self,
        program: FPCAProgram,
        *,
        backend: Backend,
        model: BucketCurvefitModel,
        mesh: jax.sharding.Mesh | None = None,
        cache: ExecutableCache | None = None,
        cache_capacity: int = 8,
        bucket_patience: int = 1,
        interpret: bool | None = None,
        stats_parent: telemetry.StatsView | None = None,
    ):
        if bucket_patience < 1:
            raise ValueError("bucket_patience must be >= 1")
        self.program = program
        self.backend = backend
        self.model = model
        self.mesh = mesh
        self.interpret = interpret
        self.bucket_patience = bucket_patience
        self._cache = cache if cache is not None else ExecutableCache(cache_capacity)
        self._sig = program.signature()
        self._sticky: dict[int, StickyBucket] = {}   # keyed by padded window count
        self._kernel: jax.Array | None = None
        self._bn: jax.Array | None = None
        # parent-chained when a pipeline owns the handle: shared-name fields
        # (windows_executed, launches_skipped, ...) single-source into the
        # pipeline's PipelineStats cells
        self.stats = FrontendStats(parent=stats_parent)

    # -- introspection -------------------------------------------------------
    @property
    def spec(self) -> FPCASpec:
        return self.program.spec

    @property
    def out_channels(self) -> int:
        return int(self.program.out_channels)

    @property
    def out_shape(self) -> tuple[int, int, int]:
        return self.program.out_shape

    @property
    def kernel(self) -> jax.Array | None:
        """Currently programmed NVM weights (None until :meth:`reprogram`)."""
        return self._kernel

    @property
    def bn_offset(self) -> jax.Array | None:
        return self._bn

    def signature(self) -> tuple:
        return self._sig

    def cache_info(self, verbose: bool = False) -> CacheInfo | CacheInfoVerbose:
        """LRU executable-cache counters (``hits/misses/evictions/currsize``).

        ``misses`` counts compiles: it must not move across
        :meth:`reprogram` — the field-programmability contract.
        ``verbose=True`` adds the per-signature hit/miss breakdown, the
        resident keys in LRU order, and the bounded eviction history."""
        return self._cache.info(verbose=verbose)

    def reset_bucket_state(self) -> None:
        """Forget sticky row-bucket state (counters in ``stats`` remain)."""
        self._sticky.clear()

    # -- programming ---------------------------------------------------------
    def reprogram(
        self, kernel: Any, bn_offset: Any | None = None
    ) -> "CompiledFrontend":
        """Rewrite the NVM weight planes (and BN offsets) in place.

        Guaranteed not to recompile: weights enter every executable traced,
        and the cache key is the program signature, which excludes them by
        construction.  Returns ``self`` so ``compile(...).reprogram(k)``
        chains.
        """
        kernel = jnp.asarray(kernel, jnp.float32)
        want = self.program.kernel_shape
        if tuple(kernel.shape) != want:
            raise ValueError(
                f"kernel shape {tuple(kernel.shape)} does not match program "
                f"kernel shape {want}"
            )
        if bn_offset is None:
            bn_offset = (
                self._bn
                if self._bn is not None
                else jnp.zeros((self.out_channels,), jnp.float32)
            )
        bn_offset = jnp.asarray(bn_offset, jnp.float32)
        if bn_offset.shape != (self.out_channels,):
            raise ValueError(
                f"bn_offset shape {tuple(bn_offset.shape)} != "
                f"({self.out_channels},)"
            )
        with telemetry.span("reprogram"):
            self._kernel = kernel
            self._bn = bn_offset
            self.stats.reprograms += 1
        return self

    # -- execution -----------------------------------------------------------
    def run(
        self,
        images: Any,
        *,
        block_mask: np.ndarray | None = None,
        window_keep: np.ndarray | None = None,
    ) -> jax.Array:
        """Serve one frame ``(H, W, c_i)`` or batch ``(B, H, W, c_i)``.

        ``block_mask`` is the §3.4.5 per-block keep grid (one grid applied
        to every frame, or a leading batch axis of grids); ``window_keep``
        is the already-derived per-window ``(B, h_o, w_o)`` boolean mask —
        pass at most one.  Skipped windows never execute on fused backends
        and come back as exact zeros.  Dispatch is non-blocking (jax async);
        the squeezed result mirrors the input's batchedness.
        """
        if self._kernel is None:
            raise RuntimeError(
                "no weights programmed: call reprogram(kernel) first "
                "(or pass weights= to compile())"
            )
        images = jnp.asarray(images, jnp.float32)
        squeeze = images.ndim == 3
        if squeeze:
            images = images[None]
        if block_mask is not None:
            if window_keep is not None:
                raise ValueError("pass block_mask or window_keep, not both")
            block_mask = np.asarray(block_mask)
            if block_mask.ndim == 2:
                keep = active_window_mask(self.spec, block_mask)
                window_keep = np.broadcast_to(
                    keep, (images.shape[0],) + keep.shape
                )
            else:
                window_keep = np.stack(
                    [active_window_mask(self.spec, m) for m in block_mask]
                )
        with telemetry.span("run"):
            counts = self.run_weighted(
                self._kernel, self._bn, images, window_keep
            )
        return counts[0] if squeeze else counts

    def run_weighted(
        self,
        kernel: jax.Array,
        bn_offset: jax.Array,
        images: jax.Array,
        window_keep: np.ndarray | None = None,
    ) -> jax.Array:
        """One fused executable call with explicit weights — the core
        dispatch every serving layer routes to.

        ``images`` is a ``(b, H, W, c_i)`` batch; ``window_keep`` an optional
        per-window ``(b, h_o, w_o)`` boolean keep grid.  The batch is padded
        to its pow-2 bucket (mesh-aligned), padding frames are masked out
        *in-kernel* whenever a keep grid is present, and the call is
        dispatched asynchronously — the returned array is unrealised, so
        callers can overlap host prep with device compute and block later.

        The weights are per-call state (this is what lets
        :class:`repro.serving.FPCAPipeline` serve many programmed
        configurations — including channel-stacked fan-outs — through
        signature-shared handles); :meth:`run` binds the handle's own
        programmed weights.
        """
        return self._dispatch_weighted(kernel, bn_offset, images, window_keep)

    def _dispatch_weighted(
        self,
        kernel: jax.Array,
        bn_offset: jax.Array,
        images: jax.Array,
        window_keep: np.ndarray | None = None,
        *,
        executable_for: Callable | None = None,
        extra: tuple = (),
        empty: Callable | None = None,
    ) -> jax.Array:
        """Shared padding / sharding / bucketing / accounting engine behind
        every weighted call.

        Hooks let :class:`CompiledModel` reuse the whole machinery with a
        fused frontend+head executable: ``executable_for(m_bucket)`` builds
        (or fetches) the jitted closure, ``extra`` is appended as traced call
        arguments (head parameters) before the window mask, and
        ``empty(b, h_o, w_o, c_o)`` produces the all-skipped short-circuit
        result (exact-zero counts for the frontend; head-on-zeros logits for
        a model).
        """
        executable_for = executable_for or self._executable
        spec = self.spec
        host_images = not isinstance(images, jax.Array)
        images = jnp.asarray(images, jnp.float32)
        if host_images:
            self.stats.h2d_bytes += images.nbytes
        want = (spec.image_h, spec.image_w, spec.in_channels)
        if images.ndim != 4 or images.shape[1:] != want:
            raise ValueError(
                f"expected (b, {want[0]}, {want[1]}, {want[2]}) batch, "
                f"got {images.shape}"
            )
        c_o = int(kernel.shape[0])
        if c_o != self.out_channels:
            raise ValueError(
                f"kernel has {c_o} output channels; this handle is compiled "
                f"for {self.out_channels}"
            )
        b = images.shape[0]
        h_o, w_o = output_dims(spec)
        if window_keep is not None and window_keep.shape != (b, h_o, w_o):
            raise ValueError(
                f"window_keep shape {window_keep.shape} != {(b, h_o, w_o)}"
            )
        padded = self._padded_batch(b)
        if padded > b:
            images = jnp.pad(images, ((0, padded - b), (0, 0), (0, 0), (0, 0)))
            if window_keep is not None:
                window_keep = np.concatenate(
                    [window_keep, np.zeros((padded - b, h_o, w_o), bool)]
                )
        m_total = padded * h_o * w_o
        self.stats.windows_total += m_total
        if window_keep is None:
            images = self._shard_batch(images)
            self.stats.runs += 1
            run = executable_for(None)
            self.stats.windows_executed += m_total
            return run(images, kernel, bn_offset, *extra)[:b]
        # on a mesh each device compacts its own batch shard, so the row
        # bucket is sized for the busiest shard
        n_shards = self.data_parallelism
        m_shard = m_total // n_shards
        kept = np.count_nonzero(window_keep.reshape(n_shards, -1), axis=1)
        if not kept.any():
            # all-skipped tick: the frontend result is exact zeros by
            # contract, so no kernel launches at all (0 executed windows in
            # the stats); the sticky bucket still counts the tick as
            # under-full so a stale large bucket shrinks on the first active
            # tick after the lull
            self.stats.launches_skipped += 1
            sticky = self._sticky.get(m_shard)
            if sticky is not None:
                sticky.observe_idle()
            if empty is not None:
                return empty(b, h_o, w_o, c_o)
            return jnp.zeros((b, h_o, w_o, c_o), jnp.float32)
        images = self._shard_batch(images)
        self.stats.runs += 1
        m_bucket = self._bucket_for(int(kept.max()), m_shard)
        run = executable_for(m_bucket)
        self.stats.windows_executed += m_bucket * n_shards
        if isinstance(window_keep, np.ndarray):
            self.stats.h2d_bytes += window_keep.nbytes
        window_keep = self._shard_batch(jnp.asarray(window_keep))
        return run(images, kernel, bn_offset, *extra, window_keep)[:b]

    def stream(
        self,
        frames: Iterable[Any],
        *,
        gate: Any = _USE_PROGRAM,
        controller: Any = _USE_PROGRAM,
        depth: int = 2,
        stream_id: str = "stream0",
    ) -> Iterator[Any]:
        """Serve a continuous frame stream through this handle.

        The single-camera counterpart of
        :class:`repro.serving.StreamServer`: each frame steps a temporal
        delta gate (defaults to ``program.gate``; pass an explicit
        ``gate=None`` for a dense readout even on a gated program),
        optionally servoed by a closed-loop threshold controller (defaults
        to ``program.controller``; explicit ``None`` disables), and the
        resulting keep mask is compacted in-kernel.  Up to ``depth`` ticks
        stay in flight (dispatch is non-blocking), results yield strictly in
        frame order as :class:`repro.serving.streaming.StreamFrameResult`.
        """
        import collections as _collections

        from repro.serving.control import GateController
        from repro.serving.streaming import StreamFrameResult, StreamSession

        if depth < 1:
            raise ValueError("depth must be >= 1")
        gate = self.program.gate if gate is _USE_PROGRAM else gate
        cconf = (
            self.program.controller
            if controller is _USE_PROGRAM
            else controller
        )
        ctl = (
            GateController(cconf, self.spec, gate.threshold, name=stream_id)
            if (cconf is not None and gate is not None)
            else None
        )
        session = StreamSession(stream_id, "__compiled__", self.spec, gate,
                                controller=ctl)
        self._stream_session = session   # introspectable (controller history)
        h_o, w_o = output_dims(self.spec)

        def _finalize(entry: dict) -> StreamFrameResult:
            return StreamFrameResult(
                stream_id=stream_id,
                frame_idx=entry["frame_idx"],
                counts=np.asarray(entry["counts"])[0],   # blocks until ready
                block_mask=entry["block_mask"],
                kept_windows=entry["kept"],
                total_windows=h_o * w_o,
                config="__compiled__",
                **self._stream_extra_results(entry),
            )

        inflight: _collections.deque[dict] = _collections.deque()
        state: dict = {}   # per-ITERATOR stream state (e.g. the model's
        #                    effective activation map) — two concurrent
        #                    stream() iterators must never share it
        span_fields = {"stream": stream_id}  # prebuilt: no per-tick churn
        for frame in frames:
            with telemetry.span("serve_tick", span_fields):
                frame = np.asarray(frame, np.float32)
                frame_idx = session.frame_idx
                block = session.step(frame)
                window = (
                    session.last_window_mask if gate is not None else None
                )
                kept = int(window.sum()) if window is not None else h_o * w_o
                entry = {
                    "frame_idx": frame_idx, "block_mask": block, "kept": kept
                }
                entry.update(self._stream_launch(frame, window, state))
            inflight.append(entry)
            while len(inflight) > depth:
                yield _finalize(inflight.popleft())
        while inflight:
            yield _finalize(inflight.popleft())

    def _stream_launch(
        self, frame: np.ndarray, window: np.ndarray | None, state: dict
    ) -> dict:
        """Dispatch one stream tick (non-blocking); returns entry fields.

        ``state`` is private to one ``stream()`` iterator.
        :class:`CompiledModel` overrides this to patch kept-window
        activations into the iterator's effective activation map and launch
        the digital head on top.
        """
        counts = self.run_weighted(
            self._require_weights(), self._bn, jnp.asarray(frame)[None],
            None if window is None else window[None],
        )
        return {"counts": counts}

    def _stream_extra_results(self, entry: dict) -> dict:
        """Extra ``StreamFrameResult`` fields realised from a tick entry."""
        return {}

    # -- device-compiled segments --------------------------------------------
    def run_segment(
        self,
        frames: Any,
        *,
        length: int | None = None,
        state: SegmentState | None = None,
        gate: Any = _USE_PROGRAM,
        m_bucket: int | None = None,
        early_exit: int | None = None,
        donate: bool = True,
    ) -> SegmentResult:
        """Serve ``K`` streaming ticks as ONE device-compiled program.

        The whole per-tick loop of :meth:`stream` — delta gate, hysteresis
        ages, keyframe cadence, kept-window compaction, zero-kept
        short-circuit — runs inside a single ``jax.lax.scan`` launch, so
        tick latency is kernel-bound instead of dispatch-bound.  Outputs are
        bit-identical, tick for tick, to the per-tick Python loop on CPU
        (the differential harness in ``tests/test_segment_parity.py`` pins
        this across backends); on a TPU counts and gate decisions stay
        identical and head logits agree to f32 rounding.

        Args:
          frames: ``(K, H, W, c_i)`` stack; ``K`` is static per compiled
            executable, so serve a stream in fixed-length chunks.
          length: optional assertion that ``K`` matches the planned segment
            length (chunking bugs fail loudly instead of recompiling).
          state: the previous segment's :attr:`SegmentResult.state`; ``None``
            starts a fresh stream (first tick keyframes, like the host loop).
          gate: ``DeltaGateConfig`` for this segment (default: the
            program's; explicit ``None`` = dense readout).  The threshold
            enters traced — a boundary servo retunes it for the next segment
            without recompiling.
          m_bucket: static compacted-row bucket for non-keyframe ticks
            (keyframes and busier ticks take the masked-dense branch).
            Default: the state's ``suggested_bucket`` from the previous
            segment, dense for the first.
          early_exit: stop after this many consecutive all-skipped ticks
            (``lax.while_loop`` variant); ``result.ticks`` reports how far
            the segment got — feed the remaining frames to the next call.
          donate: donate the carry buffers (previous frame / ages / previous
            logits) to the device call, so ``state`` is dead afterwards;
            pass ``False`` to run from one state more than once.
        """
        return self.run_segment_weighted(
            self._require_weights(), self._bn, frames,
            length=length, state=state, gate=gate, m_bucket=m_bucket,
            early_exit=early_exit, donate=donate,
        )

    def run_segment_weighted(
        self,
        kernel: jax.Array,
        bn_offset: jax.Array,
        frames: Any,
        *,
        length: int | None = None,
        state: SegmentState | None = None,
        gate: Any = _USE_PROGRAM,
        m_bucket: int | None = None,
        early_exit: int | None = None,
        donate: bool = True,
    ) -> SegmentResult:
        """:meth:`run_segment` with explicit weights (the serving-layer
        entry point — weights enter traced, so reprogramming between
        segments never recompiles)."""
        return self._dispatch_segment(
            kernel, bn_offset, frames, length=length, state=state, gate=gate,
            m_bucket=m_bucket, early_exit=early_exit, donate=donate,
            head_params=None,
        )

    def _dispatch_segment(self, *args: Any, **kwargs: Any) -> SegmentResult:
        if not telemetry.enabled():
            return self._dispatch_segment_inner(*args, **kwargs)
        with telemetry.span("run_segment",
                            {"model": kwargs.get("head_params") is not None}):
            return self._dispatch_segment_inner(*args, **kwargs)

    def _dispatch_segment_inner(
        self,
        kernel: jax.Array,
        bn_offset: jax.Array,
        frames: Any,
        *,
        length: int | None,
        state: SegmentState | None,
        gate: Any,
        m_bucket: int | None,
        early_exit: int | None,
        donate: bool,
        head_params: Any | None,
    ) -> SegmentResult:
        spec = self.spec
        frames = jnp.asarray(frames, jnp.float32)
        want = (spec.image_h, spec.image_w, spec.in_channels)
        if frames.ndim != 4 or frames.shape[1:] != want:
            raise ValueError(
                f"expected (K, {want[0]}, {want[1]}, {want[2]}) frame stack, "
                f"got {frames.shape}"
            )
        K = int(frames.shape[0])
        if K < 1:
            raise ValueError("need at least one frame")
        if length is not None and int(length) != K:
            raise ValueError(
                f"length={length} does not match the {K}-frame stack"
            )
        c_o = int(kernel.shape[0])
        if c_o != self.out_channels:
            raise ValueError(
                f"kernel has {c_o} output channels; this handle is compiled "
                f"for {self.out_channels}"
            )
        gate = self.program.gate if gate is _USE_PROGRAM else gate
        gated = gate is not None
        h_o, w_o = output_dims(spec)
        M = h_o * w_o
        bh, bw = gating.block_grid(spec)
        is_model = head_params is not None
        if gated:
            if m_bucket is None:
                m_bucket = (
                    state.suggested_bucket
                    if state is not None and state.suggested_bucket
                    else M
                )
            m_bucket = max(1, min(int(m_bucket), M))
        else:
            m_bucket = None
        if early_exit is not None:
            early_exit = int(early_exit)
            if early_exit < 1:
                raise ValueError("early_exit patience must be >= 1")
            if not gated:
                raise ValueError("early_exit requires a gated segment")
        run = self._segment_executable(
            K, m_bucket, gated, early_exit, bool(donate), model=is_model
        )
        if state is None:
            state = self._fresh_segment_state(
                gate.hysteresis if gated else 0, is_model
            )
        carry = state.carry(is_model)
        first_idx = int(state.frame_idx)
        args: list = [frames, kernel, bn_offset]
        if is_model:
            args.append(head_params)
        if gated:
            args.append((
                jnp.asarray(gate.threshold, jnp.float32),
                jnp.asarray(gate.hysteresis, jnp.int32),
                jnp.asarray(gate.keyframe_interval, jnp.int32),
            ))
        args.append(carry)
        outs, new_carry = run(*args)
        # the per-tick bookkeeping is realised eagerly (it feeds stats and
        # the boundary servo); counts/logits stay lazy for overlap
        ticks = int(outs["ticks"])
        if gated:
            kept = np.asarray(outs["kept"], np.int64)
            keyframes = np.asarray(outs["keyframe"], bool)
            block_masks = np.asarray(outs["block_keep"], bool)
            rows = np.where(kept == 0, 0, np.where(kept > m_bucket, M, m_bucket))
            rows[ticks:] = 0
            suggested = segment_bucket(kept[:ticks], M, keyframes[:ticks])
        else:
            kept = np.full(K, M, np.int64)
            keyframes = np.zeros(K, bool)
            block_masks = np.ones((K, bh, bw), bool)
            rows = np.full(K, M, np.int64)
            suggested = None
        new_state = SegmentState(*new_carry[:4])
        if is_model:
            new_state.eff, new_state.logits = new_carry[4], new_carry[5]
        new_state.suggested_bucket = suggested
        detect_classes = (
            self.model_program.detect_classes if is_model else None
        )
        self.stats.runs += 1
        self.stats.segments += 1
        self.stats.segment_ticks += ticks
        self.stats.windows_total += ticks * M
        self.stats.windows_executed += int(rows[:ticks].sum())
        if gated:
            self.stats.launches_skipped += int((kept[:ticks] == 0).sum())
        return SegmentResult(
            counts=outs["counts"],
            block_masks=block_masks,
            kept_windows=kept,
            keyframes=keyframes,
            rows_executed=rows,
            ticks=ticks,
            length=K,
            first_frame_idx=first_idx,
            gated=gated,
            state=new_state,
            logits=outs.get("logits"),
            detect_classes=detect_classes,
        )

    def _fresh_segment_state(
        self, hysteresis: int, is_model: bool
    ) -> SegmentState:
        st = SegmentState(*gating.init_gate_carry(self.spec, hysteresis))
        if is_model:
            h_o, w_o = output_dims(self.spec)
            st.eff = jnp.zeros((h_o, w_o, self.out_channels), jnp.float32)
            # head_out_shape generalises (n_classes,) to detection maps
            st.logits = jnp.zeros(
                self.model_program.head_out_shape, jnp.float32
            )
        return st

    def _segment_executable(
        self,
        K: int,
        m_bucket: int | None,
        gated: bool,
        early_exit: int | None,
        donate: bool,
        *,
        model: bool = False,
    ) -> Callable:
        mb_key = m_bucket
        if mb_key is not None and not self.backend.bucket_sensitive:
            mb_key = -1
        key = self.signature() + (
            self.backend.name, "segment", K, mb_key, gated, early_exit,
            donate, model,
        )

        def build() -> Callable:
            return self.backend.instrumented(
                self.backend.make_segment_executable(
                    self.model,
                    spec=self.spec,
                    adc=self.program.adc,
                    enc=self.program.enc,
                    interpret=self.interpret,
                    length=K,
                    gated=gated,
                    m_bucket=m_bucket,
                    model_program=self.model_program if model else None,
                    early_exit=early_exit,
                    donate=donate,
                ),
                site="segment",
            )

        return self._cache.get(key, build)

    @property
    def data_parallelism(self) -> int:
        """Devices the fused batch shards over (1 = unsharded single device).

        The batch-carrying extent of the compiled mesh — what
        :meth:`_padded_batch` rounds the launch up to and what the fleet
        weak-scaling bench sweeps (`benchmarks/fleet_bench.py`).  Gate state
        never shards: it stays host-local per stream.
        """
        return 1 if self.mesh is None else data_extent(self.mesh)

    # -- internals -----------------------------------------------------------
    def _require_weights(self) -> jax.Array:
        if self._kernel is None:
            raise RuntimeError(
                "no weights programmed: call reprogram(kernel) first"
            )
        return self._kernel

    def _padded_batch(self, b: int) -> int:
        padded = _round_up_pow2(b)
        if self.mesh is not None:
            n_data = data_extent(self.mesh)
            padded = -(-padded // n_data) * n_data
        return padded

    def _shard_batch(self, images: jax.Array) -> jax.Array:
        if self.mesh is None:
            return images
        P = jax.sharding.PartitionSpec
        sharding = jax.sharding.NamedSharding(
            self.mesh, P(data_axes(self.mesh), *([None] * (images.ndim - 1)))
        )
        return jax.device_put(images, sharding)

    def _frontend_transfer(self) -> str:
        """Bucket-transfer lowering the frontend executables serve: "int8"
        for a precision="int8" model program on a quant_transfer backend
        (so streaming counts match the fused model jit's frontend stage),
        "f32" everywhere else — frontend-only handles included."""
        mp = getattr(self, "model_program", None)
        if (
            mp is not None
            and mp.precision == "int8"
            and self.backend.quant_transfer
        ):
            return "int8"
        return "f32"

    def _executable(self, m_bucket: int | None) -> Callable:
        # bucket-insensitive backends (dense eval + post-hoc mask) serve
        # every bucket size with one executable: collapse the key so sticky
        # bucket transitions don't churn the shared LRU with identical jits
        if m_bucket is not None and not self.backend.bucket_sensitive:
            m_bucket = -1
        transfer = self._frontend_transfer()
        key = self._sig + (self.backend.name, m_bucket, transfer, self.mesh)

        def build() -> Callable:
            # a FRESH jitted closure per signature: its compiled programs are
            # owned by the closure, so LRU eviction genuinely frees the
            # executable (a shared module-level jit cache would keep them
            # alive).
            kw = {"transfer": transfer} if transfer != "f32" else {}
            run = self.backend.make_executable(
                self.model,
                spec=self.spec,
                adc=self.program.adc,
                enc=self.program.enc,
                interpret=self.interpret,
                m_bucket=m_bucket,
                **kw,
            )
            if self.mesh is not None:
                run = data_parallel(run, self.mesh)
            return self.backend.instrumented(run, site="frontend")

        return self._cache.get(key, build)

    def _bucket_for(self, n_keep: int, m_total: int) -> int:
        """Sticky row bucket for one (handle, window-count) batch shape.

        With ``bucket_patience=1`` this is exactly
        :func:`repro.kernels.fpca_conv.ops.window_bucket`, but bucket
        transitions are still counted — ``stats.bucket_switches`` is the
        flap count a hysteresis-free server pays.
        """
        sticky = self._sticky.get(m_total)
        if sticky is None:
            sticky = self._sticky[m_total] = StickyBucket(self.bucket_patience)
        before = (sticky.switches, sticky.shrinks_deferred)
        m_bucket = sticky.bucket(n_keep, m_total)
        self.stats.bucket_switches += sticky.switches - before[0]
        self.stats.bucket_shrinks_deferred += sticky.shrinks_deferred - before[1]
        return m_bucket


class CompiledModel(CompiledFrontend):
    """An explicitly-held multi-layer model executable: analog frontend +
    digital CNN head behind one handle.

    Construct via :func:`compile` on an
    :class:`repro.fpca.FPCAModelProgram`.  Everything the frontend handle
    owns is reused — the shared bounded executable LRU, sticky region-skip
    buckets, batch padding + mesh sharding, executed-window stats — but:

    * :meth:`run` returns class **logits**: the head is fused into the same
      jit as the frontend (one dispatch per batch), bit-identical to
      composing a frontend handle with
      :meth:`~repro.fpca.FPCAModelProgram.apply_head`;
    * :meth:`reprogram` rewrites NVM planes AND/OR head parameters — both
      enter every executable traced, so neither ever recompiles;
    * :meth:`stream` is **skip-aware**: each delta-gated tick patches the
      kept-window activations into the previous *effective activation map*
      and runs the head on the patched map, so a stream of mostly-skipped
      ticks still yields a class decision per tick (an all-skipped tick
      reproduces the previous logits exactly).
    """

    def __init__(
        self,
        model_program: "FPCAModelProgram",
        *,
        head_params: Any | None = None,
        **kw: Any,
    ):
        from repro.fpca.program import FPCAModelProgram

        if not isinstance(model_program, FPCAModelProgram):
            raise TypeError(
                f"expected FPCAModelProgram, got {type(model_program)}"
            )
        super().__init__(model_program.frontend, **kw)
        self.model_program = model_program
        self._model_sig = model_program.signature()
        self._head_params: Any | None = None
        # arch-labeled workload cells (zoo stamp; "custom" off-registry)
        self.arch = model_program.arch or "custom"
        self._m_runs = _C_MODEL_RUNS.labels(arch=self.arch)
        self._m_frames = _C_MODEL_FRAMES.labels(arch=self.arch)
        if head_params is not None:
            self.reprogram(head_params=head_params)

    # -- introspection -------------------------------------------------------
    @property
    def n_classes(self) -> int:
        return self.model_program.n_classes

    @property
    def head_out_shape(self) -> tuple[int, ...]:
        return self.model_program.head_out_shape

    @property
    def output_kind(self) -> str:
        return self.model_program.output_kind

    @property
    def detect_classes(self) -> int | None:
        return self.model_program.detect_classes

    @property
    def head_params(self) -> Any | None:
        """Currently programmed head parameters (None until programmed)."""
        return self._head_params

    def signature(self) -> tuple:
        """The MODEL signature (extends the frontend's; golden-pinned)."""
        return self._model_sig

    def frontend_signature(self) -> tuple:
        return self._sig

    # -- programming ---------------------------------------------------------
    def reprogram(
        self,
        kernel: Any | None = None,
        bn_offset: Any | None = None,
        *,
        head_params: Any | None = None,
    ) -> "CompiledModel":
        """Rewrite NVM weight planes, BN offsets and/or the head pytree.

        Any side may be updated alone (a ``bn_offset``-only rewrite reuses
        the currently programmed kernel); everything enters every executable
        traced, so — like the frontend contract — reprogramming never
        recompiles (asserted via ``cache_info()`` in the test suite).
        """
        if kernel is None and bn_offset is None and head_params is None:
            raise ValueError(
                "reprogram needs kernel, bn_offset and/or head_params"
            )
        if kernel is not None:
            super().reprogram(kernel, bn_offset)
        elif bn_offset is not None:
            super().reprogram(self._require_weights(), bn_offset)
        if head_params is not None:
            if kernel is None and bn_offset is None:
                # head-only rewrite: the base reprogram (and its span) did
                # not run, so count and trace it here
                with telemetry.span("reprogram"):
                    self._head_params = self.model_program.bind_head_params(
                        head_params
                    )
                    self.stats.reprograms += 1
            else:
                self._head_params = self.model_program.bind_head_params(
                    head_params
                )
        return self

    def _require_head(self) -> Any:
        if self._head_params is None:
            raise RuntimeError(
                "no head parameters programmed: call "
                "reprogram(head_params=...) first (or pass head_params= to "
                "compile())"
            )
        return self._head_params

    # -- execution -----------------------------------------------------------
    def run(
        self,
        images: Any,
        *,
        block_mask: np.ndarray | None = None,
        window_keep: np.ndarray | None = None,
    ) -> Any:
        """Serve one frame or batch through the fused frontend+head jit.

        Classifiers return logits ``(n_classes,)`` / ``(B, n_classes)``;
        detection models return :class:`repro.models.heads.Detections`
        (scores + boxes split lazily from the raw per-cell map)."""
        out = super().run(
            images, block_mask=block_mask, window_keep=window_keep
        )
        dc = self.detect_classes
        if dc is not None:
            from repro.models.heads import Detections

            return Detections.from_raw(out, dc)
        return out

    def run_weighted(
        self,
        kernel: jax.Array,
        bn_offset: jax.Array,
        images: jax.Array,
        window_keep: np.ndarray | None = None,
        *,
        head_params: Any | None = None,
    ) -> jax.Array:
        """One fused frontend+head call -> ``(b,) + head_out_shape`` raw
        outputs (logits, or per-cell detection maps for a detection head —
        :meth:`run` wraps those in :class:`repro.models.heads.Detections`).

        Routed through the same padding / sharding / sticky-bucket engine as
        the frontend handle; the executable itself is the backend's
        :meth:`~repro.fpca.Backend.make_model_executable` closure (ONE jit).
        An all-skipped batch short-circuits the frontend launch and serves
        the head on the exact-zero activation map instead.
        """
        hp = self._require_head() if head_params is None else head_params
        self._m_runs.add(1)
        self._m_frames.add(int(np.shape(images)[0]))

        def empty(b: int, h_o: int, w_o: int, c_o: int) -> jax.Array:
            zeros = jnp.zeros((b, h_o, w_o, c_o), jnp.float32)
            return self._head_executable()(hp, zeros)

        return self._dispatch_weighted(
            kernel, bn_offset, images, window_keep,
            executable_for=lambda m: self._model_executable(m),
            extra=(hp,),
            empty=empty,
        )

    def run_frontend_weighted(
        self,
        kernel: jax.Array,
        bn_offset: jax.Array,
        images: jax.Array,
        window_keep: np.ndarray | None = None,
    ) -> jax.Array:
        """The frontend stage alone (SS-ADC counts) — what the streaming
        paths use before the skip-aware head patch.  Executables are keyed
        by the FRONTEND signature, so they are shared with plain frontend
        handles on the same cache."""
        return self._dispatch_weighted(kernel, bn_offset, images, window_keep)

    def head_logits(self, counts: Any, head_params: Any | None = None) -> jax.Array:
        """Digital head on an explicit activation map (non-blocking)."""
        hp = self._require_head() if head_params is None else head_params
        self._m_runs.add(1)
        return self._head_executable()(hp, jnp.asarray(counts, jnp.float32))

    def patched_logits(
        self,
        counts: Any,
        prev_eff: Any,
        window_keep: Any,
        head_params: Any | None = None,
    ) -> tuple[jax.Array, jax.Array]:
        """Skip-aware head step: patch kept windows of ``counts`` into
        ``prev_eff`` and run the head on the patched map.

        Returns ``(logits, effective)`` — callers carry ``effective``
        forward as the next tick's ``prev_eff``.  One jitted closure (shared
        LRU), dispatched asynchronously.
        """
        hp = self._require_head() if head_params is None else head_params
        self._m_runs.add(1)
        self._m_frames.add(int(np.shape(counts)[0]))
        return self._patch_executable()(
            hp,
            jnp.asarray(counts, jnp.float32),
            jnp.asarray(prev_eff, jnp.float32),
            jnp.asarray(window_keep),
        )

    def fused_patched_logits(
        self,
        head_params_rows: Any,
        counts: Any,
        prev_eff: Any,
        window_keep: Any,
    ) -> tuple[jax.Array, jax.Array]:
        """Shared-head fusion: ONE vmapped patch+head pass over stacked
        per-config rows, each row binding its OWN head parameters
        (``head_params_rows`` is the per-row pytree stack, leading axis ==
        ``counts.shape[0]``).

        Row-for-row bit-identical to per-config :meth:`patched_logits`
        calls — every op in the patch body and the head is row-independent,
        the same contract the segment parity harness already pins for the
        in-scan head — asserted by the fused-vs-unfused parity test.
        """
        self._m_runs.add(1)
        self._m_frames.add(int(np.shape(counts)[0]))
        return self._fused_patch_executable()(
            head_params_rows,
            jnp.asarray(counts, jnp.float32),
            jnp.asarray(prev_eff, jnp.float32),
            jnp.asarray(window_keep),
        )

    # -- device-compiled segments --------------------------------------------
    def run_segment_weighted(
        self,
        kernel: jax.Array,
        bn_offset: jax.Array,
        frames: Any,
        *,
        head_params: Any | None = None,
        length: int | None = None,
        state: "SegmentState | None" = None,
        gate: Any = _USE_PROGRAM,
        m_bucket: int | None = None,
        early_exit: int | None = None,
        donate: bool = True,
    ) -> "SegmentResult":
        """Model variant of :meth:`CompiledFrontend.run_segment_weighted`:
        the per-tick head pass (skip-aware effective-map patch + logits) runs
        inside the scan, carrying the previous effective map and logits on
        the device.  ``result.logits`` is ``(K,) + head_out_shape`` (class
        logits, or raw per-cell maps — ``result.detections()`` splits
        those)."""
        hp = self._require_head() if head_params is None else head_params
        seg = self._dispatch_segment(
            kernel, bn_offset, frames, length=length, state=state, gate=gate,
            m_bucket=m_bucket, early_exit=early_exit, donate=donate,
            head_params=hp,
        )
        self._m_runs.add(1)
        self._m_frames.add(seg.ticks)
        return seg

    # -- streaming -----------------------------------------------------------
    def _stream_launch(
        self, frame: np.ndarray, window: np.ndarray | None, state: dict
    ) -> dict:
        h_o, w_o = output_dims(self.spec)
        counts = self.run_frontend_weighted(
            self._require_weights(), self._bn, jnp.asarray(frame)[None],
            None if window is None else window[None],
        )
        # the effective activation map lives in the ITERATOR's state, never
        # on the handle: concurrent stream() iterators stay independent
        prev = state.get("eff")
        if prev is None:
            prev = jnp.zeros((1, h_o, w_o, self.out_channels), jnp.float32)
        keep = (
            np.ones((1, h_o, w_o), bool) if window is None else window[None]
        )
        logits, eff = self.patched_logits(counts, prev, keep)
        state["eff"] = eff
        return {"counts": counts, "logits": logits}

    def _stream_extra_results(self, entry: dict) -> dict:
        lg = np.asarray(entry["logits"])[0]
        out: dict = {"logits": lg}
        dc = self.detect_classes
        if dc is not None:
            from repro.models.heads import Detections

            out["detections"] = Detections.from_raw(lg, dc)
        return out

    # -- internals -----------------------------------------------------------
    def _model_executable(self, m_bucket: int | None) -> Callable:
        if m_bucket is not None and not self.backend.bucket_sensitive:
            m_bucket = -1
        key = self._model_sig + (self.backend.name, "model", m_bucket, self.mesh)

        def build() -> Callable:
            return self.backend.instrumented(
                self.backend.make_model_executable(
                    self.model_program,
                    self.model,
                    interpret=self.interpret,
                    m_bucket=m_bucket,
                    mesh=self.mesh,
                ),
                site="model",
            )

        return self._cache.get(key, build)

    def _head_executable(self) -> Callable:
        key = self._model_sig + ("head",)
        head = self.model_program.apply_head

        def build() -> Callable:
            @jax.jit
            def run(head_params, counts):
                return head(head_params, counts)

            return self.backend.instrumented(run, site="head")

        return self._cache.get(key, build)

    def _patch_executable(self) -> Callable:
        key = self._model_sig + ("head-patch",)
        head = self.model_program.apply_head

        def build() -> Callable:
            @jax.jit
            def run(head_params, counts, prev_eff, window_keep):
                eff = jnp.where(window_keep[..., None], counts, prev_eff)
                return head(head_params, eff), eff

            return self.backend.instrumented(run, site="head_patch")

        return self._cache.get(key, build)

    def _fused_patch_executable(self) -> Callable:
        key = self._model_sig + ("head-patch-fused",)
        head = self.model_program.apply_head

        def build() -> Callable:
            def one(hp, c, pe, wk):
                eff = jnp.where(wk[..., None], c, pe)
                return head(hp, eff[None])[0], eff

            run = jax.jit(jax.vmap(one))
            return self.backend.instrumented(run, site="head_patch_fused")

        return self._cache.get(key, build)


def compile(  # noqa: A001  (torch.compile-style public name)
    program: FPCAProgram | FPCASpec,
    *,
    backend: str | Backend | None = None,
    mesh: jax.sharding.Mesh | None = None,
    weights: Any | None = None,
    bn_offset: Any | None = None,
    head_params: Any | None = None,
    model: BucketCurvefitModel | None = None,
    cache: ExecutableCache | None = None,
    cache_capacity: int = 8,
    bucket_patience: int = 1,
    interpret: bool | None = None,
    stats_parent: Any | None = None,
) -> CompiledFrontend:
    """Compile an :class:`FPCAProgram` into a held executable handle.

    An :class:`repro.fpca.FPCAModelProgram` (frontend + digital CNN head)
    compiles to a :class:`CompiledModel` whose ``.run()`` serves class
    logits through ONE fused jit; ``head_params`` then programs the trained
    head the way ``weights`` programs the NVM planes.

    Args:
      program: the validated program spec (a bare :class:`FPCASpec` is
        wrapped in a default program for convenience; an
        :class:`FPCAModelProgram` yields a :class:`CompiledModel`).
      backend: registered backend name (see
        :func:`repro.fpca.available_backends`) or a :class:`Backend`
        instance; ``None`` auto-selects by platform (Pallas on TPU, the XLA
        basis form elsewhere).
      mesh: optional ``jax.sharding.Mesh`` — batches shard over its data
        axes and batch padding rounds up to the data-axis extent; each
        device runs the frontend on its own shard
        (:func:`repro.fpca.backends.data_parallel`).
      weights / bn_offset: optionally program the NVM planes immediately
        (equivalent to calling :meth:`CompiledFrontend.reprogram`).
      model: fitted :class:`BucketCurvefitModel`; fitted on demand from
        ``program.circuit`` when omitted (a one-off ~seconds calibration, as
        a deployment would run once).
      cache: share a bounded :class:`ExecutableCache` across handles (the
        pipeline does this to bound total live executables); a private cache
        of ``cache_capacity`` otherwise.
      bucket_patience: sticky-bucket hysteresis for region-skip row buckets
        (``1`` = stateless).
      interpret: forwarded to Pallas (default: interpret off-TPU).
      stats_parent: optional :class:`repro.fpca.telemetry.StatsView` whose
        same-named cells receive every increment of the handle's stats
        (how ``FPCAPipeline`` single-sources its fleet totals).
    """
    from repro.fpca.program import FPCAModelProgram

    if isinstance(program, FPCASpec):
        program = FPCAProgram(spec=program)
    is_model = isinstance(program, FPCAModelProgram)
    if not is_model and not isinstance(program, FPCAProgram):
        raise TypeError(
            f"expected FPCAProgram, FPCAModelProgram or FPCASpec, "
            f"got {type(program)}"
        )
    if head_params is not None and not is_model:
        raise ValueError("head_params= needs an FPCAModelProgram")
    frontend = program.frontend if is_model else program
    be = get_backend(backend if backend is not None else default_backend_name())
    with telemetry.span("compile", {"backend": be.name, "model": is_model}):
        if model is None:
            model = fit_bucket_model(
                frontend.circuit, n_pixels=frontend.spec.n_active_pixels
            )
        common = dict(
            backend=be,
            model=model,
            mesh=mesh,
            cache=cache,
            cache_capacity=cache_capacity,
            bucket_patience=bucket_patience,
            interpret=interpret,
            stats_parent=stats_parent,
        )
        if is_model:
            handle: CompiledFrontend = CompiledModel(
                program, head_params=head_params, **common
            )
        else:
            handle = CompiledFrontend(program, **common)
        if weights is not None:
            handle.reprogram(weights, bn_offset)
    return handle
