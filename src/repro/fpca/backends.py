"""Pluggable execution backends for the FPCA frontend.

Replaces the string-literal dispatch that used to live inside
:func:`repro.core.fpca_sim.fpca_forward` with a registry: each
:class:`Backend` names one way of evaluating a programmed array and carries
the two entry points the rest of the stack needs —

* ``conv``            — one-shot batched forward (what ``fpca_forward``
  dispatches fused backends through);
* ``make_executable`` — a factory returning a *fresh* jitted
  ``(images, kernel, bn_offset[, window_mask]) -> counts`` closure whose
  compiled programs die with it.  This is what
  :class:`repro.fpca.CompiledFrontend` holds in its bounded LRU cache, so a
  serving host genuinely bounds live executables by dropping references.

Built-ins (registered at import):

* ``"reference"`` — the dense jnp simulation (every mode, the only
  differentiable path; the parity oracle).  Its executables serve the same
  calibrated bucket-sigmoid + hard-ADC semantics as the fused backends, so
  backends are interchangeable behind one :class:`CompiledFrontend`.
* ``"pallas"``    — the fused TPU kernel (``interpret=True`` off-TPU;
  validation only there).  On a mesh it runs per device under
  :func:`data_parallel`.
* ``"basis"``     — the identical basis-expanded matmul-bank math lowered
  through XLA — the fast deployment path on non-TPU hosts.

Third parties register with the decorator::

    @register_backend("mysim", description="in-house RTL cosim")
    def _mysim_executable(model, *, spec, adc, enc, interpret=None,
                          m_bucket=None):
        ...return a (images, kernel, bn_offset[, window_mask]) callable...
"""

from __future__ import annotations

import dataclasses
from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core import gating
from repro.core.adc import ADCConfig, updown_readout
from repro.core.curvefit import BucketCurvefitModel
from repro.core.fpca_sim import WeightEncoding, _analog_read, encode_weights, extract_windows
from repro.core.mapping import FPCASpec, output_dims
from repro.launch.mesh import data_axes

__all__ = [
    "Backend",
    "register_backend",
    "get_backend",
    "available_backends",
    "default_backend_name",
    "data_parallel",
]


@dataclasses.dataclass(frozen=True)
class Backend:
    """One registered execution backend.

    ``fused`` marks backends that serve the calibrated bucket-sigmoid model
    with hard ADC rounding through a single fused call (deployment-mode
    serving of the sensor model); non-fused backends run the dense
    simulation and may be differentiable.
    """

    name: str
    make_executable: Callable
    conv: Callable | None = None
    fused: bool = True
    differentiable: bool = False
    # whether executables differ per region-skip row bucket (m_bucket).
    # Fused kernels compile one program per bucket size; backends that
    # evaluate densely and mask post-hoc (the reference oracle) serve every
    # bucket with one executable, so caches can collapse the key.
    bucket_sensitive: bool = True
    # whether make_executable accepts transfer="int8" — the quantised
    # bucket-transfer LUT of precision="int8" model programs.  Backends
    # without it keep serving the f32 frontend under int8 models (the
    # reference backend stays the f32-frontend oracle the parity harness
    # bounds against); only flag backends whose factory takes the kwarg.
    quant_transfer: bool = False
    description: str = ""

    def instrumented(self, fn: Callable, *, site: str) -> Callable:
        """Wrap a jitted closure with the opt-in device-profile hooks
        (:func:`repro.fpca.telemetry.instrument_launch`): launch counting,
        ``jax.profiler.TraceAnnotation`` tagging and rate-limited
        ``block_until_ready`` device-time sampling, all labeled
        ``{site, backend}``.  :class:`repro.fpca.CompiledFrontend` routes
        every cache-built executable through this, so third-party backends
        registered via :func:`register_backend` are covered uniformly.
        Disabled-mode cost is one ``is None`` check per call."""
        from repro.fpca.telemetry import instrument_launch

        return instrument_launch(fn, site=site, backend=self.name)

    def make_model_executable(
        self,
        model_program,                      # repro.fpca.FPCAModelProgram
        bucket_model: "BucketCurvefitModel",
        *,
        interpret: bool | None = None,
        m_bucket: int | None = None,
        mesh: jax.sharding.Mesh | None = None,
    ) -> Callable:
        """A fresh jitted **whole-model** executable: frontend + digital head
        in ONE jit.

        The frontend stage is this backend's :attr:`make_executable` closure
        (inlined into the trace — still the registry-dispatched kernel math);
        the head is :meth:`repro.fpca.FPCAModelProgram.apply_head` lowered as
        plain jnp ops, so the fused outputs are bit-identical to composing a
        frontend handle with the reference head apply.  Signature:
        ``(images, kernel, bn_offset, head_params) -> head outputs`` — class
        logits for chain heads, any ``head_out_shape`` for zoo head graphs
        (e.g. per-cell detection maps) — with a trailing ``window_mask``
        argument when ``m_bucket`` is set (the region-skip compacted path;
        skipped windows enter the head as exact zeros).  Head parameters
        enter traced, so reprogramming them — like NVM weights — never
        recompiles.

        A ``precision="int8"`` model program selects the quantised head
        lowering through ``apply_head`` (same dispatch, traced quant
        pytree); on :attr:`quant_transfer` backends the frontend stage also
        serves the int8 bucket-transfer LUT.

        With a ``mesh`` the frontend stage runs per device under
        :func:`data_parallel` and the head partitions over the same batch
        shards.
        """
        kw = {}
        if self.quant_transfer and model_program.precision == "int8":
            kw["transfer"] = "int8"
        frontend = self.make_executable(
            bucket_model,
            spec=model_program.frontend.spec,
            adc=model_program.frontend.adc,
            enc=model_program.frontend.enc,
            interpret=interpret,
            m_bucket=m_bucket,
            **kw,
        )
        if mesh is not None:
            frontend = data_parallel(frontend, mesh)
        head = model_program.apply_head

        if m_bucket is None:

            @jax.jit
            def run(images, kernel, bn_offset, head_params):
                return head(head_params, frontend(images, kernel, bn_offset))

        else:

            @jax.jit
            def run(images, kernel, bn_offset, head_params, window_mask):
                return head(
                    head_params, frontend(images, kernel, bn_offset, window_mask)
                )

        return run

    def make_segment_executable(
        self,
        bucket_model: "BucketCurvefitModel",
        *,
        spec: FPCASpec,
        adc: ADCConfig | None = None,
        enc: WeightEncoding | None = None,
        interpret: bool | None = None,
        length: int,
        gated: bool = True,
        m_bucket: int | None = None,
        model_program=None,                 # repro.fpca.FPCAModelProgram
        early_exit: int | None = None,
        donate: bool = False,
    ) -> Callable:
        """A fresh jitted **segment** executable: ``length`` streaming ticks
        rolled into ONE device program (``jax.lax.scan``), the delta gate /
        hysteresis / keyframe state machine living in the carry.

        Per tick the body steps the gate (:mod:`repro.core.gating` — the
        same jnp numerics the host loop evaluates, so keep/skip decisions
        compare identical bits), derives the per-window keep grid and routes
        the frame through this backend's :attr:`make_executable` closures:

        * zero kept windows  -> exact zeros, no kernel math at all;
        * ``n_keep > m_bucket`` (keyframes, busy scenes) -> the masked dense
          variant (post-hoc zero mask — the existing dense-fallback path);
        * otherwise          -> the ``m_bucket``-compacted variant (static
          ``jnp.nonzero`` gather; the servo picks the bucket *between*
          segments so it stays trace-friendly inside the scan).

        With ``model_program`` the digital head is fused in: each tick
        patches kept windows into the carried effective activation map and
        runs the head on the patched map (an all-skipped tick reproduces
        the carried previous logits bit-exactly).  With ``early_exit=p`` the
        scan becomes a ``lax.while_loop`` that stops after ``p`` consecutive
        all-skipped ticks (quiescent scene) and reports ``ticks`` executed.

        Signature of the returned closure (gate knobs and all parameters
        enter traced — reprogramming and boundary servo steps never
        recompile)::

            run(frames, kernel, bn_offset[, head_params][, gate_args], carry)
              -> (outs, new_carry)

        where ``gate_args = (threshold f32, hysteresis i32, interval i32)``
        is present iff ``gated``; ``carry`` is the flat gate-state tuple
        (plus ``(eff, logits)`` for models) and ``outs`` maps ``counts``,
        ``block_keep``, ``kept``, ``keyframe``, ``ticks`` (and ``logits``).
        The head slot of the carry is shape-generic: chain heads carry
        ``(n_classes,)`` logits, zoo head graphs whatever
        ``FPCAModelProgram.head_out_shape`` says (per-cell detection maps
        included) — the per-tick ``outs["logits"]`` stacks ``K`` of them.
        ``donate=True`` donates the carry buffers (previous frame / ages /
        previous logits) to the next segment: the carry passed in is dead
        after the call.
        """
        adc = adc or ADCConfig()
        enc = enc or WeightEncoding()
        K = int(length)
        if K < 1:
            raise ValueError("segment length must be >= 1")
        h_o, w_o = output_dims(spec)
        M = h_o * w_o
        bh, bw = gating.block_grid(spec)
        head = model_program.apply_head if model_program is not None else None
        if early_exit is not None and not gated:
            raise ValueError("early_exit requires a gated segment")

        common = dict(
            spec=spec, adc=adc, enc=enc, interpret=interpret
        )
        if (
            self.quant_transfer
            and model_program is not None
            and model_program.precision == "int8"
        ):
            # int8 model segments serve the quantised bucket transfer in
            # every in-scan frontend branch, matching the fused model jit
            common["transfer"] = "int8"
        if not gated:
            mb = None
            fe_dense = self.make_executable(bucket_model, m_bucket=None, **common)
            fe_masked = fe_compact = None
        else:
            mb = M if m_bucket is None else max(1, min(int(m_bucket), M))
            fe_dense = None
            fe_masked = self.make_executable(bucket_model, m_bucket=M, **common)
            fe_compact = (
                self.make_executable(bucket_model, m_bucket=mb, **common)
                if mb < M and self.bucket_sensitive
                else None
            )

        def tick(kernel, bn_offset, head_params, gate_args, carry, frame):
            gate_carry = gating.GateCarry(*carry[:4])
            if gated:
                thr, hyst, ki = gate_args
                cur = gating.effective_frame(frame, spec)
                gate_carry, keep, keyframe = gating.gate_tick(
                    spec, gate_carry, cur, thr, hyst, ki
                )
                window = gating.window_mask_from_blocks(keep, spec)
                n_keep = jnp.sum(window).astype(jnp.int32)
            else:
                keep = jnp.ones((bh, bw), bool)
                keyframe = jnp.zeros((), bool)
                n_keep = jnp.asarray(M, jnp.int32)
                window = None
                gate_carry = gating.GateCarry(
                    gate_carry.has_prev,
                    gate_carry.prev_eff,
                    gate_carry.age,
                    gate_carry.frame_idx + 1,
                )
            c_o = kernel.shape[0]

            def compute(_):
                if not gated:
                    return fe_dense(frame[None], kernel, bn_offset)
                if fe_compact is None:
                    return fe_masked(frame[None], kernel, bn_offset, window[None])
                return jax.lax.cond(
                    n_keep > mb,
                    lambda __: fe_masked(
                        frame[None], kernel, bn_offset, window[None]
                    ),
                    lambda __: fe_compact(
                        frame[None], kernel, bn_offset, window[None]
                    ),
                    None,
                )

            if gated:
                # the zero-kept branch reproduces the host loop's
                # launch short-circuit: exact zeros, no kernel math
                counts = jax.lax.cond(
                    n_keep == 0,
                    lambda _: jnp.zeros((1, h_o, w_o, c_o), jnp.float32),
                    compute,
                    None,
                )[0]
            else:
                counts = compute(None)[0]
            outs = {
                "counts": counts,
                "block_keep": keep,
                "kept": n_keep,
                "keyframe": keyframe,
            }
            if head is None:
                return tuple(gate_carry), outs
            eff_prev, logits_prev = carry[4], carry[5]
            if gated:

                def quiet_head(_):
                    return eff_prev, logits_prev

                def live_head(_):
                    eff = jnp.where(window[..., None], counts, eff_prev)
                    return eff, head(head_params, eff[None])[0]

                eff, logits = jax.lax.cond(
                    n_keep == 0, quiet_head, live_head, None
                )
            else:
                eff = counts
                logits = head(head_params, eff[None])[0]
            outs["logits"] = logits
            return tuple(gate_carry) + (eff, logits), outs

        def scan_run(frames, kernel, bn_offset, head_params, gate_args, carry):
            def body(c, frame):
                return tick(kernel, bn_offset, head_params, gate_args, c, frame)

            carry, outs = jax.lax.scan(body, carry, frames)
            outs["ticks"] = jnp.asarray(K, jnp.int32)
            return outs, carry

        def while_run(frames, kernel, bn_offset, head_params, gate_args, carry):
            patience = int(early_exit)
            c_o = kernel.shape[0]
            outs0 = {
                "counts": jnp.zeros((K, h_o, w_o, c_o), jnp.float32),
                "block_keep": jnp.zeros((K, bh, bw), bool),
                "kept": jnp.zeros((K,), jnp.int32),
                "keyframe": jnp.zeros((K,), bool),
            }
            if head is not None:
                outs0["logits"] = jnp.zeros(
                    (K,) + tuple(carry[5].shape), jnp.float32
                )

            def cond_fn(state):
                t, quiet, _, __ = state
                return jnp.logical_and(t < K, quiet < patience)

            def body_fn(state):
                t, quiet, c, outs = state
                frame = jax.lax.dynamic_index_in_dim(
                    frames, t, axis=0, keepdims=False
                )
                c, o = tick(kernel, bn_offset, head_params, gate_args, c, frame)
                outs = {k: outs[k].at[t].set(o[k]) for k in outs}
                quiet = jnp.where(o["kept"] == 0, quiet + 1, 0)
                return t + 1, quiet, c, outs

            t, _, carry, outs = jax.lax.while_loop(
                cond_fn,
                body_fn,
                (jnp.zeros((), jnp.int32), jnp.zeros((), jnp.int32), carry, outs0),
            )
            outs["ticks"] = t
            return outs, carry

        inner = while_run if early_exit is not None else scan_run

        if gated and head is not None:

            def run(frames, kernel, bn_offset, head_params, gate_args, carry):
                return inner(frames, kernel, bn_offset, head_params, gate_args, carry)

            donate_idx = 5
        elif gated:

            def run(frames, kernel, bn_offset, gate_args, carry):
                return inner(frames, kernel, bn_offset, None, gate_args, carry)

            donate_idx = 4
        elif head is not None:

            def run(frames, kernel, bn_offset, head_params, carry):
                return inner(frames, kernel, bn_offset, head_params, None, carry)

            donate_idx = 4
        else:

            def run(frames, kernel, bn_offset, carry):
                return inner(frames, kernel, bn_offset, None, None, carry)

            donate_idx = 3
        if donate:
            return jax.jit(run, donate_argnums=(donate_idx,))
        return jax.jit(run)


def data_parallel(frontend: Callable, mesh: jax.sharding.Mesh) -> Callable:
    """Run a frontend executable on every device of ``mesh``, each on its
    own shard of the batch.

    Mosaic kernels cannot be partitioned by the compiler, so a mesh-served
    frontend runs under ``shard_map``: images (and the window mask, when
    the executable takes one) split along the mesh's data axes, weights are
    replicated, and each device runs the unmodified executable on its rows.
    The kernel math is row-independent, so no collective is needed and no
    device ever sees another's windows.  A region-skip executable compacts
    within its shard, so its ``m_bucket`` counts the rows of one shard.
    ``check_vma`` is off because a Pallas call's output carries no
    per-axis variance annotation; every output is batch-sharded anyway.
    """
    batch = P(data_axes(mesh))

    def run(images, kernel, bn_offset, *window_mask):
        return jax.shard_map(
            frontend,
            mesh=mesh,
            in_specs=(batch, P(), P()) + (batch,) * len(window_mask),
            out_specs=batch,
            check_vma=False,
        )(images, kernel, bn_offset, *window_mask)

    return jax.jit(run)


_REGISTRY: dict[str, Backend] = {}


def register_backend(
    name: str,
    *,
    conv: Callable | None = None,
    fused: bool = True,
    differentiable: bool = False,
    bucket_sensitive: bool = True,
    quant_transfer: bool = False,
    description: str = "",
    overwrite: bool = False,
) -> Callable[[Callable], Callable]:
    """Decorator registering an executable factory as backend ``name``.

    The decorated callable must have the signature
    ``factory(model, *, spec, adc, enc, interpret=None, m_bucket=None)`` and
    return a jitted ``(images, kernel, bn_offset) -> counts`` closure —
    ``(images, kernel, bn_offset, window_mask)`` when ``m_bucket`` is set
    (the region-skip compacted serving path).  With
    ``quant_transfer=True`` the factory must additionally accept
    ``transfer="f32" | "int8"`` (the quantised bucket-transfer lowering of
    ``precision="int8"`` model programs); the kwarg is never passed to
    backends registered without it.
    """

    def deco(make_executable: Callable) -> Callable:
        if name in _REGISTRY and not overwrite:
            raise ValueError(f"backend {name!r} already registered")
        _REGISTRY[name] = Backend(
            name=name,
            make_executable=make_executable,
            conv=conv,
            fused=fused,
            differentiable=differentiable,
            bucket_sensitive=bucket_sensitive,
            quant_transfer=quant_transfer,
            description=description,
        )
        return make_executable

    return deco


def get_backend(name: str | Backend) -> Backend:
    """Resolve a backend by name (raises ``ValueError`` listing the options)."""
    if isinstance(name, Backend):
        return name
    try:
        return _REGISTRY[name]
    except KeyError:
        raise ValueError(
            f"unknown backend {name!r}; available: {available_backends()}"
        ) from None


def available_backends() -> tuple[str, ...]:
    return tuple(_REGISTRY)


def default_backend_name() -> str:
    """Platform auto-select: the Pallas kernel on TPU, the XLA basis form
    elsewhere (interpret-mode Pallas is validation-only, far too slow to
    serve)."""
    return "pallas" if jax.default_backend() == "tpu" else "basis"


# ---------------------------------------------------------------------------
# built-in backends
# ---------------------------------------------------------------------------


def _fused_conv(impl: str) -> Callable:
    def conv(
        images: jax.Array,
        kernel: jax.Array,
        model: BucketCurvefitModel,
        *,
        spec: FPCASpec,
        adc: ADCConfig,
        enc: WeightEncoding,
        bn_offset: jax.Array,
        interpret: bool | None = None,
        window_mask=None,
    ) -> jax.Array:
        from repro.kernels.fpca_conv.ops import fpca_conv

        return fpca_conv(
            images, kernel, model, spec=spec, adc=adc, enc=enc,
            bn_offset=bn_offset, impl=impl, interpret=interpret,
            window_mask=window_mask,
        )

    return conv


def _fused_factory(impl: str) -> Callable:
    def make_executable(
        model: BucketCurvefitModel,
        *,
        spec: FPCASpec,
        adc: ADCConfig | None = None,
        enc: WeightEncoding | None = None,
        interpret: bool | None = None,
        m_bucket: int | None = None,
        transfer: str = "f32",
    ) -> Callable:
        from repro.kernels.fpca_conv.ops import make_fpca_conv_executable

        return make_fpca_conv_executable(
            model, spec=spec, adc=adc, enc=enc, impl=impl,
            interpret=interpret, m_bucket=m_bucket, transfer=transfer,
        )

    return make_executable


register_backend(
    "pallas",
    conv=_fused_conv("pallas"),
    description="fused TPU Pallas kernel (interpret-mode off-TPU: validation only)",
)(_fused_factory("pallas"))

register_backend(
    "basis",
    conv=_fused_conv("basis"),
    quant_transfer=True,
    description="basis-expanded matmul-bank math lowered through XLA "
    "(fast serving path on non-TPU hosts)",
)(_fused_factory("basis"))


@register_backend(
    "reference",
    fused=False,
    differentiable=True,
    bucket_sensitive=False,   # dense eval + post-hoc mask: one jit serves all buckets
    description="dense jnp simulation (parity oracle; the only "
    "differentiable path)",
)
def _reference_executable(
    model: BucketCurvefitModel,
    *,
    spec: FPCASpec,
    adc: ADCConfig | None = None,
    enc: WeightEncoding | None = None,
    interpret: bool | None = None,
    m_bucket: int | None = None,
) -> Callable:
    """Dense-reference executable serving the same deployment semantics as
    the fused kernels (calibrated bucket-sigmoid model, hard ADC).

    The masked variant evaluates every window and zeroes skipped slots
    post-hoc — the bit-exact oracle the compacted fused paths are pinned
    against; no compute is saved (use a fused backend to serve).
    """
    del interpret  # dense jnp path: nothing to interpret
    adc = adc or ADCConfig()
    enc = enc or WeightEncoding()

    def _counts(images: jax.Array, kernel: jax.Array, bn_offset: jax.Array) -> jax.Array:
        w_pos, w_neg = encode_weights(kernel, spec, enc, hard=True)
        I = extract_windows(images, spec)
        n_active = spec.n_active_pixels
        v_pos = _analog_read(I, w_pos, "bucket_sigmoid", None, model, n_active)
        v_neg = _analog_read(I, w_neg, "bucket_sigmoid", None, model, n_active)
        return updown_readout(v_pos, v_neg, adc, bn_offset, hard=True)

    if m_bucket is None:

        @jax.jit
        def run(images, kernel, bn_offset):
            return _counts(images, kernel, bn_offset)

    else:

        @jax.jit
        def run(images, kernel, bn_offset, window_mask):
            counts = _counts(images, kernel, bn_offset)
            keep = jnp.reshape(window_mask, counts.shape[:-1])
            return counts * keep[..., None].astype(counts.dtype)

    return run
