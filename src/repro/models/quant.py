"""Quantised int8 lowering for digital heads (and the shared symmetric
int8 leaf numerics).

The FPCA frontend already emits hard-rounded SS-ADC integer counts; this
module makes the *digital* side match the extreme-edge deployment story:
per-channel symmetric int8 weights, int8 activations with statically
calibrated scales, int32 accumulation, and a requantise between stages —
selected via ``FPCAModelProgram(precision="int8")`` and flowing through
every compiled executable (fused run, pipeline serve, per-tick streaming,
``lax.scan`` segments).

Numerics contract (what the parity harness pins):

* **weights** — per-out-channel symmetric scales, ``s_w[c] =
  max|w[..., c]| / 127``; ``w_q = clip(round(w / s_w), -127, 127)``;
* **activations** — one symmetric scale per parameterized stage,
  calibrated from an f32 forward pass over sample counts (``s_x =
  max|x| / 127``); requantised at every stage input;
* **accumulation** — exact int8 x int8 -> int32.  On hosts without a
  native int8 GEMM the products ride *integer-valued f32 sgemm carriers*:
  each partial sum reduces at most :data:`_CHUNK` = 1024 terms, so its
  magnitude stays below ``1024 * 127 * 127 < 2**24`` — exactly
  representable in f32 — and partials are cast to int32 between chunks.
  This is bit-exact int8 semantics at sgemm speed (the same trick the
  basis backend's matmul bank uses for its int8 transfer LUT);
* **dequantise** — ``y = acc * (s_x * s_w) + b`` in f32, then the stage
  activation; pooling and joins run in f32 between stages.

Parity against the f32 reference is *bounded, not bit-exact*:
``tests/test_quant.py`` pins max logit divergence and top-1 agreement
across the dense / masked / zero-kept / bucket-edge grid.

The per-tensor leaf helpers (:func:`quantize_leaf_symmetric` /
:func:`dequantize_leaf`) are the single source of symmetric int8
numerics — :mod:`repro.training.compression` re-imports them for
gradient compression.
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

__all__ = [
    "check_int8_lowering",
    "quantize_symmetric",
    "quantize_leaf_symmetric",
    "dequantize_leaf",
    "quant_bank_dot",
    "linear_int8",
    "conv2d_int8",
    "calibrate_head_scales",
    "quantize_head_params",
    "bind_quant_head_params",
    "is_quantized_params",
    "apply_head_int8",
    "pack_act_scales",
    "unpack_act_scales",
    "logit_parity",
]

# Max reduction depth per f32-carrier partial sum: every partial stays
# below 1024 * 127 * 127 = 16 516 096 < 2**24, the largest contiguous
# integer range f32 represents exactly.
_CHUNK = 1024

_QUANT_KEYS = frozenset({"w_q", "w_scale", "b", "x_scale"})


# ---------------------------------------------------------------------------
# leaf numerics (shared with training/compression.py)
# ---------------------------------------------------------------------------

def quantize_symmetric(
    g: jax.Array, channel_axis: int | None = None
) -> tuple[jax.Array, jax.Array]:
    """Symmetric int8 quantisation of one tensor.

    ``channel_axis=None`` is the per-tensor form (one scalar scale — the
    gradient-compression numerics); an integer axis yields per-channel
    scales with ``keepdims`` shape, ready to divide/multiply in place.
    """
    if channel_axis is None:
        scale = jnp.maximum(jnp.max(jnp.abs(g)), 1e-12) / 127.0
    else:
        red = tuple(i for i in range(g.ndim) if i != channel_axis % g.ndim)
        scale = (
            jnp.maximum(jnp.max(jnp.abs(g), axis=red, keepdims=True), 1e-12)
            / 127.0
        )
    q = jnp.clip(jnp.round(g / scale), -127, 127).astype(jnp.int8)
    return q, scale


def quantize_leaf_symmetric(g: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Per-tensor symmetric int8 quantise: ``(q int8, scale f32 scalar)``."""
    return quantize_symmetric(g)


def dequantize_leaf(q: jax.Array, scale: jax.Array) -> jax.Array:
    """Inverse of :func:`quantize_leaf_symmetric` (f32)."""
    return q.astype(jnp.float32) * scale


# ---------------------------------------------------------------------------
# exact int8 matmul / conv on f32 carriers
# ---------------------------------------------------------------------------

def quant_bank_dot(x_q: jax.Array, w_q: jax.Array) -> jax.Array:
    """Exact ``int8 x int8 -> int32`` matmul through the f32 sgemm bank.

    ``x_q`` is an *integer-valued* f32 carrier in [-127, 127] (shape
    ``(..., K)``), ``w_q`` an int8 ``(K, N)`` plane.  Reduction is chunked
    to :data:`_CHUNK` terms so every f32 partial is exactly representable;
    partials accumulate in int32 across chunks.  This is the head-side
    counterpart of the basis backend's matmul-bank lowering: int8 semantics
    at f32-GEMM speed on hosts whose native int8 dot is slower than sgemm.
    """
    K, N = w_q.shape
    wf = w_q.astype(jnp.float32)
    dn = (((x_q.ndim - 1,), (0,)), ((), ()))
    if K <= _CHUNK:
        out = jax.lax.dot_general(
            x_q, wf, dn, preferred_element_type=jnp.float32
        )
        return out.astype(jnp.int32)
    n_chunks = -(-K // _CHUNK)
    pad = n_chunks * _CHUNK - K
    if pad:
        x_q = jnp.pad(x_q, [(0, 0)] * (x_q.ndim - 1) + [(0, pad)])
        wf = jnp.pad(wf, [(0, pad), (0, 0)])
    lead = x_q.shape[:-1]
    xs = jnp.moveaxis(
        x_q.reshape(lead + (n_chunks, _CHUNK)), -2, 0
    ).reshape((n_chunks, -1, _CHUNK))               # (n_chunks, M, _CHUNK)
    ws = wf.reshape(n_chunks, _CHUNK, N)
    # one chunk-batched sgemm (batch dim = chunk index), each f32 partial
    # exactly representable, then an int32 reduction over chunks — much
    # faster than a sequential lax.scan of small GEMMs, identical result
    parts = jax.lax.dot_general(
        xs, ws, (((2,), (1,)), ((0,), (0,))),
        preferred_element_type=jnp.float32,
    )                                               # (n_chunks, M, N)
    return parts.astype(jnp.int32).sum(axis=0).reshape(lead + (N,))


def _requant(x: jax.Array, x_scale: jax.Array) -> jax.Array:
    """Quantise an f32 activation to an integer-valued f32 int8 carrier."""
    return jnp.clip(jnp.round(x / x_scale), -127.0, 127.0)


def linear_int8(qp: dict, x: jax.Array) -> jax.Array:
    """Quantised biased dense stage: requantise -> int32 GEMM -> dequant."""
    acc = quant_bank_dot(_requant(x, qp["x_scale"]), qp["w_q"])
    return acc.astype(jnp.float32) * (qp["x_scale"] * qp["w_scale"]) + qp["b"]


def conv2d_int8(
    qp: dict, x: jax.Array, stride: int = 1, padding: str = "VALID"
) -> jax.Array:
    """Quantised NHWC convolution (weights ``(c_out, k, k, c_in)`` int8).

    The ``k*k*c_in`` reduction is chunked over input channels so each f32
    partial reduces at most :data:`_CHUNK` terms (same exactness argument
    as :func:`quant_bank_dot`); chunk partials accumulate in int32.
    """
    x_q = _requant(x, qp["x_scale"])
    w = qp["w_q"]
    k = int(w.shape[1])
    c_in = int(w.shape[3])
    chunk = max(1, _CHUNK // (k * k))
    acc = None
    for lo in range(0, c_in, chunk):
        part = jax.lax.conv_general_dilated(
            x_q[..., lo:lo + chunk].transpose(0, 3, 1, 2),
            w[:, :, :, lo:lo + chunk].astype(jnp.float32).transpose(0, 3, 1, 2),
            window_strides=(stride, stride),
            padding=padding,
            dimension_numbers=("NCHW", "OIHW", "NCHW"),
        ).transpose(0, 2, 3, 1).astype(jnp.int32)
        acc = part if acc is None else acc + part
    return acc.astype(jnp.float32) * (qp["x_scale"] * qp["w_scale"]) + qp["b"]


def check_int8_lowering(program) -> None:
    """Refuse a head this module cannot lower: a grouped (depthwise)
    convolution has no int8 path here, so ``precision="int8"`` on such a
    model fails at construction, naming the stage, instead of serving a
    lowering that ignores its groups."""
    from repro.fpca.program import ConvSpec

    if program.is_graph_head:
        stages = [(f"head node {n.name!r}", n.op) for n in program.head.nodes]
    else:
        stages = [(f"head[{i}]", op) for i, op in enumerate(program.head)]
    for where, op in stages:
        if isinstance(op, ConvSpec) and op.groups != 1:
            raise ValueError(
                f"{where}: grouped convolution (groups={op.groups}) has no "
                f"int8 lowering; serve this model with precision='f32'"
            )


# ---------------------------------------------------------------------------
# calibration
# ---------------------------------------------------------------------------

def _default_calib_counts(program) -> jax.Array:
    """Data-free calibration input: one full-scale SS-ADC count map (every
    count at ``levels - 1`` — the frontend's hard output ceiling)."""
    h_o, w_o, c_o = program.frontend.out_shape
    lv = float(program.frontend.adc.levels - 1)
    return jnp.full((1, h_o, w_o, c_o), lv, jnp.float32)


def _scale_of(x: jax.Array) -> float:
    return max(float(jnp.max(jnp.abs(x))), 1e-12) / 127.0


def calibrate_head_scales(program, params: Any, sample_counts: Any) -> Any:
    """Per-stage input activation scales from one f32 forward pass.

    ``params`` must be the *bound f32* head pytree.  Returns a list aligned
    with the chain stages (``None`` for parameterless stages), or a dict
    keyed by parameterized node name for graph heads.  Host-side — scales
    are concrete floats; they enter the quant pytree as traced f32 scalars
    (so :meth:`CompiledModel.reprogram` with freshly calibrated scales
    never recompiles).
    """
    from repro.fpca.program import (
        ConvSpec, DenseSpec, PoolSpec, _apply_activation,
    )
    from repro.models.layers import avg_pool2d, conv2d, linear, max_pool2d

    x = jnp.asarray(sample_counts, jnp.float32)
    if x.ndim == 3:
        x = x[None]
    x = x * jnp.float32(program.input_scale)
    if program.is_graph_head:
        return _calibrate_graph(program.head, params, x)
    scales: list[float | None] = []
    for layer, p in zip(program.head, params):
        if isinstance(layer, ConvSpec):
            scales.append(_scale_of(x))
            x = _apply_activation(
                layer.activation, conv2d(p, x, layer.stride, layer.padding)
            )
        elif isinstance(layer, PoolSpec):
            scales.append(None)
            pool = max_pool2d if layer.kind == "max" else avg_pool2d
            x = pool(x, layer.size, layer.stride)
        elif isinstance(layer, DenseSpec):
            if x.ndim > 2:
                x = x.reshape(x.shape[0], -1)
            scales.append(_scale_of(x))
            x = _apply_activation(layer.activation, linear(p, x))
        else:
            scales.append(None)
            x = _apply_activation(layer.fn, x)
    return scales


def _calibrate_graph(graph, params: Any, x: jax.Array) -> dict[str, float]:
    from repro.fpca.program import (
        ConvSpec, DenseSpec, PoolSpec, _apply_activation,
    )
    from repro.models.heads import (
        INPUT, AddSpec, ConcatSpec, DetectSpec, GlobalPoolSpec,
    )
    from repro.models.layers import (
        avg_pool2d, conv2d, global_avg_pool2d, linear, max_pool2d,
    )

    values: dict[str, Any] = {INPUT: x}
    scales: dict[str, float] = {}
    for node in graph.toposort():
        op = node.op
        ins = [values[r] for r in node.inputs]
        if isinstance(op, ConvSpec):
            scales[node.name] = _scale_of(ins[0])
            y = _apply_activation(
                op.activation,
                conv2d(params[node.name], ins[0], op.stride, op.padding),
            )
        elif isinstance(op, DetectSpec):
            scales[node.name] = _scale_of(ins[0])
            y = conv2d(params[node.name], ins[0], 1, "SAME")
        elif isinstance(op, PoolSpec):
            pool = max_pool2d if op.kind == "max" else avg_pool2d
            y = pool(ins[0], op.size, op.stride)
        elif isinstance(op, GlobalPoolSpec):
            y = global_avg_pool2d(ins[0])
        elif isinstance(op, DenseSpec):
            v = ins[0]
            if v.ndim > 2:
                v = v.reshape(v.shape[0], -1)
            scales[node.name] = _scale_of(v)
            y = _apply_activation(op.activation, linear(params[node.name], v))
        elif isinstance(op, AddSpec):
            y = ins[0]
            for v in ins[1:]:
                y = y + v
            y = _apply_activation(op.activation, y)
        elif isinstance(op, ConcatSpec):
            y = _apply_activation(op.activation, jnp.concatenate(ins, axis=-1))
        else:                               # ActivationSpec
            y = _apply_activation(op.fn, ins[0])
        values[node.name] = y
    return scales


# ---------------------------------------------------------------------------
# head parameter quantisation / binding
# ---------------------------------------------------------------------------

def _quant_stage(p: dict, channel_axis: int, x_scale: float) -> dict:
    w_q, w_scale = quantize_symmetric(p["w"], channel_axis=channel_axis)
    return {
        "w_q": w_q,
        "w_scale": jnp.reshape(w_scale, (-1,)).astype(jnp.float32),
        "b": jnp.asarray(p["b"], jnp.float32),
        "x_scale": jnp.float32(x_scale),
    }


def is_quantized_params(params: Any) -> bool:
    """Whether a head pytree carries quantised stages (``w_q`` leaves)."""
    if isinstance(params, dict):
        vals = list(params.values())
    else:
        try:
            vals = list(params)
        except TypeError:
            return False
    return any(isinstance(p, dict) and "w_q" in p for p in vals)


def quantize_head_params(
    program,
    params: Any,
    *,
    sample_counts: Any | None = None,
    act_scales: Any | None = None,
) -> Any:
    """Quantise an f32 head pytree into the int8 serving pytree.

    ``act_scales`` (from :func:`calibrate_head_scales`, or round-tripped
    from an export bundle via :func:`unpack_act_scales`) takes precedence;
    otherwise scales are calibrated on ``sample_counts``, falling back to
    the data-free full-scale count map.  The result is what
    ``FPCAModelProgram(precision="int8").bind_head_params`` serves: one
    ``{"w_q", "w_scale", "b", "x_scale"}`` dict per parameterized stage
    (all leaves traced arrays — reprogramming never recompiles).
    """
    from repro.fpca.program import ConvSpec, DenseSpec
    from repro.models.heads import DetectSpec

    bound = program._bind_f32(params)
    if act_scales is None:
        if sample_counts is None:
            sample_counts = _default_calib_counts(program)
        act_scales = calibrate_head_scales(program, bound, sample_counts)
    if program.is_graph_head:
        out: dict[str, dict] = {}
        for node in program.head._param_nodes():
            axis = 0 if isinstance(node.op, (ConvSpec, DetectSpec)) else 1
            out[node.name] = _quant_stage(
                bound[node.name], axis, act_scales[node.name]
            )
        return out
    staged: list[dict] = []
    for layer, p, s in zip(program.head, bound, act_scales):
        if isinstance(layer, ConvSpec):
            staged.append(_quant_stage(p, 0, s))
        elif isinstance(layer, DenseSpec):
            staged.append(_quant_stage(p, 1, s))
        else:
            staged.append({})
    return staged


def _bind_quant_stage(p: Any, want_w: tuple, where: str) -> dict:
    p = dict(p)
    if set(p) != set(_QUANT_KEYS):
        raise ValueError(
            f"{where}: quantised stage needs keys {sorted(_QUANT_KEYS)}, "
            f"got {sorted(p)}"
        )
    out = {
        "w_q": jnp.asarray(p["w_q"], jnp.int8),
        "w_scale": jnp.asarray(p["w_scale"], jnp.float32),
        "b": jnp.asarray(p["b"], jnp.float32),
        "x_scale": jnp.asarray(p["x_scale"], jnp.float32),
    }
    c = want_w[0] if len(want_w) == 4 else want_w[1]
    got = {k: tuple(v.shape) for k, v in out.items()}
    want = {"w_q": want_w, "w_scale": (c,), "b": (c,), "x_scale": ()}
    if got != want:
        raise ValueError(
            f"{where}: quantised parameter shapes {got} do not match "
            f"expected {want}"
        )
    return out


def bind_quant_head_params(program, params: Any) -> Any:
    """Validate + coerce an int8 head pytree for serving (the ``precision=
    "int8"`` counterpart of the f32 binding path — same call sites, same
    fail-at-the-boundary contract)."""
    from repro.fpca.program import ConvSpec, DenseSpec

    if program.is_graph_head:
        if not isinstance(params, dict):
            raise ValueError(
                "graph head parameters must be a dict keyed by node name, "
                f"got {type(params).__name__}"
            )
        want_names = {n.name for n in program.head._param_nodes()}
        if set(params) != want_names:
            raise ValueError(
                f"graph head parameters keyed {sorted(params)} do not match "
                f"parameterized nodes {sorted(want_names)}"
            )
        shapes = program.head.shapes(program.frontend.out_shape)
        return {
            node.name: _bind_quant_stage(
                params[node.name],
                program.head._want_shapes(node, shapes)["w"],
                f"head node {node.name!r}",
            )
            for node in program.head._param_nodes()
        }
    bound = list(params)
    if len(bound) != len(program.head):
        raise ValueError(
            f"head has {len(program.head)} stages but got {len(bound)} "
            f"parameter entries"
        )
    shapes = program.head_shapes()
    out: list[dict] = []
    for i, (layer, p) in enumerate(zip(program.head, bound)):
        cur = shapes[i]
        if isinstance(layer, ConvSpec):
            want_w: tuple = (layer.out_channels, layer.kernel, layer.kernel,
                             cur[-1])
        elif isinstance(layer, DenseSpec):
            d_in = 1
            for d in cur:
                d_in *= int(d)
            want_w = (d_in, layer.features)
        else:
            if p:
                raise ValueError(
                    f"head[{i}] ({type(layer).__name__}): parameterless "
                    f"stage got parameters"
                )
            out.append({})
            continue
        out.append(_bind_quant_stage(
            p, want_w, f"head[{i}] ({type(layer).__name__})"
        ))
    return out


# ---------------------------------------------------------------------------
# int8 head apply (the precision="int8" numerics contract)
# ---------------------------------------------------------------------------

def apply_head_int8(program, params: Any, counts: jax.Array) -> jax.Array:
    """The int8 counterpart of ``FPCAModelProgram.apply_head`` — what every
    ``precision="int8"`` executable traces (fused model jit, head jit,
    patched streaming head, in-scan segment head)."""
    from repro.fpca.program import (
        ConvSpec, DenseSpec, PoolSpec, _apply_activation,
    )
    from repro.models.layers import avg_pool2d, max_pool2d

    x = jnp.asarray(counts, jnp.float32) * jnp.float32(program.input_scale)
    if program.is_graph_head:
        return _apply_graph_int8(program.head, params, x)
    if len(params) != len(program.head):
        raise ValueError(
            f"head has {len(program.head)} stages but got {len(params)} "
            f"parameter entries"
        )
    for layer, p in zip(program.head, params):
        if isinstance(layer, ConvSpec):
            x = _apply_activation(
                layer.activation, conv2d_int8(p, x, layer.stride, layer.padding)
            )
        elif isinstance(layer, PoolSpec):
            pool = max_pool2d if layer.kind == "max" else avg_pool2d
            x = pool(x, layer.size, layer.stride)
        elif isinstance(layer, DenseSpec):
            if x.ndim > 2:
                x = x.reshape(x.shape[0], -1)
            x = _apply_activation(layer.activation, linear_int8(p, x))
        else:
            x = _apply_activation(layer.fn, x)
    return x


def _apply_graph_int8(graph, params: Any, x: jax.Array) -> jax.Array:
    from repro.fpca.program import (
        ConvSpec, DenseSpec, PoolSpec, _apply_activation,
    )
    from repro.models.heads import (
        INPUT, AddSpec, ConcatSpec, DetectSpec, GlobalPoolSpec,
    )
    from repro.models.layers import avg_pool2d, global_avg_pool2d, max_pool2d

    if x.ndim == 3:
        return _apply_graph_int8(graph, params, x[None])[0]
    values: dict[str, Any] = {INPUT: x}
    for node in graph.toposort():
        op = node.op
        ins = [values[r] for r in node.inputs]
        if isinstance(op, ConvSpec):
            y = _apply_activation(
                op.activation,
                conv2d_int8(params[node.name], ins[0], op.stride, op.padding),
            )
        elif isinstance(op, DetectSpec):
            y = conv2d_int8(params[node.name], ins[0], 1, "SAME")
        elif isinstance(op, PoolSpec):
            pool = max_pool2d if op.kind == "max" else avg_pool2d
            y = pool(ins[0], op.size, op.stride)
        elif isinstance(op, GlobalPoolSpec):
            y = global_avg_pool2d(ins[0])
        elif isinstance(op, DenseSpec):
            v = ins[0]
            if v.ndim > 2:
                v = v.reshape(v.shape[0], -1)
            y = _apply_activation(
                op.activation, linear_int8(params[node.name], v)
            )
        elif isinstance(op, AddSpec):
            y = ins[0]
            for v in ins[1:]:
                y = y + v
            y = _apply_activation(op.activation, y)
        elif isinstance(op, ConcatSpec):
            y = _apply_activation(op.activation, jnp.concatenate(ins, axis=-1))
        else:                               # ActivationSpec
            y = _apply_activation(op.fn, ins[0])
        values[node.name] = y
    return values[graph.output]


# ---------------------------------------------------------------------------
# export bundle round-trip + parity metrics
# ---------------------------------------------------------------------------

def pack_act_scales(program, act_scales: Any) -> np.ndarray:
    """Flatten calibrated activation scales into one f32 array for an npz
    export bundle (chain: one slot per stage, 0 marking parameterless
    stages; graph: parameterized nodes in topological order)."""
    if program.is_graph_head:
        names = [n.name for n in program.head._param_nodes()]
        return np.asarray([act_scales[n] for n in names], np.float32)
    return np.asarray(
        [0.0 if s is None else float(s) for s in act_scales], np.float32
    )


def unpack_act_scales(program, arr: Any) -> Any:
    """Inverse of :func:`pack_act_scales`."""
    arr = np.asarray(arr, np.float32).reshape(-1)
    if program.is_graph_head:
        names = [n.name for n in program.head._param_nodes()]
        if arr.size != len(names):
            raise ValueError(
                f"expected {len(names)} activation scales, got {arr.size}"
            )
        return {n: float(s) for n, s in zip(names, arr)}
    if arr.size != len(program.head):
        raise ValueError(
            f"expected {len(program.head)} activation scales, got {arr.size}"
        )
    return [None if s == 0.0 else float(s) for s in arr]


def logit_parity(ref: Any, test: Any) -> dict[str, float]:
    """Bounded-parity metrics of an int8 lowering against its f32
    reference: ``max_abs_divergence`` over all outputs and ``top1_agreement``
    over the trailing class axis (1.0 for single-output maps)."""
    ref = np.asarray(ref, np.float32)
    test = np.asarray(test, np.float32)
    if ref.shape != test.shape:
        raise ValueError(
            f"shape mismatch: reference {ref.shape} vs test {test.shape}"
        )
    max_div = float(np.max(np.abs(ref - test))) if ref.size else 0.0
    if ref.ndim >= 2 and ref.shape[-1] > 1:
        a = np.argmax(ref.reshape(-1, ref.shape[-1]), axis=-1)
        b = np.argmax(test.reshape(-1, test.shape[-1]), axis=-1)
        top1 = float(np.mean(a == b))
    else:
        top1 = 1.0
    return {"max_abs_divergence": max_div, "top1_agreement": top1}
