"""Shared transformer building blocks (pure-functional, pytree params).

Conventions:
* params are nested dicts of jnp arrays; every module is an ``init`` +
  ``apply`` pair of pure functions;
* compute dtype is configurable (bf16 on TPU), numerics-critical reductions
  (norms, softmax) run in f32;
* weight layouts are chosen for the sharding rules in
  :mod:`repro.launch.sharding` (see DESIGN.md §5).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

__all__ = [
    "rms_norm",
    "init_rms_norm",
    "init_dense",
    "dense",
    "init_swiglu",
    "swiglu",
    "init_mlp",
    "mlp",
    "init_embedding",
    "embed",
    "unembed",
    "rope",
    "cross_entropy_loss",
    "init_conv2d",
    "conv2d",
    "init_linear",
    "linear",
    "max_pool2d",
    "avg_pool2d",
    "global_avg_pool2d",
]


def init_rms_norm(d: int, dtype=jnp.float32) -> dict:
    return {"scale": jnp.ones((d,), dtype)}


def rms_norm(params: dict, x: jax.Array, eps: float = 1e-5) -> jax.Array:
    dtype = x.dtype
    x32 = x.astype(jnp.float32)
    var = jnp.mean(x32 * x32, axis=-1, keepdims=True)
    out = x32 * jax.lax.rsqrt(var + eps)
    return (out * params["scale"].astype(jnp.float32)).astype(dtype)


def init_dense(key: jax.Array, d_in: int, d_out: int, dtype=jnp.bfloat16) -> dict:
    scale = 1.0 / jnp.sqrt(d_in)
    return {"w": (jax.random.normal(key, (d_in, d_out)) * scale).astype(dtype)}


def dense(params: dict, x: jax.Array) -> jax.Array:
    return x @ params["w"]


def init_swiglu(key: jax.Array, d: int, d_ff: int, dtype=jnp.bfloat16) -> dict:
    k1, k2, k3 = jax.random.split(key, 3)
    return {
        "gate": init_dense(k1, d, d_ff, dtype),
        "up": init_dense(k2, d, d_ff, dtype),
        "down": init_dense(k3, d_ff, d, dtype),
    }


def swiglu(params: dict, x: jax.Array) -> jax.Array:
    return dense(params["down"], jax.nn.silu(dense(params["gate"], x)) * dense(params["up"], x))


def init_mlp(key: jax.Array, d: int, d_ff: int, dtype=jnp.bfloat16) -> dict:
    """Plain GELU MLP (used by the Seamless enc-dec backbone)."""
    k1, k2 = jax.random.split(key)
    return {"up": init_dense(k1, d, d_ff, dtype), "down": init_dense(k2, d_ff, d, dtype)}


def mlp(params: dict, x: jax.Array) -> jax.Array:
    return dense(params["down"], jax.nn.gelu(dense(params["up"], x)))


def maybe_shard(x: jax.Array, *spec) -> jax.Array:
    """with_sharding_constraint iff tracing under a mesh (no-op in tests).

    Axis names in ``spec`` that don't exist in the ambient mesh are dropped
    (so the same model code lowers under 2-axis and 3-axis meshes)."""
    mesh = jax.sharding.get_abstract_mesh()
    if mesh.empty:
        return x

    def clean(entry):
        if entry is None:
            return None
        if isinstance(entry, str):
            return entry if entry in mesh.axis_names else None
        sub = tuple(a for a in entry if a in mesh.axis_names)
        return sub if sub else None

    return jax.lax.with_sharding_constraint(
        x, jax.sharding.PartitionSpec(*(clean(s) for s in spec))
    )


def shard_batch(x: jax.Array) -> jax.Array:
    """Pin the leading batch axis to the data axes; keep the rest unsharded
    except a model-sharded last axis is preserved for (B, S, V) logits.

    GSPMD sometimes re-shards the residual-stream scan carry to a
    batch-replicated layout (observed: involuntary full remat around the
    vocab matmul); pinning the batch axis at block boundaries prevents the
    blow-up.  No-op without an ambient mesh.
    """
    return maybe_shard(x, ("pod", "data"), *([None] * (x.ndim - 1)))


def init_embedding(key: jax.Array, vocab: int, d: int, dtype=jnp.bfloat16) -> dict:
    return {"table": (jax.random.normal(key, (vocab, d)) * 0.02).astype(dtype)}


def embed(params: dict, tokens: jax.Array) -> jax.Array:
    return params["table"][tokens]


def unembed(params: dict, x: jax.Array) -> jax.Array:
    """Logits in f32.

    The table is stored vocab-replicated / d-FSDP (clean token gathers); for
    the output projection we re-shard it vocab-over-model so the (B, S, V)
    logits are born vocab-sharded — never materialised whole on one device.
    The one-off table reshard per step is a deliberate trade (DESIGN.md §5).
    """
    table = maybe_shard(params["table"], "model", None)
    return (x @ table.T.astype(x.dtype)).astype(jnp.float32)


# ---------------------------------------------------------------------------
# Small-CNN building blocks (the digital head behind an FPCA frontend).
#
# NHWC layout, f32 by default: these serve the extreme-edge classifier heads
# (repro.fpca.FPCAModelProgram), where numerics-exactness against a reference
# composition matters more than bf16 throughput.
# ---------------------------------------------------------------------------


def init_conv2d(
    key: jax.Array, c_in: int, c_out: int, kernel: int, dtype=jnp.float32,
    groups: int = 1,
) -> dict:
    """Biased conv params: ``w`` is ``(c_out, k, k, c_in // groups)`` (FPCA
    kernel layout, so frontend and head convolutions read the same way);
    each output channel's fan-in is ``k * k * c_in / groups``."""
    fan_in = kernel * kernel * (c_in // groups)
    w = jax.random.normal(key, (c_out, kernel, kernel, c_in // groups)) * fan_in ** -0.5
    return {"w": w.astype(dtype), "b": jnp.zeros((c_out,), dtype)}


def conv2d(
    params: dict, x: jax.Array, stride: int = 1, padding: str = "VALID",
    groups: int = 1,
) -> jax.Array:
    """NHWC convolution with bias; ``padding`` is ``"VALID"`` or ``"SAME"``;
    ``groups`` independent channel groups (``groups == c_in == c_out`` is
    depthwise)."""
    out = jax.lax.conv_general_dilated(
        x.transpose(0, 3, 1, 2),
        params["w"].transpose(0, 3, 1, 2),
        window_strides=(stride, stride),
        padding=padding,
        dimension_numbers=("NCHW", "OIHW", "NCHW"),
        feature_group_count=groups,
    ).transpose(0, 2, 3, 1)
    return out + params["b"]


def global_avg_pool2d(x: jax.Array) -> jax.Array:
    """Mean over the whole ``(h, w)`` map of an NHWC batch: ``(b, c)``."""
    return jnp.mean(x, axis=(1, 2))


def init_linear(key: jax.Array, d_in: int, d_out: int, dtype=jnp.float32) -> dict:
    """Biased dense params (``init_dense`` is the bias-free LM variant)."""
    w = jax.random.normal(key, (d_in, d_out)) * d_in ** -0.5
    return {"w": w.astype(dtype), "b": jnp.zeros((d_out,), dtype)}


def linear(params: dict, x: jax.Array) -> jax.Array:
    return x @ params["w"] + params["b"]


def _pool(x: jax.Array, size: int, stride: int | None, init, op) -> jax.Array:
    s = size if stride is None else stride
    return jax.lax.reduce_window(
        x, init, op,
        window_dimensions=(1, size, size, 1),
        window_strides=(1, s, s, 1),
        padding="VALID",
    )


def max_pool2d(x: jax.Array, size: int, stride: int | None = None) -> jax.Array:
    return _pool(x, size, stride, -jnp.inf, jax.lax.max)


def avg_pool2d(x: jax.Array, size: int, stride: int | None = None) -> jax.Array:
    return _pool(x, size, stride, 0.0, jax.lax.add) / float(size * size)


def rope(x: jax.Array, positions: jax.Array, theta: float = 1e4) -> jax.Array:
    """Rotary embedding. x: (..., S, H, D), positions: broadcastable to (..., S)."""
    d = x.shape[-1]
    half = d // 2
    freqs = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    angles = positions[..., None].astype(jnp.float32) * freqs  # (..., S, half)
    cos = jnp.cos(angles)[..., None, :]  # (..., S, 1, half)
    sin = jnp.sin(angles)[..., None, :]
    x1, x2 = x[..., :half], x[..., half:]
    out = jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1
    )
    return out.astype(x.dtype)


def cross_entropy_loss(
    logits: jax.Array, labels: jax.Array, mask: jax.Array | None = None
) -> jax.Array:
    """Mean token CE in f32. logits (..., V), labels (...) int32.

    The gold logit is extracted with a fusable one-hot reduction rather than
    ``take_along_axis``: a gather over the vocab axis (which we keep sharded
    over 'model') forces the SPMD partitioner into involuntary full
    rematerialisation of the logits — the one-hot product reduces locally and
    cross-shard with a cheap all-reduce instead.
    """
    logits = logits.astype(jnp.float32)
    logz = jax.nn.logsumexp(logits, axis=-1)
    onehot = labels[..., None] == jnp.arange(logits.shape[-1], dtype=labels.dtype)
    gold = jnp.sum(jnp.where(onehot, logits, 0.0), axis=-1)
    nll = logz - gold
    if mask is not None:
        return jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)
    return jnp.mean(nll)
