"""Compile-only guards for the TPU path: the Pallas frontend, the fused
model and a segment executable at the ``fpca_cnn`` width, compiled for a
described ``v5e:2x2`` topology without a chip.

Interpret-mode tests cannot see what the chip's compiler refuses (tile
alignment, fast-memory limits, kernels it cannot partition); these compiles
can.  Nothing runs, so they say nothing about results or times.

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU compiler, and every test worker
imports this file.
"""

from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P, SingleDeviceSharding

from repro.configs import fpca_cnn
from repro.core import gating
from repro.core.mapping import output_dims
from repro.fpca import build_model, get_backend
from repro.fpca.backends import data_parallel

BATCH = 32
SEGMENT = 16
MODEL = build_model(fpca_cnn.CFG)
SPEC = MODEL.spec
H_O, W_O = output_dims(SPEC)


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def mesh4(topo):
    return Mesh(np.asarray(topo.devices).reshape(4, 1), ("data", "model"))


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(tuple(shape), dtype, sharding=sharding)


def _frontend_args(batch_sharding, weight_sharding, m_bucket):
    args = [
        _sds((BATCH, SPEC.image_h, SPEC.image_w, SPEC.in_channels), jnp.float32,
             batch_sharding),
        _sds(MODEL.frontend.kernel_shape, jnp.float32, weight_sharding),
        _sds((SPEC.out_channels,), jnp.float32, weight_sharding),
    ]
    if m_bucket is not None:
        args.append(_sds((BATCH, H_O, W_O), jnp.bool_, batch_sharding))
    return args


def _head_params(sharding):
    shapes = jax.eval_shape(MODEL.init_head, jax.random.PRNGKey(0))
    return jax.tree.map(lambda s: _sds(s.shape, s.dtype, sharding), shapes)


def _frontend(bucket_model, m_bucket):
    return get_backend("pallas").make_executable(
        bucket_model, spec=SPEC, adc=MODEL.frontend.adc, enc=MODEL.frontend.enc,
        interpret=False, m_bucket=m_bucket,
    )


def _compile(fn, *args) -> str:
    return fn.lower(*args).compile().as_text()


@pytest.mark.parametrize("m_bucket", [None, 128])
def test_pallas_frontend_compiles_for_v5e(bucket_model, one_chip, m_bucket):
    hlo = _compile(
        _frontend(bucket_model, m_bucket),
        *_frontend_args(one_chip, one_chip, m_bucket),
    )
    assert "tpu_custom_call" in hlo


def test_fused_model_compiles_for_v5e(bucket_model, one_chip):
    run = get_backend("pallas").make_model_executable(
        MODEL, bucket_model, interpret=False
    )
    hlo = _compile(
        run, *_frontend_args(one_chip, one_chip, None), _head_params(one_chip)
    )
    assert "tpu_custom_call" in hlo


def test_segment_executable_compiles_for_v5e(bucket_model, one_chip):
    run = get_backend("pallas").make_segment_executable(
        bucket_model, spec=SPEC, adc=MODEL.frontend.adc, enc=MODEL.frontend.enc,
        interpret=False, length=SEGMENT, m_bucket=128, model_program=MODEL,
        donate=True,
    )
    gate_carry = jax.eval_shape(lambda: gating.init_gate_carry(SPEC, 1))
    carry = tuple(_sds(s.shape, s.dtype, one_chip) for s in gate_carry) + (
        _sds((H_O, W_O, SPEC.out_channels), jnp.float32, one_chip),
        _sds(MODEL.head_out_shape, jnp.float32, one_chip),
    )
    gate_args = (
        _sds((), jnp.float32, one_chip),
        _sds((), jnp.int32, one_chip),
        _sds((), jnp.int32, one_chip),
    )
    frames = _sds(
        (SEGMENT, SPEC.image_h, SPEC.image_w, SPEC.in_channels), jnp.float32,
        one_chip,
    )
    fe_args = _frontend_args(one_chip, one_chip, None)
    hlo = _compile(
        run, frames, fe_args[1], fe_args[2], _head_params(one_chip), gate_args,
        carry,
    )
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("m_bucket", [None, H_O * W_O * BATCH // 4 // 8])
def test_data_sharded_frontend_compiles_on_four_chips(bucket_model, mesh4, m_bucket):
    """Each chip runs the kernel on its own batch shard (``shard_map``); a
    region-skip bucket counts the rows of one shard, and the batch is never
    gathered onto one chip."""
    batch = NamedSharding(mesh4, P("data"))
    replicated = NamedSharding(mesh4, P())
    hlo = _compile(
        data_parallel(_frontend(bucket_model, m_bucket), mesh4),
        *_frontend_args(batch, replicated, m_bucket),
    )
    assert "tpu_custom_call" in hlo
    assert "all-gather" not in hlo and "all-to-all" not in hlo


def test_data_sharded_model_compiles_on_four_chips(bucket_model, mesh4):
    batch = NamedSharding(mesh4, P("data"))
    replicated = NamedSharding(mesh4, P())
    run = get_backend("pallas").make_model_executable(
        MODEL, bucket_model, interpret=False, mesh=mesh4
    )
    hlo = _compile(
        run, *_frontend_args(batch, replicated, None), _head_params(replicated)
    )
    assert "tpu_custom_call" in hlo
    assert "all-gather" not in hlo
