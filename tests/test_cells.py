"""Cell machinery on a host mesh: the same build/lower/compile path the
512-device dry-run uses, exercised at reduced scale in CI."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import pytest

from repro.configs import ARCHS, reduce_for_smoke
from repro.configs.base import ShapeSpec
from repro.launch.cells import CellPlan, build_cell
from repro.launch.mesh import make_host_mesh
from repro.launch.roofline import summarize_cell
from repro.launch.sharding import ShardingPolicy, param_shardings
from repro.models.transformer import init_model

# every test here lowers+compiles full cells — the slow half of tier-1
pytestmark = pytest.mark.slow


def _mesh():
    return make_host_mesh(1, 1)


@pytest.mark.parametrize("arch", ["qwen3-1.7b", "granite-moe-3b-a800m", "mamba2-2.7b", "zamba2-7b"])
@pytest.mark.parametrize(
    "shape",
    [
        ShapeSpec("t", 64, 4, "train"),
        ShapeSpec("p", 64, 4, "prefill"),
        ShapeSpec("d", 64, 4, "decode"),
    ],
)
def test_cell_compiles_on_host_mesh(arch, shape):
    cfg = reduce_for_smoke(ARCHS[arch])
    mesh = _mesh()
    with jax.sharding.set_mesh(mesh):
        jitted, args = build_cell(cfg, shape, mesh, CellPlan(remat="none"))
        compiled = jitted.lower(*args).compile()
    rec = summarize_cell(compiled, cfg, shape, mesh.size)
    assert rec["flops_per_device"] > 0
    assert rec["terms"]["dominant"] in ("compute_s", "memory_s", "collective_s")
    assert rec["collectives"]["unknown_trip_whiles"] == 0


def test_param_shardings_cover_every_leaf():
    """Every parameter leaf of every arch must match a sharding rule whose
    spec rank fits the leaf (catches new params w/o rules)."""
    mesh = _mesh()
    for arch, full_cfg in ARCHS.items():
        cfg = reduce_for_smoke(full_cfg)
        shapes = jax.eval_shape(
            lambda k, c=cfg: init_model(k, c), jax.ShapeDtypeStruct((2,), jnp.uint32)
        )
        shardings = param_shardings(shapes, mesh, ShardingPolicy())
        for (path, leaf), (_, sh) in zip(
            jax.tree_util.tree_flatten_with_path(shapes)[0],
            jax.tree_util.tree_flatten_with_path(shardings)[0],
        ):
            assert len(sh.spec) <= leaf.ndim, (arch, path, leaf.shape, sh.spec)


def test_expert_parallel_policy_changes_expert_specs():
    mesh = _mesh()
    cfg = reduce_for_smoke(ARCHS["granite-moe-3b-a800m"])
    shapes = jax.eval_shape(
        lambda k: init_model(k, cfg), jax.ShapeDtypeStruct((2,), jnp.uint32)
    )
    tp = param_shardings(shapes, mesh, ShardingPolicy(expert_parallel=False))
    ep = param_shardings(shapes, mesh, ShardingPolicy(expert_parallel=True))
    tp_spec = tp["blocks"]["moe"]["experts"]["gate"].spec
    ep_spec = ep["blocks"]["moe"]["experts"]["gate"].spec
    assert tp_spec != ep_spec
    assert "model" in str(ep_spec[1])  # expert axis sharded (after layer pad)
