"""Production mesh construction.

Defined as functions (never module-level constants) so importing this module
never touches jax device state.  The dry-run entrypoint sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` *before* any jax
import; everything else in the repo sees the real single CPU device.

Topology rationale (DESIGN.md §5): ``model`` is the fast-ICI minor axis
(tensor parallel), ``data`` the second intra-pod axis (FSDP + data parallel),
``pod`` the cross-pod axis that only ever carries gradient all-reduces — the
one pattern that scales to thousands of nodes.
"""

from __future__ import annotations

import jax
import numpy as np

__all__ = ["make_production_mesh", "make_host_mesh", "data_axes", "data_extent"]


def _mesh(shape: tuple[int, ...], axes: tuple[str, ...]) -> jax.sharding.Mesh:
    n = int(np.prod(shape))
    devices = jax.devices()
    if len(devices) == n:
        return jax.make_mesh(
            shape, axes, axis_types=(jax.sharding.AxisType.Auto,) * len(axes)
        )
    if len(devices) < n:
        raise RuntimeError(
            f"need {n} devices for mesh {dict(zip(axes, shape))}, have {len(devices)} — "
            "run via launch/dryrun.py (it forces 512 host devices)"
        )
    dev = np.asarray(devices[:n]).reshape(shape)
    return jax.sharding.Mesh(dev, axes)


def make_production_mesh(*, multi_pod: bool = False) -> jax.sharding.Mesh:
    """16x16 single-pod (256 chips) or 2x16x16 multi-pod (512 chips) mesh."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mesh(shape, axes)


def make_host_mesh(data: int = 1, model: int = 1) -> jax.sharding.Mesh:
    """Tiny mesh over however many (virtual) devices tests run with."""
    return _mesh((data, model), ("data", "model"))


def data_axes(mesh: jax.sharding.Mesh) -> tuple[str, ...]:
    """Axes that carry the batch dimension (pure DP + FSDP axes)."""
    return tuple(a for a in mesh.axis_names if a in ("pod", "data"))


def data_extent(mesh: jax.sharding.Mesh) -> int:
    """Total device count along the batch-carrying axes — the multiple a
    data-parallel batch must pad to (used by the FPCA serving handles)."""
    return int(np.prod([mesh.shape[a] for a in data_axes(mesh)], dtype=np.int64))
