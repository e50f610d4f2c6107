"""Production meshes.

Single pod: (16, 16) = 256 chips, axes ("data", "model").
Multi-pod:  (2, 16, 16) = 512 chips, axes ("pod", "data", "model") — the
"pod" axis is an outer data/FSDP axis; cross-pod traffic is gradient
reduction (DCN), intra-pod is TP/EP/FSDP (ICI).

Defined as functions so importing this module never touches jax device
state (the dry-run must set XLA_FLAGS before first jax init).

Every mesh here has ``Auto`` axes: ``jax.make_mesh`` defaults to
``Explicit`` axes, which put the sharding into array types and refuse
indexing whose output sharding is ambiguous (``theta[0:1]`` of a
node-sharded array). The repo's programs place arrays with explicit
``NamedSharding``s and ``shard_map`` specs instead.
"""
from __future__ import annotations

import jax

from repro.core.krls import KRLS_SHARD_AXIS

__all__ = [
    "make_mesh",
    "make_production_mesh",
    "make_krls_mesh",
    "data_axes",
    "DP_AXES",
    "MODEL_AXIS",
    "KRLS_SHARD_AXIS",
]

MODEL_AXIS = "model"


def make_mesh(shape: tuple[int, ...], axes: tuple[str, ...]) -> jax.sharding.Mesh:
    """``jax.make_mesh`` with every axis ``Auto``."""
    auto = (jax.sharding.AxisType.Auto,) * len(axes)
    return jax.make_mesh(shape, axes, axis_types=auto)


def make_production_mesh(*, multi_pod: bool = False) -> jax.sharding.Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_krls_mesh(n_shards: int | None = None) -> jax.sharding.Mesh:
    """1-D mesh over the KRLS shard axis (the P row-block partition).

    Defaults to every visible device; for host-platform simulation set
    ``XLA_FLAGS=--xla_force_host_platform_device_count=N`` before first jax
    use (the pattern tests/test_krls_sharded.py runs in a subprocess).
    """
    n = n_shards if n_shards is not None else jax.device_count()
    return make_mesh((n,), (KRLS_SHARD_AXIS,))


def data_axes(mesh: jax.sharding.Mesh) -> tuple[str, ...]:
    """All data-parallel-like axes (everything except the model axis)."""
    return tuple(a for a in mesh.axis_names if a != MODEL_AXIS)


DP_AXES = data_axes  # alias
