"""Mesh construction helpers shared by launch/, retrieval/ and tests.

Every mesh pins ``AxisType.Auto`` so pjit/shard_map keep their implicit
sharding semantics. Meshes are built by functions, never module-level
constants, so importing this module never touches jax device state.
"""

from __future__ import annotations

from typing import Sequence

import jax
import numpy as np
from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec


def make_mesh(shape: Sequence[int], axes: Sequence[str]) -> Mesh:
    return jax.make_mesh(tuple(shape), tuple(axes), axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    """Single-pod: 16×16 = 256 chips, axes (data, model). Multi-pod:
    2×16×16 = 512 chips, axes (pod, data, model) — the pod axis is the
    slower DCN/ICI dimension that gradient all-reduce crosses."""
    if multi_pod:
        return make_mesh((2, 16, 16), ("pod", "data", "model"))
    return make_mesh((16, 16), ("data", "model"))


def corpus_mesh(n_shards: int, axis: str = "data") -> Mesh:
    """1-axis mesh over the first ``n_shards`` devices for corpus-row
    sharding (``ShardingPolicy.corpus_rows`` layout).

    Unlike :func:`make_mesh` (which always spans every device), this takes a
    device *subset* so an S-way sharded retrieval backend can coexist with
    other work on the remaining devices — and so S < device_count is
    expressible at all. Raises with the remediation (``XLA_FLAGS=
    --xla_force_host_platform_device_count=N`` for CPU hosts) when the host
    has too few devices.
    """
    devices = jax.devices()
    if n_shards < 1:
        raise ValueError(f"n_shards must be >= 1, got {n_shards}")
    if n_shards > len(devices):
        raise ValueError(
            f"n_shards={n_shards} > visible devices ({len(devices)}); on CPU "
            "hosts set XLA_FLAGS=--xla_force_host_platform_device_count="
            f"{n_shards} before importing jax, or use execution='threads'"
        )
    return Mesh(np.asarray(devices[:n_shards]), (axis,), axis_types=(AxisType.Auto,))


def named_sharding(mesh: Mesh, *spec) -> NamedSharding:
    return NamedSharding(mesh, PartitionSpec(*spec))


def mesh_device_count(mesh: Mesh) -> int:
    n = 1
    for s in mesh.shape.values():
        n *= s
    return n
