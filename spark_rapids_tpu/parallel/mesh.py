"""Device mesh construction.

The SQL engine's parallelism is data-parallel over partitions (the
reference's model: one Spark task per partition, §2.9 of SURVEY.md), so
the canonical mesh is 1-D over the `data` axis.  Multi-host meshes come
from jax.distributed the usual way; everything downstream only sees axis
names.
"""

from __future__ import annotations

import hashlib
from typing import Optional, Sequence

import jax
import numpy as np
from jax.sharding import Mesh

DATA_AXIS = "data"


#: process-active mesh for collective shuffle lowering (the executor's
#: "device topology" state; ref: GpuShuffleEnv.scala:26 detecting the
#: transport-backed shuffle manager)
_ACTIVE_MESH: Optional[Mesh] = None


def set_active_mesh(mesh: Optional[Mesh]) -> None:
    global _ACTIVE_MESH
    _ACTIVE_MESH = mesh


def active_mesh() -> Optional[Mesh]:
    return _ACTIVE_MESH


def mesh_key(mesh: Mesh) -> tuple:
    """Structural identity of a mesh for compile-cache keys: device ids,
    axis names and axis sizes.  Two meshes over DIFFERENT device sets
    must never share a cached partitioned executable (the sharding's
    repr alone does not carry device identity), so every SPMD stage
    program folds this into its cached_jit key."""
    return (tuple(int(d.id) for d in mesh.devices.flat),
            tuple(mesh.axis_names),
            tuple(int(mesh.shape[a]) for a in mesh.axis_names))


def mesh_cache_suffix() -> str:
    """The mesh-identity component of a serving-tier cache key: a short
    digest of ``mesh_key(active_mesh())``, or '' when no mesh is
    active.  A plan or result minted on one mesh must never serve
    another (an 8-device entry must not answer on 4 devices), so the
    plan, prepared-statement and result caches fold this in."""
    m = active_mesh()
    if m is None:
        return ""
    return "|mesh:" + hashlib.sha256(
        repr(mesh_key(m)).encode()).hexdigest()[:12]


def make_mesh(n_devices: Optional[int] = None,
              axes: Sequence[str] = (DATA_AXIS,),
              shape: Optional[Sequence[int]] = None,
              devices: Optional[Sequence] = None) -> Mesh:
    devs = list(devices) if devices is not None else jax.devices()
    if n_devices is not None:
        if len(devs) < n_devices:
            raise ValueError(
                f"need {n_devices} devices, have {len(devs)}; for CPU tests "
                "set XLA_FLAGS=--xla_force_host_platform_device_count=N")
        devs = devs[:n_devices]
    if shape is None:
        shape = (len(devs),) + (1,) * (len(axes) - 1)
    arr = np.array(devs).reshape(tuple(shape))
    return Mesh(arr, tuple(axes))
