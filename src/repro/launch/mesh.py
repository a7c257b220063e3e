"""Production meshes.

Defined as functions (NOT module constants) so importing this module never
touches jax device state — critical because the dry-run sets
XLA_FLAGS=--xla_force_host_platform_device_count=512 before any jax
import, while smoke tests and benchmarks must see one device.
"""
from __future__ import annotations

from typing import Optional, Sequence

import jax
import numpy as np
from jax.sharding import AxisType


def _mk(shape, axes):
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """Single pod: 16x16 = 256 chips (v5e pod), axes (data, model).
    Multi-pod: 2 pods = 512 chips, axes (pod, data, model) — the "pod"
    axis spans the DCN boundary and carries only data-parallel traffic."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mk(shape, axes)


def make_elastic_mesh(n_pods: int, *, data: int = 16, model: int = 16):
    """Degraded mesh after pod loss (see launch.elastic): same per-pod
    topology, fewer pods. n_pods == 1 drops the pod axis entirely so
    collective layouts match the single-pod program."""
    if n_pods == 1:
        return _mk((data, model), ("data", "model"))
    return _mk((n_pods, data, model), ("pod", "data", "model"))


def make_host_mesh(*, model: Optional[int] = None):
    """Whatever this host actually has — for smoke tests and examples."""
    n = len(jax.devices())
    m = model or 1
    assert n % m == 0
    return _mk((n // m, m), ("data", "model"))


def make_candidates_mesh(devices: Optional[Sequence] = None, *,
                         axis: str = "candidates"):
    """1-D mesh over explicit devices for candidate-batch sharding
    (`repro.core.sweep.shard`): the sweep engine partitions the batch
    axis of each bucket over this axis. Unlike the production meshes the
    device list is explicit — the sweep layer picks a power-of-two
    prefix so its batch buckets always divide the mesh."""
    devs = list(devices) if devices is not None else jax.devices()
    mesh = jax.sharding.Mesh(np.asarray(devs), (axis,))
    return mesh
