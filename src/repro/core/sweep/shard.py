"""Device-sharded sweep execution: partition the candidate batch axis.

The batch engine's hot path is ``jit(vmap(simulate))`` over a padded
candidate batch — embarrassingly parallel across candidates, yet the
seed implementation ran every bucket on one device, so grid size (not
hardware) bounded sweep throughput. This module shards the *batch axis*
of each bucket over a 1-D device mesh:

    batch [C_pad, ...] --shard_map over axis "candidates"--> C_pad/S rows
                                                             per device

* The mesh is 1-D over the largest power-of-two prefix of the chosen
  devices (``resolve_mesh``), so power-of-two batch buckets always
  divide the shard count — remainders are absorbed by the *existing*
  bucket padding (`SweepEngine` pads ``c_pad = max(pow2(C), S)``), never
  by a fresh compile.
* Per-candidate simulation is row-independent (no cross-row collectives
  inside the vmap body), so the sharded executable is **bit-identical**
  to the single-device ``jit(vmap)`` path — asserted element-wise by the
  property tests in tests/test_shard.py across batch sizes straddling
  device-count boundaries.
* With one visible device (or ``devices=None``) everything falls back to
  the plain vmap executable: same cache keys (shards=1), zero behaviour
  change.

``XLA_FLAGS=--xla_force_host_platform_device_count=8`` exercises the
sharded path on CPU-only hosts (the CI matrix leg and the ``sweepshard``
benchmark both run under it).
"""
from __future__ import annotations

from typing import Optional, Sequence, Union

import jax
import numpy as np
from jax.sharding import Mesh, PartitionSpec

from ...launch.mesh import make_candidates_mesh
from .buckets import bucket_pow2

# the single mesh axis the batch dimension is partitioned over
SHARD_AXIS = "candidates"

# what SweepEngine accepts as its ``devices`` option
DevicesLike = Union[None, int, Sequence, Mesh]


def pow2_floor(n: int) -> int:
    """Largest power of two <= n (0 for n < 1)."""
    return 1 << (n.bit_length() - 1) if n >= 1 else 0


def resolve_mesh(devices: DevicesLike) -> Optional[Mesh]:
    """Normalize a ``devices`` option into a 1-D sweep mesh (or None).

    * ``None``            -> None (single-device vmap fallback)
    * ``0``               -> all visible devices
    * ``n > 0``           -> the first n visible devices
    * a device sequence   -> those devices
    * a 1-D ``Mesh``      -> used as-is

    Device counts are rounded *down* to a power of two (so every
    power-of-two batch bucket divides the shard count evenly); a
    resolved count of one returns None — sharding a 1-device mesh would
    only add dispatch overhead over the plain executable.
    """
    if devices is None:
        return None
    if isinstance(devices, Mesh):
        if len(devices.axis_names) != 1:
            raise ValueError(
                f"sweep mesh must be 1-D, got axes {devices.axis_names}")
        return None if devices.size == 1 else devices
    if isinstance(devices, int):
        if devices < 0:
            raise ValueError(f"devices must be >= 0, got {devices}")
        avail = jax.devices()
        devs = avail if devices == 0 else avail[:devices]
    else:
        devs = list(devices)
    n = pow2_floor(len(devs))
    if n <= 1:
        return None
    return make_candidates_mesh(devs[:n], axis=SHARD_AXIS)


def shard_count(mesh: Optional[Mesh]) -> int:
    """Number of batch-axis shards an engine mesh implies (1 = no mesh)."""
    return 1 if mesh is None else int(mesh.size)


def shard_pad(n: int, n_shards: int) -> int:
    """Batch-bucket size for n candidates over n_shards devices.

    The plain power-of-two batch bucket, floored at the shard count:
    because the shard count is itself a power of two, padding up to it
    keeps the batch divisible without inventing new bucket sizes.
    """
    return max(bucket_pow2(n, floor=1), n_shards)


def sharded_executable(vmapped_fn, mesh: Mesh, n_args: int = 2):
    """jit(shard_map(vmapped_fn)) over the batch axis of every argument.

    ``vmapped_fn(batch, st_vecs[, fbatch])`` must be a per-row-independent
    map (our ``vmap`` of one-candidate simulation); the single
    ``PartitionSpec(SHARD_AXIS)`` acts as a pytree prefix, splitting the
    leading axis of every `OpArrays` leaf, of the service-time matrix and
    (for faulted buckets, ``n_args=3``) of every `FaultArrays` leaf. Each
    device runs the identical program on its C_pad/S rows; outputs
    concatenate back in candidate order.
    """
    axis = mesh.axis_names[0]
    spec = PartitionSpec(axis)
    specs = (spec,) * n_args
    # skipping the varying-manual-axes check is safe: every output is
    # fully partitioned, nothing is claimed replicated
    mapped = jax.shard_map(vmapped_fn, mesh=mesh, in_specs=specs,
                           out_specs=spec, check_vma=False)
    return jax.jit(mapped)


def mesh_identity(mesh: Optional[Mesh]):
    """Hashable identity used to detect mesh changes (executables close
    over their mesh, so a different device set invalidates them)."""
    if mesh is None:
        return None
    return tuple(d.id for d in np.ravel(mesh.devices))
