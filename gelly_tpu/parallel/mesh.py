"""Device mesh helpers — the substrate the reference delegated to Flink.

The reference's notion of parallelism is Flink operator subtasks connected by
Netty shuffles (SURVEY.md §2.8-2.9); here the equivalent substrate is a
``jax.sharding.Mesh`` over the TPU slice, with ``shard_map`` partitioning and
XLA collectives over ICI. A single 1-D ``shards`` axis plays the role of
operator parallelism; multi-host meshes extend the same axis over DCN.

For tests (the MiniCluster analog) the CPU backend is forced with
``--xla_force_host_platform_device_count=8``; the same code paths then run on
real chips unchanged.
"""

from __future__ import annotations

from functools import partial

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

SHARD_AXIS = "shards"

# Recorded by initialize_multihost so observability (heartbeat lines,
# Chrome-trace otherData) can attribute a capture to its cluster without
# re-deriving launcher state. None on single-process / auto-detected runs.
_COORDINATOR_ADDRESS: str | None = None


def initialize_multihost(coordinator_address: str | None = None,
                         num_processes: int | None = None,
                         process_id: int | None = None) -> None:
    """Join a multi-host mesh over DCN (jax.distributed).

    After initialization, ``jax.devices()`` spans every host's chips and
    :func:`make_mesh` builds one global shard axis across them — ICI within
    a slice, DCN between hosts. This is the analog of the reference's
    multi-TaskManager deployment (SURVEY.md §2.9: its inter-host transport
    is Flink's Netty shuffle; here it is XLA collectives over DCN). Under a
    standard TPU pod launcher the arguments auto-detect (pass nothing).
    """
    kw = {}
    if coordinator_address is not None:
        kw["coordinator_address"] = coordinator_address
    if num_processes is not None:
        kw["num_processes"] = num_processes
    if process_id is not None:
        kw["process_id"] = process_id
    jax.distributed.initialize(**kw)
    global _COORDINATOR_ADDRESS
    _COORDINATOR_ADDRESS = coordinator_address


def host_info() -> dict:
    """This process's mesh identity — the host fields heartbeat lines
    and Chrome-trace ``otherData`` carry so multi-host captures are
    attributable per host: ``process_index`` / ``process_count`` (0/1
    on single-process runs) and the ``coordinator_address`` recorded by
    :func:`initialize_multihost` (None when not multihost)."""
    try:
        idx, cnt = jax.process_index(), jax.process_count()
    except Exception:  # pre-backend-init edge: identity is still useful
        idx, cnt = 0, 1
    return {
        "process_index": int(idx),
        "process_count": int(cnt),
        "coordinator_address": _COORDINATOR_ADDRESS,
    }


def make_mesh(num_shards: int | None = None, devices=None) -> Mesh:
    """A 1-D mesh over ``num_shards`` devices (default: all available)."""
    devs = list(devices if devices is not None else jax.devices())
    if num_shards is not None:
        if num_shards > len(devs):
            raise ValueError(
                f"requested {num_shards} shards but only {len(devs)} devices"
            )
        devs = devs[:num_shards]
    return Mesh(np.array(devs), (SHARD_AXIS,))


def num_shards(mesh: Mesh) -> int:
    return mesh.shape[SHARD_AXIS]


def shard_spec() -> P:
    """Partition along the shard axis (leading dim)."""
    return P(SHARD_AXIS)


def replicated_spec() -> P:
    return P()


def shard_map_fn(mesh: Mesh, fn, in_specs, out_specs, check_vma: bool = False):
    """Thin wrapper over jax.shard_map pinned to the stream mesh."""
    return jax.shard_map(
        fn, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
        check_vma=check_vma,
    )


def device_put_sharded_leading(mesh: Mesh, tree):
    """Place a pytree whose leaves have leading dim == num_shards, sharded."""
    sharding = NamedSharding(mesh, P(SHARD_AXIS))
    return jax.device_put(tree, sharding)


def device_put_replicated(mesh: Mesh, tree):
    sharding = NamedSharding(mesh, P())
    return jax.device_put(tree, sharding)
