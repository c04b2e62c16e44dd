"""Topology discovery and executor<->chip mapping (L2).

The reference's bootstrap publishes each executor's UCX worker address and lets the
driver introduce members (rpc/UcxDriverRpcEndpoint.scala:21-42); the TPU analogue
must additionally discover the *slice topology* so executors map onto chips in ICI
order (BASELINE.json north star: "executor bootstrap discovers the TPU slice
topology to build the executor<->chip mapping").

``discover_topology`` inspects the JAX backend; ``executor_mesh`` orders devices by
their physical coords so mesh-adjacent executors are ICI neighbors (XLA schedules
ragged all_to_all over neighbor links; a coords-sorted ring keeps per-hop distance
minimal on v4/v5 tori).  ``init_distributed`` wraps ``jax.distributed.initialize``
— the multi-controller analogue of the reference's driver RpcEnv bootstrap
(CommonUcxShuffleManager.scala:45-62).
"""

from __future__ import annotations

import os
from dataclasses import dataclass
from typing import List, Optional, Sequence, Tuple

import numpy as np
from jax.sharding import Mesh


@dataclass(frozen=True)
class TopologyInfo:
    platform: str
    num_devices: int
    num_local_devices: int
    process_index: int
    process_count: int
    device_kinds: Tuple[str, ...]
    coords: Tuple[Optional[Tuple[int, ...]], ...]  # physical chip coords when exposed

    @property
    def is_tpu(self) -> bool:
        return self.platform == "tpu"

    @property
    def multi_host(self) -> bool:
        return self.process_count > 1


def discover_topology() -> TopologyInfo:
    import jax

    devices = jax.devices()
    coords = tuple(getattr(d, "coords", None) for d in devices)
    return TopologyInfo(
        platform=devices[0].platform,
        num_devices=len(devices),
        num_local_devices=len(jax.local_devices()),
        process_index=jax.process_index(),
        process_count=jax.process_count(),
        device_kinds=tuple(d.device_kind for d in devices),
        coords=coords,
    )


def _ici_order(devices: Sequence) -> List:
    """Order devices so consecutive executors are physical ICI neighbors.

    Snake-orders by (z, y, x) coords when the backend exposes them (TPU), so the
    1-D executor ring embeds into the torus with unit-distance hops; otherwise
    keeps backend order (CPU/GPU test meshes)."""
    coords = [getattr(d, "coords", None) for d in devices]
    if any(c is None for c in coords):
        return list(devices)

    def key(d):
        c = d.coords
        # snake along x within each y-row to keep wraparound hops short
        x, y, z = (list(c) + [0, 0, 0])[:3]
        sx = x if y % 2 == 0 else -x
        return (z, y, sx, getattr(d, "core_on_chip", 0))

    return sorted(devices, key=key)


def executor_mesh(
    num_executors: int, axis_name: str = "ex", devices: Optional[Sequence] = None
) -> Mesh:
    """The executor mesh, ICI-ordered.  One executor per chip, mirroring the
    reference's one-transport-per-executor model
    (CommonUcxShuffleManager.scala:67-99)."""
    import jax

    devs = _ici_order(list(devices if devices is not None else jax.devices()))
    if len(devs) < num_executors:
        raise ValueError(f"need {num_executors} devices, have {len(devs)}")
    return Mesh(np.array(devs[:num_executors]), (axis_name,))


def surviving_submesh(mesh: Mesh, phys: Sequence[int], axis_name: Optional[str] = None) -> Mesh:
    """The shrunk mesh for degraded-mode recovery (elastic.enabled): the
    devices of the surviving executor slots ``phys`` (already the pow2 bucket
    chosen by ``shuffle.resolver.degraded_plan``), in the full mesh's ICI
    order.  Preserving the parent's device order keeps surviving neighbors
    ICI-adjacent — the shrunk ring is a sub-ring of the full ring, so no
    re-ordering (and no new topology probe) is needed."""
    flat = list(mesh.devices.reshape(-1))
    devs = [flat[p] for p in phys]
    return Mesh(np.array(devs), (axis_name or mesh.axis_names[0],))


def executor_for_device(mesh: Mesh, device) -> int:
    flat = list(mesh.devices.reshape(-1))
    return flat.index(device)


def init_distributed(
    coordinator_address: Optional[str] = None,
    num_processes: Optional[int] = None,
    process_id: Optional[int] = None,
) -> TopologyInfo:
    """Multi-host bootstrap: initialize the JAX coordination service, then
    discover the global topology.  On TPU pods the arguments are auto-detected
    from the environment; explicit values serve CPU/GPU clusters.

    This replaces the reference's dedicated "ucx-rpc-env" + driver endpoint
    address exchange (CommonUcxShuffleManager.scala:73-99): the coordination
    service plays the driver, ``jax.devices()`` after init plays
    ``IntroduceAllExecutors``."""
    import jax

    if jax.process_count() == 1 and (coordinator_address or os.environ.get("JAX_COORDINATOR_ADDRESS")):
        jax.distributed.initialize(
            coordinator_address=coordinator_address,
            num_processes=num_processes,
            process_id=process_id,
        )
    return discover_topology()
