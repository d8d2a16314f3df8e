"""Device mesh helpers.

The reference's distribution layer is a planned-only sharded vector index
(docs/architecture/clustering-roadmap.md, "Sharded ... Planned") plus a
host-side TCP transport (pkg/replication/transport.go). The TPU-native design
promotes the data plane to first-class XLA collectives over ICI: pick a Mesh,
annotate shardings, let XLA insert the collectives (scaling-book recipe).

Axis conventions used across the framework:
  "data"  — shards the corpus / batch dimension (vector search, DP training)
  "model" — shards model weights (TP)
  "seq"   — shards the sequence dimension (ring attention / context parallel)
"""

from __future__ import annotations

from typing import Optional, Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def make_mesh(
    axis_shapes: Optional[dict[str, int]] = None,
    devices: Optional[Sequence[jax.Device]] = None,
    backend=None,
) -> Mesh:
    """Build a Mesh. Default: all devices on one "data" axis.

    make_mesh({"data": 4, "model": 2}) lays an 8-device mesh as 4x2.

    Enumerating devices is a COLD backend acquisition (PJRT init on a
    fresh process), so the default goes through the backend lifecycle
    manager: bounded wait on its worker thread, DeviceUnavailable when
    the backend is degraded — never an unbounded hang on the caller.
    ``backend`` injects a specific BackendManager (tests).
    """
    if devices is not None:
        devs = list(devices)
    elif backend is not None:
        if not backend.await_ready():
            from nornicdb_tpu.errors import DeviceUnavailable

            raise DeviceUnavailable(
                f"backend {backend.state}: cannot enumerate mesh devices"
            )
        devs = list(jax.devices())
    else:
        from nornicdb_tpu import backend as _backend

        devs = list(_backend.devices())
    if not axis_shapes:
        axis_shapes = {"data": len(devs)}
    names = tuple(axis_shapes)
    shape = tuple(axis_shapes[n] for n in names)
    total = int(np.prod(shape))
    if total != len(devs):
        raise ValueError(f"mesh shape {shape} needs {total} devices, have {len(devs)}")
    arr = np.array(devs).reshape(shape)
    return Mesh(arr, names)


def data_sharding(mesh: Mesh, axis: str = "data") -> NamedSharding:
    """Rows sharded across `axis`, features replicated."""
    return NamedSharding(mesh, P(axis, None))


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


def local_device_count() -> int:
    # gated: device enumeration is a cold backend acquisition
    from nornicdb_tpu import backend as _backend

    return len(_backend.devices())


def can_shard() -> bool:
    """True when a mesh data plane is worth building: more than one
    device is reachable through the backend manager.  Raises
    DeviceUnavailable (from the gated enumeration) while degraded — the
    caller decides whether to retry or pin single-device serving."""
    return local_device_count() > 1
