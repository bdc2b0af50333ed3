"""Production mesh construction (+ BandPilot-ordered device selection).

``make_production_mesh`` is a FUNCTION (importing this module never touches
jax device state): single-pod (16, 16) = 256 chips with ("data", "model")
axes, or multi-pod (2, 16, 16) = 512 chips with ("pod", "data", "model").
Axis placement follows TPU practice: the fast ICI fabric carries the
"model" (TP/EP) axis, "data" runs FSDP over ICI, and the slow DCN fabric
carries the "pod" axis (pure DP / optional pipeline).

``bandpilot_mesh`` is the framework integration of the paper: given a device
pool and a request size, BandPilot selects *which* devices form the mesh
(balanced across hosts to maximize collective bandwidth) and orders them
host-major so the mesh's fastest-changing axis stays intra-host.
"""

from __future__ import annotations

from typing import List, Optional, Sequence, Tuple

import jax
import numpy as np


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    # Auto axes: the model code places activations with
    # with_sharding_constraint, which refuses make_mesh's Explicit default.
    return jax.make_mesh(
        shape, axes, axis_types=(jax.sharding.AxisType.Auto,) * len(axes)
    )


def make_mesh_from_devices(
    devices: Sequence, shape: Tuple[int, ...], axes: Tuple[str, ...]
):
    """Build a Mesh over an explicit (BandPilot-ordered) device list."""
    arr = np.asarray(devices, dtype=object).reshape(shape)
    return jax.sharding.Mesh(arr, axes)


def bandpilot_device_order(
    dispatcher,
    avail_ids: Sequence[int],
    k: int,
) -> List[int]:
    """Dispatch k device ids via BandPilot and order them host-major.

    The returned order is used to lay out the mesh so that consecutive mesh
    columns (the highest-traffic axis) stay on the same host where possible.
    """
    subset = dispatcher.dispatch(list(avail_ids), k)
    cluster = dispatcher.cluster
    return sorted(subset, key=lambda g: (cluster.gpu_host[g], cluster.gpu_local[g]))


def bandpilot_mesh(
    dispatcher,
    devices: Sequence,
    k: int,
    shape: Tuple[int, ...],
    axes: Tuple[str, ...],
    avail_ids: Optional[Sequence[int]] = None,
):
    """Select + order k devices with BandPilot, then build the mesh.

    ``devices[i]`` is assumed to correspond to cluster GPU id ``i`` (the
    launcher keeps that mapping).  Falls back to the first k devices if the
    dispatcher is None.
    """
    if avail_ids is None:
        avail_ids = range(len(devices))
    if dispatcher is None:
        chosen = list(avail_ids)[:k]
    else:
        chosen = bandpilot_device_order(dispatcher, avail_ids, k)
    dev_list = [devices[i] for i in chosen]
    return make_mesh_from_devices(dev_list, shape, axes), chosen
