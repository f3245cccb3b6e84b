"""Elastic remesh: shrink the device mesh after node failures.

Follows the asynchronous-relaxation direction of Devarakonda et al.
(arXiv:1712.06047): rather than blocking until a failed host returns, the
runner rebuilds on the largest mesh the surviving devices support. The model
(tensor-parallel) axis is preserved — params are sharded over it, so changing
it would reshard every weight; losing hosts only shrinks the data axis, which
costs throughput, not correctness (the CA-k schedule is batch-linear).
"""
from __future__ import annotations

import math
from typing import Optional, Sequence, Tuple

import numpy as np

from jax.sharding import Mesh


def largest_mesh_shape(n_devices: int, model_size: int) -> Tuple[int, int]:
    """Largest (data, model) shape on ``n_devices`` that keeps the model axis.

    data = floor(n / model), clamped to >= 1 (a mesh never vanishes: with
    fewer devices than model shards the caller keeps the model axis and
    oversubscribes — largest_mesh_shape(8, 16) == (1, 16) states the target
    shape, remesh() then clamps to what is physically placeable).
    """
    return (max(n_devices // model_size, 1), model_size)


def remesh(mesh: Mesh, devices: Optional[Sequence] = None) -> Mesh:
    """Rebuild ``mesh`` from the surviving devices, preserving axis names and
    the model-axis size wherever physically possible.

    Leading (pod/data) axes absorb the shrink: a (pod, data, model) mesh comes
    back as (1, data', model). Call after a failure with the current
    ``jax.devices()`` (default) or an explicit survivor list.
    """
    import jax
    devs = list(devices) if devices is not None else list(jax.devices())
    names = mesh.axis_names
    old_total = math.prod(mesh.shape.values())
    # shrink-only: failures remove capacity; a remesh never outgrows the job's
    # original allocation even when the host exposes more devices
    n = min(len(devs), old_total)
    if len(names) == 1:  # pure data mesh
        shape: Tuple[int, ...] = (max(n, 1),)
    else:
        model = mesh.shape[names[-1]]
        if model > n:  # cannot keep full TP: clamp to what exists
            model = max(n, 1)
        data, model = largest_mesh_shape(n, model)
        data = min(data, old_total // mesh.shape[names[-1]])
        shape = (1,) * (len(names) - 2) + (data, model)
    n = int(np.prod(shape))
    arr = np.array(devs[:n]).reshape(shape)
    return Mesh(arr, names)
