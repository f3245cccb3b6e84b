"""Production mesh construction.

Defined as functions (never module-level constants) so importing this module
never touches jax device state — required for the dry-run's forced 512-device
host platform to initialize first.
"""
from __future__ import annotations

import jax
import numpy as np
from jax.sharding import AxisType, Mesh


def make_mesh(shape, names):
    """``jax.make_mesh`` with every axis ``Auto`` (compiler-sharded; its
    default is ``Explicit``)."""
    return jax.make_mesh(shape, names,
                         axis_types=(AxisType.Auto,) * len(names))


def spoof_mesh(shape, names):
    """Mesh of (possibly duplicated) host devices, for spec-only computation.

    ``Mesh`` accepts any ndarray of devices, so PartitionSpec inference for a
    512-chip production mesh runs on a 1-CPU host — nothing is ever placed on
    a spoofed mesh.
    """
    n = int(np.prod(shape))
    devs = np.array(list(jax.devices()) * n)[:n].reshape(shape)
    return Mesh(devs, names)                 # axes default to Auto


def make_production_mesh(*, multi_pod: bool = False):
    """Single pod: (data=16, model=16) = 256 chips (TPU v5e pod).
    Multi-pod: (pod=2, data=16, model=16) = 512 chips across DCN."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh():
    """Whatever this host offers (smoke tests / examples): 1 device -> 1x1."""
    n = len(jax.devices())
    model = 1
    for m in (4, 2, 1):
        if n % m == 0:
            model = m
            break
    return make_mesh((n // model, model), ("data", "model"))
