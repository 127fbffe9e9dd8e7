"""Mesh construction helpers."""
from __future__ import annotations

from typing import Optional, Sequence, Tuple


def make_mesh(axis_sizes: Sequence[int], axis_names: Sequence[str],
              devices=None):
    import jax
    import numpy as np

    devices = devices if devices is not None else jax.devices()
    n = int(np.prod(axis_sizes))
    if n > len(devices):
        raise ValueError("mesh wants %d devices, only %d available"
                         % (n, len(devices)))
    arr = np.array(devices[:n]).reshape(tuple(axis_sizes))
    return jax.sharding.Mesh(arr, tuple(axis_names))


def default_mesh(num_devices: Optional[int] = None, axis_name: str = "dp"):
    import jax

    devs = jax.devices()
    n = num_devices or len(devs)
    return make_mesh([n], [axis_name], devs)


def mesh_key(mesh) -> Tuple:
    """Stable mesh identity for executable-cache keys: id(mesh) can be
    reused by a new mesh after GC and alias a stale executable compiled
    for different devices."""
    return (tuple(d.id for d in mesh.devices.flat),
            tuple(mesh.axis_names))
