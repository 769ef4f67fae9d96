"""Device-mesh helpers.

The reference's device set is an explicit list of Contexts handed to Module
(python/mxnet/module/module.py ctx list); collective layout is implicit in
KVStore type. On TPU the device set is a ``jax.sharding.Mesh`` with named axes,
and every collective is an XLA op over an axis. These helpers build the standard
meshes (data/tensor/pipeline/sequence) over ``jax.devices()`` — real chips, or
the virtual CPU devices of a process pinned to the CPU platform (the analog of
the reference's CPU-fake-device trick,
tests/python/unittest/test_multi_device_exec.py:20-33). A mesh that does not
fit the default platform is an error, never a silent move to host devices.
"""
from __future__ import annotations

import numpy as np

__all__ = ["build_mesh", "local_mesh", "mesh_axis_size"]


def build_mesh(axis_sizes, devices=None):
    """Build a Mesh from {"axis": size} (in order). size -1 means "rest".

    Example: build_mesh({"dp": -1, "tp": 2}) on 8 devices → 4x2 mesh.
    """
    import jax
    from jax.sharding import Mesh

    if devices is None:
        devices = jax.devices()
    names = list(axis_sizes.keys())

    sizes = list(axis_sizes.values())
    if -1 in sizes:  # -1 takes the rest
        known = int(np.prod([s for s in sizes if s != -1])) if len(sizes) > 1 else 1
        sizes[sizes.index(-1)] = len(devices) // known
    total = int(np.prod(sizes))
    if total == 0 or total > len(devices):
        raise ValueError(
            "mesh %s needs %s devices, have %d" % (axis_sizes, total or "more",
                                                   len(devices)))
    arr = np.asarray(devices[:total]).reshape(sizes)
    return Mesh(arr, names)


def local_mesh(n=None, axis="dp"):
    """1-D mesh over the first n local devices."""
    import jax

    devices = jax.devices()
    if n is not None:
        devices = devices[:n]
    return build_mesh({axis: len(devices)}, devices)


def mesh_axis_size(mesh, axis):
    return mesh.shape[axis]
