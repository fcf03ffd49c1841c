"""Production mesh construction.

A FUNCTION (not module-level state) so importing never touches jax device
initialization.  Single pod: (data=16, model=16) = 256 chips (TPU v5e pod).
Multi-pod: (pod=2, data=16, model=16) = 512 chips; the 'pod' axis carries
hierarchical data parallelism (gradient all-reduce staged within-pod first,
then across the slow inter-pod links).
"""
from __future__ import annotations

import jax


def _mesh(shape, axes):
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mesh(shape, axes)


def make_host_mesh(data: int = 1, model: int = 1, pod: int = 0):
    """Small explicit mesh for CPU integration tests."""
    if pod:
        return _mesh((pod, data, model), ("pod", "data", "model"))
    return _mesh((data, model), ("data", "model"))


def make_spatial_mesh(sp_h: int, sp_w: int = 1, data: int = 1):
    """Mesh for plane-parallel conv execution (``core.spatial``): 'sp_h' /
    'sp_w' carry one conv plane's rows/cols (the ``DEFAULT_RULES``
    'plane_h'/'plane_w' targets).  The leading 'data' axis (extent 1 by
    default) keeps batch parallelism alive and lets the serving layer's
    ``image_spec`` constraints resolve on this mesh unchanged.  Axis order
    is (data, sp_h, sp_w) so neighbouring spatial shards land on
    neighbouring devices — the halo ``ppermute`` is a nearest-neighbour
    hop on ring interconnects."""
    return _mesh((data, sp_h, sp_w), ("data", "sp_h", "sp_w"))
