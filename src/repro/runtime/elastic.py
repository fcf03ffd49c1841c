"""Elastic scaling: restore a checkpoint onto a different mesh.

Checkpoints are mesh-independent (flat numpy), so elasticity reduces to
recomputing shardings for the surviving mesh and ``device_put``-ing each
leaf.  ``shrink_mesh`` models the coordinator's decision after node loss:
drop the data-parallel extent to the largest power-of-two that the remaining
chips support (model-parallel extent is preserved — TP groups must stay
intact, only whole DP replicas are dropped).
"""
from __future__ import annotations

from typing import Optional

import jax

from repro.sharding import DistContext
from repro.train.checkpoint import CheckpointManager


def shrink_mesh(devices_left: int, model: int, pod: int = 0):
    """Largest (data, model) mesh from the surviving chips, TP preserved."""
    if devices_left < model:
        raise ValueError(f"cannot keep TP={model} with {devices_left} chips")
    data = 1
    while data * 2 * model * max(pod, 1) <= devices_left:
        data *= 2
    shape = (pod, data, model) if pod else (data, model)
    names = ("pod", "data", "model") if pod else ("data", "model")
    return jax.make_mesh(
        shape, names, axis_types=(jax.sharding.AxisType.Auto,) * len(shape))


def restore_on_mesh(ckpt: CheckpointManager, template, logical_specs,
                    dist: DistContext, step: Optional[int] = None):
    """Restore ``template``-shaped state, placed per ``logical_specs`` on the
    (new) mesh carried by ``dist``."""
    shardings = jax.tree.map(
        lambda sp: dist.sharding(sp), logical_specs,
        is_leaf=lambda x: hasattr(x, "index") or type(x).__name__ == "PartitionSpec")
    return ckpt.restore(template, step=step, shardings=shardings)
