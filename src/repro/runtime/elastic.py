"""Elastic scaling: resume a run on a different device count / mesh shape.

The checkpoint stores full logical arrays (checkpoint/manager.py), so scaling
is a matter of (1) choosing a new mesh for the surviving devices, (2) building
shardings for that mesh, (3) device_put on restore. ``choose_mesh_shape``
picks the (data, model) factorization for an arbitrary surviving chip count,
preferring to shrink the data axis first (keeps TP intact so per-chip layer
shards — and therefore compiled kernels' tile sizes — are unchanged).
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax

from repro.sharding import specs


def choose_mesh_shape(n_devices: int, *, model_parallel: int = 16,
                      with_pod_axis: bool = False) -> Tuple[Tuple[int, ...], Tuple[str, ...]]:
    """Largest (data, model) grid with model axis <= model_parallel that
    divides n_devices; shrinks model parallelism only when unavoidable."""
    mp = min(model_parallel, n_devices)
    while mp > 1 and n_devices % mp:
        mp //= 2
    dp = n_devices // mp
    if with_pod_axis and dp % 2 == 0 and dp > 1:
        return (2, dp // 2, mp), ("pod", "data", "model")
    return (dp, mp), ("data", "model")


def remesh(n_devices: Optional[int] = None, *, model_parallel: int = 16):
    devs = jax.devices()[: (n_devices or len(jax.devices()))]
    shape, axes = choose_mesh_shape(len(devs), model_parallel=model_parallel)
    return specs.make_mesh(shape, axes, devices=devs)


def elastic_restore(manager, model, optimizer, *, mesh, step=None):
    """Restore a train state onto `mesh` (any shape). Returns (state, meta)."""
    from repro.launch import steps as steps_mod
    with specs.use_mesh(mesh):
        state_sds = jax.eval_shape(
            lambda k: steps_mod.init_train_state(model, optimizer, k),
            jax.random.PRNGKey(0))
        shardings = steps_mod.state_shardings(model, state_sds)
        return manager.restore(step, shardings=shardings)
