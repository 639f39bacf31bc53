"""Pipeline parallelism over the 'pod' (or any) mesh axis: GPipe schedule via
shard_map + collective_permute.

Each pipeline stage owns L/P contiguous layers (stage-stacked params). The
microbatch loop runs as a lax.scan over (n_micro + P - 1) ticks; at each tick
a stage processes the activation it holds and collective_permutes it to the
next stage. Bubble fraction = (P-1)/(n_micro+P-1), the GPipe bound.

This is the inter-POD alternative to pure DP when a model's layers do not fit
a single pod's HBM even fully sharded: `PIPELINE_RULES` in sharding/specs.py
re-maps 'batch' to the data axis only, and stage params get the 'stage' axis.
"""
from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


def _bcast_from(x, axis_name, src):
    """Broadcast x from shard `src` along axis_name to all shards."""
    idx = jax.lax.axis_index(axis_name)
    masked = jnp.where(idx == src, x, jnp.zeros_like(x))
    return jax.lax.psum(masked, axis_name)


def make_pipeline(mesh, stage_fn: Callable, params_spec=None, *,
                  stage_axis: str = "pod", n_micro: int):
    """GPipe pipeline for stage-stacked params.

    stage_fn(stage_params, x) -> x applies ONE stage's layers.

    Returns pipe(stage_params, x_micro):
      stage_params leaves: [P, ...] sharded over stage_axis (leading dim)
      x_micro: (n_micro, B_micro, ...) replicated over stage_axis
      -> (n_micro, B_micro, ...) final-stage outputs (valid on every shard)
    """
    n_stages = mesh.shape[stage_axis]

    def per_stage(params_stage, x_micro):
        params_local = jax.tree.map(lambda t: t[0], params_stage)
        stage_id = jax.lax.axis_index(stage_axis)
        n_ticks = n_micro + n_stages - 1
        buf = jnp.zeros_like(x_micro[0])
        outs = jnp.zeros_like(x_micro)

        def tick(carry, t):
            buf, outs = carry
            inject = jnp.where(t < n_micro, t, 0)
            x_in = jnp.where(stage_id == 0, x_micro[inject].astype(buf.dtype),
                             buf)
            active = (t - stage_id >= 0) & (t - stage_id < n_micro)
            y = stage_fn(params_local, x_in)
            y = jnp.where(active, y, x_in)
            # last stage records its finished microbatch
            mb = jnp.clip(t - (n_stages - 1), 0, n_micro - 1)
            write = (active & (stage_id == n_stages - 1)).astype(outs.dtype)
            cur = jax.lax.dynamic_index_in_dim(outs, mb, 0, keepdims=False)
            upd = write * y + (1 - write) * cur
            outs = jax.lax.dynamic_update_index_in_dim(outs, upd, mb, 0)
            # shift activations to the next stage (ring; wraparound unused)
            perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]
            buf = jax.lax.ppermute(y, stage_axis, perm)
            return (buf, outs), None

        (_, outs), _ = jax.lax.scan(tick, (buf, outs), jnp.arange(n_ticks))
        return _bcast_from(outs, stage_axis, n_stages - 1)

    if params_spec is None:
        params_spec = P(stage_axis)
    return jax.shard_map(per_stage, mesh=mesh,
                         in_specs=(params_spec, P()),
                         out_specs=P(), check_vma=False)


def bubble_fraction(n_stages: int, n_micro: int) -> float:
    return (n_stages - 1) / (n_micro + n_stages - 1)
