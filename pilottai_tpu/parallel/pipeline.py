"""Pipeline parallelism: GPipe-style microbatch pipeline over a 'stage'
mesh axis.

Layers are split into contiguous stage groups; each device in the
``stage`` axis holds one group's parameters and activations flow
stage-to-stage over ICI via ``ppermute``. Microbatches fill the pipeline
(n_micro + n_stages - 1 ticks); the bubble fraction is
(n_stages - 1) / (n_micro + n_stages - 1), so callers pick
n_micro >= n_stages for decent utilization. Differentiable end to end
(ppermute transposes to the reverse rotation), so the same primitive
serves training.

This is the standalone pp building block; the transformer trainer
composes it with the other axes (dp/fsdp/tp/sp/ep) by splitting the
layer stack into stage groups.

No reference counterpart (SURVEY.md §2.13).
"""

from __future__ import annotations

from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P


def pipeline_apply(
    block_fn: Callable[[Any, jax.Array], jax.Array],
    stage_params: Any,        # pytree; leaves have leading [n_stages] axis
    x: jax.Array,             # [n_micro, mb, ...] microbatched input
    mesh: Mesh,
    axis: str = "stage",
    batch_axes: tuple = ("data", "fsdp"),
) -> jax.Array:
    """Run ``block_fn`` over ``n_stages`` pipeline stages.

    ``block_fn(params_for_stage, activation) -> activation`` must preserve
    the activation shape (classic transformer trunk). Microbatch i's
    output appears in slot i of the returned [n_micro, mb, ...] array.
    """
    n_stages = mesh.shape[axis]
    n_micro = x.shape[0]
    total_ticks = n_micro + n_stages - 1

    param_specs = jax.tree.map(lambda _: P(axis), stage_params)
    x_spec = P(None, batch_axes)  # microbatch axis replicated across stages

    def per_stage(params, x):
        # params: this stage's group (leading axis stripped by shard_map
        # to size 1) — squeeze it.
        params = jax.tree.map(lambda a: a[0], params)
        stage = jax.lax.axis_index(axis)
        mb_shape = x.shape[1:]

        fwd_perm = [(j, (j + 1) % n_stages) for j in range(n_stages)]
        carry = jnp.zeros(mb_shape, x.dtype)      # current inbound activation
        out = jnp.zeros_like(x)                    # last stage accumulates

        for tick in range(total_ticks):
            # Stage 0 ingests microbatch `tick` (when one remains).
            mb_idx = min(tick, n_micro - 1)
            inbound = jnp.where(stage == 0, x[mb_idx], carry)
            y = block_fn(params, inbound)
            # Which microbatch is this stage holding at this tick?
            held = tick - stage                    # traced via `stage`
            live = (held >= 0) & (held < n_micro)
            y = jnp.where(live, y, jnp.zeros_like(y))
            # Last stage deposits its finished microbatch.
            is_last = stage == n_stages - 1
            slot = jnp.clip(held, 0, n_micro - 1)
            deposit = jnp.where(live & is_last, y, jnp.zeros_like(y))
            out = out.at[slot].add(deposit)
            # Rotate activations forward (last→0 wraps but stage 0 ignores
            # its inbound, so the wrap is harmless).
            carry = jax.lax.ppermute(y, axis, fwd_perm)

        # Only the last stage holds real outputs; share them along the ring.
        out = jax.lax.psum(
            jnp.where(stage == n_stages - 1, out, jnp.zeros_like(out)),
            axis,
        )
        return out

    return jax.shard_map(
        per_stage,
        mesh=mesh,
        in_specs=(param_specs, x_spec),
        out_specs=x_spec,
        check_vma=False,
    )(stage_params, x)


def split_layers_to_stages(stacked_params: Any, n_stages: int) -> Any:
    """Reshape stacked-layer params [L, ...] -> [n_stages, L/n_stages, ...]."""

    def split(leaf):
        L = leaf.shape[0]
        assert L % n_stages == 0, f"{L} layers not divisible by {n_stages} stages"
        return leaf.reshape(n_stages, L // n_stages, *leaf.shape[1:])

    return jax.tree.map(split, stacked_params)
