"""The Mamba-2 one-token state update of the live slots, as a Pallas TPU kernel.

A decode step moves the float32 state of every live slot by one token,

    h <- h * decay + dx (x) B        y = h . C

with ``decay = exp(dt A)`` per head and ``dx = dt xs`` per (head, channel)
(``models/ssm.py:mamba_step`` computes both, and B and C per group). The
pool is ``[slots, heads, head_dim, state]`` and rides the decode loop; at
Nemotron-3-Super's widths a slot's row is 4.2 MB and the pool 268 MB a
layer. This kernel is aliased to the pool and its grid walks the live slots,
found on the device from the ``active`` mask and handed in as scalar
prefetch: a live row is read once and written once, the read-out comes from
the same pass, and no other row is read or written. A grid step past the
last live slot maps to that slot's blocks again, so Mosaic neither fetches
nor writes anything for it.

The read-out sums over the state (the lanes). A lane reduction a vreg made
the kernel compute-bound; instead ``128 // head_dim`` heads' products with C
are stacked into one 128 x 128 tile, transposed, and summed over sublanes, so
``y`` comes out as ``[slots, heads * head_dim / 128, 128]``, the bytes of
``[slots, heads, head_dim]`` (PERF.md §6).

Rows that are not active read ``y = 0``: the ``y`` operand is a zero buffer
aliased to the output, and only live rows' blocks are written.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128


def ssm_update_ok(heads: int, head_dim: int, state: int) -> bool:
    """Shapes the kernel takes: a state of 128 (one vreg of lanes) and
    head_dim dividing 128 with whole tiles of heads; a row's state block,
    double-buffered in and out, within 64 MiB of VMEM."""
    return (
        state == LANES and LANES % head_dim == 0
        and heads % (LANES // head_dim) == 0
        and 4 * heads * head_dim * state * 4 <= 64 * 1024 * 1024
    )


def _kernel(live_ref, n_ref, pool_ref, decay_ref, dxt_ref, b_ref, c_ref, y0_ref,
            pool_out, y_out, *, per_group: int, per_tile: int):
    """One grid step = one live slot's row: ``pool_ref`` ``[1, H, P, N]``,
    ``decay_ref`` ``[1, 1, H]``, ``dxt_ref`` ``[1, P, H]`` (dx transposed,
    so a head's column broadcasts over the lanes), ``b_ref`` / ``c_ref``
    ``[1, G, N]``, ``y_out`` ``[1, H / per_tile, 128]``."""
    i = pl.program_id(0)
    n = n_ref[0]
    heads = pool_ref.shape[1]

    @pl.when(i < n)
    def _():
        dxt = dxt_ref[0]
        decay = decay_ref[0]
        for t in range(heads // per_tile):
            parts = []
            for h in range(t * per_tile, (t + 1) * per_tile):
                g = h // per_group
                hn = pool_ref[0, h] * decay[:, h:h + 1] + dxt[:, h:h + 1] * b_ref[0, g:g + 1, :]
                pool_out[0, h] = hn
                parts.append(hn * c_ref[0, g:g + 1, :])
            tile = jnp.concatenate(parts, axis=0).T            # [N, per_tile * P]
            y_out[0, t:t + 1, :] = jnp.sum(tile, axis=0, keepdims=True)

    @pl.when((n == 0) & (i == 0))
    def _():
        # nothing is live: every step maps to row live[0], whose blocks go
        # back as they came
        pool_out[...] = pool_ref[...]
        y_out[...] = y0_ref[...]


# jit: the kernel's body unrolls every head, and tracing and lowering it
# costs about half a second. Under its own jit that is paid once a process
# and a shape; inlined, it was paid for every layer of every decode program
# at each engine start (+24 s of warm set-up on Nemotron, PERF.md §6).
@functools.partial(jax.jit, static_argnames=("groups", "interpret"))
def ssm_update(
    pool: jax.Array,     # [B, H, P, N] float32, updated in place
    decay: jax.Array,    # [B, H] float32: exp(dt A)
    dx: jax.Array,       # [B, H, P] float32: dt xs
    Bm: jax.Array,       # [B, G, N] float32
    Cm: jax.Array,       # [B, G, N] float32
    active: jax.Array,   # [B] bool
    *,
    groups: int,
    interpret: bool = False,
) -> Tuple[jax.Array, jax.Array]:
    """``(pool', y [B, H, P])``: the live rows moved by one token, every
    other row of ``pool`` untouched and of ``y`` zero."""
    B, H, P, N = pool.shape
    per_tile = LANES // P
    live = jnp.nonzero(active, size=B, fill_value=0)[0].astype(jnp.int32)
    n = jnp.sum(active, dtype=jnp.int32)[None]

    def block(shape):
        def at(i, live_ref, n_ref):
            return (live_ref[jnp.minimum(i, jnp.maximum(n_ref[0] - 1, 0))],) + (0,) * (len(shape) - 1)
        return pl.BlockSpec(shape, at)

    y_shape = (B, H // per_tile, LANES)
    row_bytes = H * P * N * 4
    pool, y = pl.pallas_call(
        functools.partial(_kernel, per_group=H // groups, per_tile=per_tile),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(B,),
            in_specs=[
                block((1, H, P, N)), block((1, 1, H)), block((1, P, H)),
                block((1, groups, N)), block((1, groups, N)), block((1,) + y_shape[1:]),
            ],
            out_specs=[block((1, H, P, N)), block((1,) + y_shape[1:])],
        ),
        out_shape=[
            jax.ShapeDtypeStruct(pool.shape, pool.dtype),
            jax.ShapeDtypeStruct(y_shape, jnp.float32),
        ],
        input_output_aliases={2: 0, 7: 1},
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=4 * row_bytes + (16 << 20)),
        interpret=interpret,
        name="ssm_update",
    )(live, n, pool, decay[:, None, :], jnp.swapaxes(dx, 1, 2), Bm, Cm,
      jnp.zeros(y_shape, jnp.float32))
    return pool, y.reshape(B, H, P)
