"""Single-token (decode) GQA attention as a Pallas TPU kernel.

The decode hot path reads the whole KV cache every token; XLA's batched
tiny matvecs ([G, H] x [H, S] per (batch, kv-head)) stream it at a
fraction of HBM bandwidth. This kernel makes the cache read the *only*
traffic: grid (B, K/Kb), each cell DMAs contiguous [Kb, S, H] K/V panels
into VMEM once (pipelined across grid steps by Mosaic) and does the
q.K^T -> softmax -> .V chain on-chip in fp32.

Cache layout is K-major ([B, K, S, H]) so each grid cell's panels are
contiguous HBM regions — the S-reduction never strides across heads.

Two modes:
* ``return_stats=False`` — normalized attention output (drop-in for the
  dense path).
* ``return_stats=True`` — unnormalized (acc, m, l) online-softmax stats,
  so the decode chunk can combine this *read-only prefix* pass with a
  small in-chunk attention over tokens generated since the last cache
  write (``engine/decode.py``). Read-only matters: a kernel that wrote
  the cache would force XLA to copy the panels around every custom call
  inside the chunk scan.

No reference counterpart (the reference computes no attention at all,
SURVEY.md §2.13); this is the serving engine's per-token hot op.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -2.0**30

# K+V panel bytes per grid cell. Mosaic's scoped allocation lands at ~4x
# this (double-buffered panels + fp32 score intermediates), and the v5e
# VMEM limit is 16 MiB — 3 MiB panels keep ~4 MiB of headroom.
_DECODE_KV_VMEM_BUDGET = 3 * 1024 * 1024


def decode_shapes_ok(S: int, head_dim: int, itemsize: int = 2) -> bool:
    """Even one kv-head per cell must fit the VMEM budget."""
    return 2 * S * head_dim * itemsize <= _DECODE_KV_VMEM_BUDGET


def _decode_kernel(
    last_ref,  # SMEM (B,) int32 (scalar prefetch) — max valid key index
    qpos_ref,  # SMEM (B,) int32 (scalar prefetch) — query absolute position
    q_ref,     # VMEM (1, Kb, G, H)
    k_ref,     # VMEM (1, Kb, S, H)
    v_ref,     # VMEM (1, Kb, S, H)
    *o_refs,
    scale: float,
    softcap: float,
    window: int,
    return_stats: bool,
):
    b = pl.program_id(0)
    last = last_ref[b]
    qpos = qpos_ref[b]

    q = q_ref[0]                                          # [Kb, G, H]
    k = k_ref[0]                                          # [Kb, S, H]
    v = v_ref[0]

    # Batched over the Kb kv-heads resident in this cell: one MXU call
    # instead of Kb tiny ones.
    s = jax.lax.dot_general(
        q, k,
        dimension_numbers=(((2,), (2,)), ((0,), (0,))),
        preferred_element_type=jnp.float32,
    ) * scale                                             # [Kb, G, S]
    if softcap > 0.0:
        s = jnp.tanh(s / softcap) * softcap

    col = jax.lax.broadcasted_iota(jnp.int32, s.shape, 2)
    mask = col <= last
    if window > 0:
        mask &= (qpos - col) < window
    s = jnp.where(mask, s, NEG_INF)

    m = jnp.max(s, axis=-1, keepdims=True)                # [Kb, G, 1]
    p = jnp.where(m > NEG_INF / 2, jnp.exp(s - m), 0.0)   # fully-masked rows
    denom = jnp.sum(p, axis=-1, keepdims=True)

    if return_stats:
        acc = jax.lax.dot_general(
            p.astype(v.dtype), v,
            dimension_numbers=(((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32,
        )                                                 # [Kb, G, H] fp32
        o_refs[0][0] = acc
        o_refs[1][0] = m
        o_refs[2][0] = denom
    else:
        w = (p / jnp.maximum(denom, 1e-30)).astype(v.dtype)
        o = jax.lax.dot_general(
            w, v,
            dimension_numbers=(((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32,
        )
        o_refs[0][0] = o.astype(o_refs[0].dtype)


@functools.partial(
    jax.jit,
    static_argnames=("scale", "softcap", "window", "return_stats", "interpret"),
)
def decode_attention(
    q: jax.Array,          # [B, N, H] current-token queries
    k_cache: jax.Array,    # [B, K, S, H] (K-major cache layout)
    v_cache: jax.Array,    # [B, K, S, H]
    last_valid: jax.Array,  # [B] int32 — keys at s <= last_valid[b] attend
    q_positions: Optional[jax.Array] = None,  # [B] int32 — for the sliding
                           # window; defaults to last_valid (self-decode)
    scale: Optional[float] = None,
    softcap: float = 0.0,
    window: int = 0,
    return_stats: bool = False,
    interpret: bool = False,
):
    """GQA decode attention against a fixed-size cache.

    Attend iff s <= last_valid[b] and (window == 0 or
    q_positions[b] - s < window). Returns [B, N, H], or with
    ``return_stats`` the unnormalized ``(acc [B,N,H] fp32, m [B,N],
    l [B,N])`` online-softmax triple.
    """
    B, N, H = q.shape
    _, K, S, _ = k_cache.shape
    assert N % K == 0
    G = N // K
    scale = scale if scale is not None else H ** -0.5

    qg = q.reshape(B, K, G, H)
    last_valid = jnp.asarray(last_valid, jnp.int32).reshape(B)
    if q_positions is None:
        q_positions = last_valid
    q_positions = jnp.asarray(q_positions, jnp.int32).reshape(B)

    # Largest kv-head chunk whose K+V panels fit the VMEM budget — bigger
    # panels amortize per-grid-cell pipeline cost.
    itemsize = jnp.dtype(k_cache.dtype).itemsize
    Kb = K
    while Kb > 1 and 2 * Kb * S * H * itemsize > _DECODE_KV_VMEM_BUDGET:
        Kb //= 2

    kernel = functools.partial(
        _decode_kernel,
        scale=scale, softcap=softcap, window=window, return_stats=return_stats,
    )
    if return_stats:
        # m/l carry a trailing singleton so the last two block dims stay
        # equal to the array dims (Mosaic tiling rule) even when Kb < K.
        out_shape = (
            jax.ShapeDtypeStruct((B, K, G, H), jnp.float32),
            jax.ShapeDtypeStruct((B, K, G, 1), jnp.float32),
            jax.ShapeDtypeStruct((B, K, G, 1), jnp.float32),
        )
        out_specs = (
            pl.BlockSpec((1, Kb, G, H), lambda b, k, *_: (b, k, 0, 0)),
            pl.BlockSpec((1, Kb, G, 1), lambda b, k, *_: (b, k, 0, 0)),
            pl.BlockSpec((1, Kb, G, 1), lambda b, k, *_: (b, k, 0, 0)),
        )
    else:
        out_shape = jax.ShapeDtypeStruct((B, K, G, H), q.dtype)
        out_specs = pl.BlockSpec((1, Kb, G, H), lambda b, k, *_: (b, k, 0, 0))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,  # last_valid, q_positions land in SMEM
        grid=(B, K // Kb),
        in_specs=[
            pl.BlockSpec((1, Kb, G, H), lambda b, k, *_: (b, k, 0, 0)),
            pl.BlockSpec((1, Kb, S, H), lambda b, k, *_: (b, k, 0, 0)),
            pl.BlockSpec((1, Kb, S, H), lambda b, k, *_: (b, k, 0, 0)),
        ],
        out_specs=out_specs,
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=out_shape,
        interpret=interpret,
        name="decode_attention",
    )(last_valid, q_positions, qg, k_cache, v_cache)
    if return_stats:
        acc, m, l = out
        return acc.reshape(B, N, H), m.reshape(B, N), l.reshape(B, N)
    return out.reshape(B, N, H)


__all__ = ["decode_attention", "decode_shapes_ok"]
