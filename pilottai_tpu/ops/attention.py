"""Attention kernels: grouped-query attention with optional logit soft-cap
and sliding windows, in pure XLA (the Pallas flash kernel in
``pilottai_tpu/ops/pallas`` is used for large prefills; this path is the
reference implementation and the decode path).

Design notes (TPU):
* softmax statistics in float32, matmuls in bfloat16 — the MXU accumulates
  in fp32 anyway, so only the exp/sum need explicit widening;
* GQA is expressed by reshaping queries to [B, K, G, T, H] and batching the
  einsum over kv-heads, which XLA tiles onto the MXU without materializing
  repeated K/V.
"""

from __future__ import annotations

import os
from typing import Optional

import jax
import jax.numpy as jnp

NEG_INF = -2.0**30  # large negative, safe in bf16 after cast


def flash_enabled() -> bool:
    """Use the Pallas flash kernel for full-sequence attention on TPU.

    Gated off on CPU (interpret mode is far slower than XLA there) and by
    ``PILOTTAI_NO_FLASH=1`` for A/B comparison."""
    if os.environ.get("PILOTTAI_NO_FLASH"):
        return False
    return jax.default_backend() == "tpu"


_FLASH_KV_VMEM_BUDGET = 8 * 1024 * 1024  # bytes for resident K+V per grid cell


def flash_shapes_ok(
    T: int,
    S: int,
    head_dim: int = 128,
    itemsize: int = 2,
    block_q: int = 128,
    block_k: int = 128,
) -> bool:
    """Size floor plus a VMEM bound: the kernel keeps the full [S, H]
    K and V resident (double-buffered by the pipeline), so the PADDED S
    must fit the budget or Mosaic fails allocation where XLA would have
    run. Ragged T/S are fine — ``flash_attention`` pads to block
    multiples internally (VERDICT r2 next-step 8); only tiny shapes,
    where the pad waste dwarfs the work, stay on XLA."""
    if T < 16 or S < 16:
        return False
    s_padded = -(-S // block_k) * block_k
    kv_bytes = 2 * s_padded * head_dim * itemsize * 2  # K+V, double-buffered
    return kv_bytes <= _FLASH_KV_VMEM_BUDGET


@jax.named_scope("attn")
def dot_product_attention(
    q: jax.Array,  # [B, T, N, H]
    k: jax.Array,  # [B, S, K, H]
    v: jax.Array,  # [B, S, K, H]
    mask: Optional[jax.Array] = None,  # [B, 1, T, S] or [B, T, S], True = attend
    scale: Optional[float] = None,
    logit_softcap: float = 0.0,
) -> jax.Array:
    """Grouped-query attention. Returns [B, T, N, H]."""
    B, T, N, H = q.shape
    _, S, K, _ = k.shape
    assert N % K == 0, f"query heads {N} not divisible by kv heads {K}"
    G = N // K
    scale = scale if scale is not None else H ** -0.5

    q = q.reshape(B, T, K, G, H)
    # [B, K, G, T, S]
    logits = jnp.einsum("btkgh,bskh->bkgts", q, k).astype(jnp.float32) * scale
    if logit_softcap > 0.0:
        logits = jnp.tanh(logits / logit_softcap) * logit_softcap
    if mask is not None:
        if mask.ndim == 3:
            mask = mask[:, None, :, :]
        # mask [B, 1, T, S] -> broadcast over (K, G)
        logits = jnp.where(mask[:, :, None, :, :], logits, NEG_INF)
    weights = jax.nn.softmax(logits, axis=-1).astype(v.dtype)
    out = jnp.einsum("bkgts,bskh->btkgh", weights, v)
    return out.reshape(B, T, N, H)


def causal_mask(T: int, dtype=jnp.bool_) -> jax.Array:
    """[T, T] lower-triangular causal mask."""
    return jnp.tril(jnp.ones((T, T), dtype=dtype))


def make_attention_mask(
    q_positions: jax.Array,  # [B, T] absolute positions of the query tokens
    kv_length: int,          # S — static cache length
    kv_valid: jax.Array,     # [B] number of valid cache entries (incl. current)
    window: int = 0,         # 0 = global; >0 = sliding window size
) -> jax.Array:
    """Causal (+ optional sliding-window) mask against a fixed-size cache.

    True where query at absolute position p may attend cache slot j, i.e.
    j <= p, j < kv_valid, and (window == 0 or p - j < window). Cache slot j
    holds the token at absolute position j (contiguous cache).
    Returns [B, T, S].
    """
    j = jnp.arange(kv_length)[None, None, :]          # [1, 1, S]
    p = q_positions[:, :, None]                        # [B, T, 1]
    mask = (j <= p) & (j < kv_valid[:, None, None])
    if window > 0:
        mask &= (p - j) < window
    return mask


def sliding_window_row_mask(
    positions: jax.Array, kv_length: int, windows: jax.Array
) -> jax.Array:
    """Per-layer-window variant used inside the layer scan: ``windows`` is a
    scalar (traced per scan step). 0 disables the window."""
    j = jnp.arange(kv_length)[None, None, :]
    p = positions[:, :, None]
    base = j <= p
    win = (p - j) < jnp.maximum(windows, 1)
    return jnp.where(windows > 0, base & win, base)
