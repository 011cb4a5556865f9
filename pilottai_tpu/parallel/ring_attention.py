"""Ring attention: exact attention over a sequence-sharded context.

Long-context plan (SURVEY.md §5.7): activations are sharded over the
``seq`` mesh axis; instead of all-gathering K/V (XLA's default when it
meets a sequence-sharded attention), each device keeps running online-
softmax statistics for its local queries while K/V chunks rotate around
the ring via ``ppermute`` — every step overlaps the neighbor transfer
(ICI) with the local block's matmuls, and no device ever holds more than
one K/V chunk beyond its own.

Built on ``shard_map`` so it composes with the 4-axis mesh: batch stays
sharded over data/fsdp, heads over model, sequence over seq. The whole
thing is differentiable (ppermute transposes to the reverse rotation),
so the training path can use it directly.

No reference counterpart (SURVEY.md §2.13 — the reference has no model
execution at all).
"""

from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from pilottai_tpu.ops.attention import NEG_INF, flash_enabled, flash_shapes_ok
from pilottai_tpu.parallel.sharding import _current_mesh

# Logical shardings of the operands (mesh axes, not logical names, because
# shard_map wants PartitionSpecs over the mesh directly).
_Q_SPEC = P(("data", "fsdp"), "seq", "model", None)
_KV_SPEC = P(("data", "fsdp"), "seq", "model", None)
_POS_SPEC = P(("data", "fsdp"), "seq")
_VALID_SPEC = P(("data", "fsdp"))


def _block_attend(q, k, v, s_mask, scale, softcap, m, l, acc):
    """One online-softmax accumulation step. q [T,N?,H]-free layout:
    operands are [B, Tq, K, G, H] x [B, Tk, K, H]."""
    s = jnp.einsum("btkgh,bskh->bkgts", q, k).astype(jnp.float32) * scale
    if softcap > 0.0:
        s = jnp.tanh(s / softcap) * softcap
    s = jnp.where(s_mask, s, NEG_INF)                     # [B, K, G, Tq, Tk]
    m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)
    corr = jnp.exp(m - m_new)
    l_new = l * corr + jnp.sum(p, axis=-1, keepdims=True)
    pv = jnp.einsum("bkgts,bskh->bkgth", p.astype(v.dtype), v).astype(jnp.float32)
    acc_new = acc * corr[..., 0][..., None] + pv
    return m_new, l_new, acc_new


def ring_attention(
    q: jax.Array,             # [B, T, N, H] — T sharded over `axis`
    k: jax.Array,             # [B, T, K, H]
    v: jax.Array,             # [B, T, K, H]
    q_positions: jax.Array,   # [B, T] absolute positions
    valid: jax.Array,         # [B] valid length (global sequence index bound)
    window: jax.Array,        # scalar int32; 0 = global
    scale: Optional[float] = None,
    softcap: float = 0.0,
    axis: str = "seq",
    mesh: Optional[Mesh] = None,
    use_flash: Optional[bool] = None,
    interpret: bool = False,
) -> jax.Array:
    """Causal GQA attention with K/V rotating around the ``axis`` ring.

    Mask semantics match ``models/transformer.py`` prefill: attend iff
    kv_pos <= q_pos, kv sequence index < valid, and (window == 0 or
    q_pos - kv_pos < window).

    Each ring step's local block runs through the Pallas flash kernel on
    TPU (``flash_attention_with_lse``; VERDICT r2 next-step 8 — the ring
    used to pay dense O(Tl·Tl) XLA math per step). Steps merge by their
    log-sum-exp rows, which is exact; the lse cotangent flows through the
    kernel's custom VJP, so training uses the same path. ``use_flash``
    overrides the TPU autodetect (tests force it with ``interpret``).
    """
    mesh = mesh if mesh is not None else _current_mesh()
    if mesh is None:
        raise ValueError("ring_attention needs a mesh (or jax.set_mesh context)")
    B, T, N, H = q.shape
    K = k.shape[2]
    G = N // K
    scale = scale if scale is not None else H ** -0.5
    P_ring = mesh.shape[axis]
    window = jnp.asarray(window, jnp.int32)
    Tl = T // P_ring
    if use_flash is None:
        use_flash = flash_enabled() and flash_shapes_ok(
            Tl, Tl, head_dim=H, itemsize=q.dtype.itemsize
        )

    def per_device_flash(q, k, v, qpos, valid, window):
        from pilottai_tpu.ops.pallas.flash_attention import (
            flash_attention_with_lse,
        )

        Bl, Tl = q.shape[0], q.shape[1]
        my = jax.lax.axis_index(axis)
        kpos = qpos                                   # kv chunk starts local
        start = jnp.full((1,), my * Tl, jnp.int32)    # chunk's global offset

        M = jnp.full((Bl, Tl, q.shape[2], 1), NEG_INF, jnp.float32)
        num = jnp.zeros((Bl, Tl, q.shape[2], H), jnp.float32)
        den = jnp.zeros_like(M)

        perm = [(j, (j + 1) % P_ring) for j in range(P_ring)]
        for step in range(P_ring):
            # The kernel's valid is a LOCAL kv-index bound; translate the
            # global valid length by this chunk's offset in the sequence.
            valid_eff = jnp.clip(valid - start[0], 0, Tl)
            o_i, lse_i = flash_attention_with_lse(
                q, k, v, qpos, kpos, valid_eff, window,
                scale=scale, softcap=softcap, interpret=interpret,
            )                                         # o [B,Tl,N,H]; lse [B,Tl,N,1]
            M_new = jnp.maximum(M, lse_i)
            w = jnp.where(lse_i > NEG_INF / 2, jnp.exp(lse_i - M_new), 0.0)
            corr = jnp.where(M > NEG_INF / 2, jnp.exp(M - M_new), 0.0)
            num = num * corr + o_i.astype(jnp.float32) * w
            den = den * corr + w
            M = M_new
            if step + 1 < P_ring:
                k = jax.lax.ppermute(k, axis, perm)
                v = jax.lax.ppermute(v, axis, perm)
                kpos = jax.lax.ppermute(kpos, axis, perm)
                start = jax.lax.ppermute(start, axis, perm)

        out = num / jnp.maximum(den, 1e-30)
        out = jnp.where(den > 0.0, out, 0.0)
        return out.astype(v.dtype)

    def per_device(q, k, v, qpos, valid, window):
        # Local shapes: q [Bl, Tl, Nl, H], k/v [Bl, Tl, Kl, H], qpos [Bl, Tl].
        Bl, Tl = q.shape[0], q.shape[1]
        Kl = k.shape[2]
        my = jax.lax.axis_index(axis)
        q = q.reshape(Bl, Tl, Kl, G, H)

        kpos = qpos                                   # kv chunk starts local
        jidx = my * Tl + jax.lax.broadcasted_iota(jnp.int32, (1, Tl), 1)

        m = jnp.full((Bl, Kl, G, Tl, 1), NEG_INF, jnp.float32)
        l = jnp.zeros_like(m)
        acc = jnp.zeros((Bl, Kl, G, Tl, H), jnp.float32)

        perm = [(j, (j + 1) % P_ring) for j in range(P_ring)]
        for step in range(P_ring):
            ip = qpos[:, None, :, None]               # [B, 1, Tq, 1]
            jp = kpos[:, None, None, :]               # [B, 1, 1, Tk]
            mask = (jp <= ip) & (jidx[:, None, None, :] < valid[:, None, None, None])
            mask &= (window <= 0) | ((ip - jp) < window)
            mask = mask[:, :, None, :, :]             # [B, 1, 1, Tq, Tk]
            m, l, acc = _block_attend(q, k, v, mask, scale, softcap, m, l, acc)
            if step + 1 < P_ring:
                k = jax.lax.ppermute(k, axis, perm)
                v = jax.lax.ppermute(v, axis, perm)
                kpos = jax.lax.ppermute(kpos, axis, perm)
                jidx = jax.lax.ppermute(jidx, axis, perm)

        out = acc / jnp.maximum(l, 1e-30)
        out = jnp.where(l > 0.0, out, 0.0)
        return (
            out.transpose(0, 3, 1, 2, 4)
            .reshape(Bl, Tl, Kl * G, H)
            .astype(v.dtype)
        )

    return jax.shard_map(
        per_device_flash if use_flash else per_device,
        mesh=mesh,
        in_specs=(_Q_SPEC, _KV_SPEC, _KV_SPEC, _POS_SPEC, _VALID_SPEC, P()),
        out_specs=_Q_SPEC,
        check_vma=False,
    )(q, k, v, q_positions, valid, window)
