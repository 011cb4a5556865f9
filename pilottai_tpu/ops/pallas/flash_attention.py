"""Flash attention (online softmax) as Pallas TPU kernels — fwd AND bwd.

Replaces the O(T·S)-memory XLA attention (``ops/attention.py``) for large
prefills: logits are never materialized; each (batch, head, q-block) grid
cell streams KV blocks through VMEM keeping running max/sum statistics in
fp32. Matmuls hit the MXU in bf16; masking (causal from absolute
positions, per-layer sliding window, valid-length) is computed in-kernel
so no [B, T, S] mask array ever exists in HBM.

The op carries a ``jax.custom_vjp``: the forward kernel also emits the
log-sum-exp rows, and two backward kernels recompute probabilities
blockwise (the standard flash backward) —

* ``dq``: grid (B, N, T/bq), K/V resident, accumulate dq per q-block;
* ``dk/dv``: grid (B, K, S/bk, T/bq) with the q-block dim innermost, so
  the kv-block outputs stay resident across q steps and accumulate
  in-place (Mosaic's revisited-output reduction pattern); the G query
  heads of each kv head are processed in-cell, so dk/dv come out already
  group-summed.

so training runs through the kernel instead of silently falling back to
XLA attention.

Fully-masked KV blocks (beyond the causal horizon or the valid length)
are skipped with ``lax.cond`` — for causal prefill that halves the work.

Multi-chip: ``flash_attention_sharded`` wraps the kernel in ``shard_map``
(batch over data/fsdp, heads over model — attention is embarrassingly
parallel across both), so TP meshes keep the fast path instead of
dropping to XLA dense.

No reference counterpart: the reference computes no attention at all
(SURVEY.md §2.13); this is the serving engine's hot op.
"""

from __future__ import annotations

import functools
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import Mesh, PartitionSpec as P

NEG_INF = -2.0**30


# --------------------------------------------------------------------- #
# Forward kernel
# --------------------------------------------------------------------- #

def _flash_kernel(
    window_ref,   # SMEM (1,) int32 (scalar prefetch) — sliding window; 0 = global
    valid_ref,    # SMEM (B,) int32 (scalar prefetch) — valid kv length per batch row
    qpos_ref,     # VMEM (1, 1, bq)     — absolute positions of the q block
    kpos_ref,     # VMEM (1, 1, S)      — absolute positions of all keys
    q_ref,        # VMEM (1, 1, bq, H)  — head-major layout
    k_ref,        # VMEM (1, 1, S, H)
    v_ref,        # VMEM (1, 1, S, H)
    o_ref,        # VMEM (1, 1, bq, H)
    lse_ref,      # VMEM (1, 1, bq, 1) fp32 — log-sum-exp rows (for the VJP;
                  # trailing singleton keeps the last two block dims
                  # Mosaic-tileable: (bq, 1) vs array dims (T, 1))
    *,
    scale: float,
    softcap: float,
    block_k: int,
):
    bq = q_ref.shape[2]
    H = q_ref.shape[3]
    S = k_ref.shape[2]
    n_kb = S // block_k

    q = q_ref[0, 0, :, :]                                    # [bq, H] bf16

    qpos = qpos_ref[0, 0, :].reshape(bq, 1)                  # [bq, 1]
    window = window_ref[0]
    valid = valid_ref[pl.program_id(0)]
    qpos_max = jnp.max(qpos)

    def body(kb, carry):
        m, l, acc = carry
        j0 = kb * block_k
        kpos = kpos_ref[0, 0, pl.ds(j0, block_k)].reshape(1, block_k)
        jidx = j0 + jax.lax.broadcasted_iota(jnp.int32, (1, block_k), 1)

        # Block-level skip: every key in this block is after every query
        # (causal), past the valid length, or older than the sliding
        # window for every query -> contributes nothing.
        block_live = (jnp.min(kpos) <= qpos_max) & (j0 < valid)
        block_live &= (window <= 0) | ((jnp.min(qpos) - jnp.max(kpos)) < window)

        def attend(carry):
            m, l, acc = carry
            k = k_ref[0, 0, pl.ds(j0, block_k), :]           # [bk, H]
            v = v_ref[0, 0, pl.ds(j0, block_k), :]           # [bk, H]
            # bf16 × bf16 on the MXU, fp32 accumulate; scale folded in
            # afterwards so the matmul itself stays at full MXU rate.
            s = jax.lax.dot_general(
                q, k,
                dimension_numbers=(((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            ) * scale                                         # [bq, bk]
            if softcap > 0.0:
                s = jnp.tanh(s / softcap) * softcap
            mask = (kpos <= qpos) & (jidx < valid)
            # (window <= 0) | in_window, as pure boolean algebra — Mosaic
            # cannot legalize select over i1 vectors.
            mask &= (window <= 0) | ((qpos - kpos) < window)
            s = jnp.where(mask, s, NEG_INF)

            m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
            p = jnp.exp(s - m_new)                            # [bq, bk]
            corr = jnp.exp(m - m_new)                         # [bq, 1]
            l_new = l * corr + jnp.sum(p, axis=-1, keepdims=True)
            pv = jax.lax.dot_general(
                p.astype(v.dtype), v,
                dimension_numbers=(((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )                                                 # [bq, H]
            acc_new = acc * corr + pv
            return m_new, l_new, acc_new

        return jax.lax.cond(block_live, attend, lambda c: c, (m, l, acc))

    m0 = jnp.full((bq, 1), NEG_INF, jnp.float32)
    l0 = jnp.zeros((bq, 1), jnp.float32)
    acc0 = jnp.zeros((bq, H), jnp.float32)
    m, l, acc = jax.lax.fori_loop(0, n_kb, body, (m0, l0, acc0))

    out = acc / jnp.maximum(l, 1e-30)
    out = jnp.where(l > 0.0, out, 0.0)                        # fully-masked rows
    o_ref[0, 0, :, :] = out.astype(o_ref.dtype)
    lse = jnp.where(
        l > 0.0, m + jnp.log(jnp.maximum(l, 1e-30)), NEG_INF
    )                                                         # [bq, 1]
    lse_ref[0, 0, :, :] = lse


def _fwd_impl(
    q, k, v, q_positions, kv_positions, valid, window,
    scale, softcap, block_q, block_k, interpret,
) -> Tuple[jax.Array, jax.Array]:
    """Runs the forward kernel. Returns (o [B,T,N,H], lse [B,N,T] fp32)."""
    B, T, N, H = q.shape
    _, S, K, _ = k.shape
    assert N % K == 0
    G = N // K
    assert T % block_q == 0, f"T={T} not divisible by block_q={block_q}"
    assert S % block_k == 0, f"S={S} not divisible by block_k={block_k}"

    window = jnp.asarray(window, jnp.int32).reshape(1)
    valid = jnp.asarray(valid, jnp.int32).reshape(B)
    qpos = jnp.asarray(q_positions, jnp.int32)[:, None, :]   # [B, 1, T]
    kpos = jnp.asarray(kv_positions, jnp.int32)[:, None, :]  # [B, 1, S]

    # Head-major layout so blocks tile as (bq, H)/(S, H) — the TPU lowering
    # requires the last two block dims be tile-aligned or full.
    q_t = q.transpose(0, 2, 1, 3)                            # [B, N, T, H]
    k_t = k.transpose(0, 2, 1, 3)                            # [B, K, S, H]
    v_t = v.transpose(0, 2, 1, 3)

    kernel = functools.partial(
        _flash_kernel, scale=scale, softcap=softcap, block_k=block_k
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,  # window, valid land in SMEM pre-kernel
        grid=(B, N, T // block_q),
        in_specs=[
            pl.BlockSpec((1, 1, block_q), lambda b, n, i, *_: (b, 0, i)),
            pl.BlockSpec((1, 1, S), lambda b, n, i, *_: (b, 0, 0)),
            pl.BlockSpec((1, 1, block_q, H), lambda b, n, i, *_: (b, n, i, 0)),
            pl.BlockSpec((1, 1, S, H), lambda b, n, i, *_: (b, n // G, 0, 0)),
            pl.BlockSpec((1, 1, S, H), lambda b, n, i, *_: (b, n // G, 0, 0)),
        ],
        out_specs=(
            pl.BlockSpec((1, 1, block_q, H), lambda b, n, i, *_: (b, n, i, 0)),
            pl.BlockSpec((1, 1, block_q, 1), lambda b, n, i, *_: (b, n, i, 0)),
        ),
    )
    o_t, lse = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=(
            jax.ShapeDtypeStruct(q_t.shape, q.dtype),
            jax.ShapeDtypeStruct((B, N, T, 1), jnp.float32),
        ),
        interpret=interpret,
        name="flash_attention",
    )(window, valid, qpos, kpos, q_t, k_t, v_t)
    return o_t.transpose(0, 2, 1, 3), lse                    # o [B,T,N,H]; lse [B,N,T,1]


# --------------------------------------------------------------------- #
# Backward kernels
# --------------------------------------------------------------------- #

def _bwd_dq_kernel(
    window_ref,   # SMEM (1,)
    valid_ref,    # SMEM (B,)
    qpos_ref,     # VMEM (1, 1, bq)
    kpos_ref,     # VMEM (1, 1, S)
    q_ref,        # VMEM (1, 1, bq, H)
    k_ref,        # VMEM (1, 1, S, H)
    v_ref,        # VMEM (1, 1, S, H)
    do_ref,       # VMEM (1, 1, bq, H)
    lse_ref,      # VMEM (1, 1, bq, 1) fp32
    delta_ref,    # VMEM (1, 1, bq, 1) fp32 — rowsum(dO * O)
    dq_ref,       # VMEM (1, 1, bq, H)
    *,
    scale: float,
    softcap: float,
    block_k: int,
):
    bq, H = q_ref.shape[2], q_ref.shape[3]
    S = k_ref.shape[2]
    n_kb = S // block_k

    q = q_ref[0, 0, :, :]
    do = do_ref[0, 0, :, :].astype(jnp.float32)
    lse = lse_ref[0, 0, :, :]                                 # [bq, 1]
    delta = delta_ref[0, 0, :, :]                             # [bq, 1]
    qpos = qpos_ref[0, 0, :].reshape(bq, 1)
    window = window_ref[0]
    valid = valid_ref[pl.program_id(0)]
    qpos_max = jnp.max(qpos)

    def body(kb, dq_acc):
        j0 = kb * block_k
        kpos = kpos_ref[0, 0, pl.ds(j0, block_k)].reshape(1, block_k)
        jidx = j0 + jax.lax.broadcasted_iota(jnp.int32, (1, block_k), 1)
        block_live = (jnp.min(kpos) <= qpos_max) & (j0 < valid)
        block_live &= (window <= 0) | ((jnp.min(qpos) - jnp.max(kpos)) < window)

        def attend(dq_acc):
            k = k_ref[0, 0, pl.ds(j0, block_k), :]
            v = v_ref[0, 0, pl.ds(j0, block_k), :]
            s = jax.lax.dot_general(
                q, k, dimension_numbers=(((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            ) * scale                                         # [bq, bk]
            if softcap > 0.0:
                t = jnp.tanh(s / softcap)
                s_c = t * softcap
            else:
                s_c = s
            mask = (kpos <= qpos) & (jidx < valid)
            mask &= (window <= 0) | ((qpos - kpos) < window)
            p = jnp.where(mask, jnp.exp(s_c - lse), 0.0)      # true softmax rows
            dp = jax.lax.dot_general(
                do, v.astype(jnp.float32),
                dimension_numbers=(((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            )                                                 # [bq, bk]
            ds = p * (dp - delta)
            if softcap > 0.0:
                ds = ds * (1.0 - t * t)
            return dq_acc + jax.lax.dot_general(
                ds.astype(k.dtype), k,
                dimension_numbers=(((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            ) * scale

        return jax.lax.cond(block_live, attend, lambda a: a, dq_acc)

    dq = jax.lax.fori_loop(0, n_kb, body, jnp.zeros((bq, H), jnp.float32))
    dq_ref[0, 0, :, :] = dq.astype(dq_ref.dtype)


def _bwd_dkv_kernel(
    window_ref,   # SMEM (1,)
    valid_ref,    # SMEM (B,)
    qpos_ref,     # VMEM (1, 1, bq)
    kpos_ref,     # VMEM (1, 1, bk)
    q_ref,        # VMEM (1, G, bq, H) — all G query heads of this kv head
    k_ref,        # VMEM (1, 1, bk, H)
    v_ref,        # VMEM (1, 1, bk, H)
    do_ref,       # VMEM (1, G, bq, H)
    lse_ref,      # VMEM (1, G, bq, 1) fp32
    delta_ref,    # VMEM (1, G, bq, 1) fp32
    dk_ref,       # VMEM (1, 1, bk, H) fp32 — accumulated across q blocks
    dv_ref,       # VMEM (1, 1, bk, H) fp32
    *,
    scale: float,
    softcap: float,
):
    G = q_ref.shape[1]
    bq = q_ref.shape[2]
    bk = k_ref.shape[2]
    i = pl.program_id(3)  # q-block index — innermost, outputs revisited

    @pl.when(i == 0)
    def _init():
        dk_ref[...] = jnp.zeros_like(dk_ref)
        dv_ref[...] = jnp.zeros_like(dv_ref)

    qpos = qpos_ref[0, 0, :].reshape(bq, 1)
    kpos = kpos_ref[0, 0, :].reshape(1, bk)
    window = window_ref[0]
    valid = valid_ref[pl.program_id(0)]
    j0 = pl.program_id(2) * bk
    jidx = j0 + jax.lax.broadcasted_iota(jnp.int32, (1, bk), 1)

    block_live = (jnp.min(kpos) <= jnp.max(qpos)) & (j0 < valid)
    block_live &= (window <= 0) | ((jnp.min(qpos) - jnp.max(kpos)) < window)

    @pl.when(block_live)
    def _body():
        kk = k_ref[0, 0, :, :]                                # [bk, H]
        vv = v_ref[0, 0, :, :]
        mask = (kpos <= qpos) & (jidx < valid)
        mask &= (window <= 0) | ((qpos - kpos) < window)
        dk_acc = jnp.zeros((bk, kk.shape[1]), jnp.float32)
        dv_acc = jnp.zeros_like(dk_acc)
        for g in range(G):                                    # static unroll
            qg = q_ref[0, g, :, :]                            # [bq, H]
            dog = do_ref[0, g, :, :].astype(jnp.float32)
            lse = lse_ref[0, g, :, :]                         # [bq, 1]
            delta = delta_ref[0, g, :, :]                     # [bq, 1]
            s = jax.lax.dot_general(
                qg, kk, dimension_numbers=(((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            ) * scale                                         # [bq, bk]
            if softcap > 0.0:
                t = jnp.tanh(s / softcap)
                s_c = t * softcap
            else:
                s_c = s
            p = jnp.where(mask, jnp.exp(s_c - lse), 0.0)
            # dv += p^T @ dO
            dv_acc += jax.lax.dot_general(
                p.astype(vv.dtype), dog.astype(vv.dtype),
                dimension_numbers=(((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            dp = jax.lax.dot_general(
                dog, vv.astype(jnp.float32),
                dimension_numbers=(((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            ds = p * (dp - delta)
            if softcap > 0.0:
                ds = ds * (1.0 - t * t)
            # dk += ds^T @ q * scale
            dk_acc += jax.lax.dot_general(
                ds.astype(qg.dtype), qg,
                dimension_numbers=(((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            ) * scale
        dk_ref[0, 0, :, :] += dk_acc
        dv_ref[0, 0, :, :] += dv_acc


def _bwd_impl(
    q, k, v, q_positions, kv_positions, valid, window, o, lse, do,
    scale, softcap, block_q, block_k, interpret, dlse=None,
):
    B, T, N, H = q.shape
    _, S, K, _ = k.shape
    G = N // K

    window = jnp.asarray(window, jnp.int32).reshape(1)
    valid = jnp.asarray(valid, jnp.int32).reshape(B)
    qpos = jnp.asarray(q_positions, jnp.int32)[:, None, :]
    kpos = jnp.asarray(kv_positions, jnp.int32)[:, None, :]

    q_t = q.transpose(0, 2, 1, 3)                            # [B, N, T, H]
    k_t = k.transpose(0, 2, 1, 3)                            # [B, K, S, H]
    v_t = v.transpose(0, 2, 1, 3)
    do_t = do.transpose(0, 2, 1, 3)
    # delta = rowsum(dO * O), fp32 — [B, N, T, 1]
    delta = jnp.sum(
        do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1
    ).transpose(0, 2, 1)[..., None]
    if dlse is not None:
        # lse cotangent (flash_attention_with_lse): d lse_i / d s_ij = p_ij,
        # so ds_ij = p_ij (dp_ij - delta_i + dlse_i) — exactly the delta
        # operand shifted. No kernel change needed.
        delta = delta - dlse.astype(jnp.float32)

    dq_kernel = functools.partial(
        _bwd_dq_kernel, scale=scale, softcap=softcap, block_k=block_k
    )
    dq_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, N, T // block_q),
        in_specs=[
            pl.BlockSpec((1, 1, block_q), lambda b, n, i, *_: (b, 0, i)),
            pl.BlockSpec((1, 1, S), lambda b, n, i, *_: (b, 0, 0)),
            pl.BlockSpec((1, 1, block_q, H), lambda b, n, i, *_: (b, n, i, 0)),
            pl.BlockSpec((1, 1, S, H), lambda b, n, i, *_: (b, n // G, 0, 0)),
            pl.BlockSpec((1, 1, S, H), lambda b, n, i, *_: (b, n // G, 0, 0)),
            pl.BlockSpec((1, 1, block_q, H), lambda b, n, i, *_: (b, n, i, 0)),
            pl.BlockSpec((1, 1, block_q, 1), lambda b, n, i, *_: (b, n, i, 0)),
            pl.BlockSpec((1, 1, block_q, 1), lambda b, n, i, *_: (b, n, i, 0)),
        ],
        out_specs=pl.BlockSpec(
            (1, 1, block_q, H), lambda b, n, i, *_: (b, n, i, 0)
        ),
    )
    dq_t = pl.pallas_call(
        dq_kernel,
        grid_spec=dq_spec,
        out_shape=jax.ShapeDtypeStruct(q_t.shape, q.dtype),
        interpret=interpret,
        name="flash_attention_bwd_dq",
    )(window, valid, qpos, kpos, q_t, k_t, v_t, do_t, lse, delta)

    dkv_kernel = functools.partial(
        _bwd_dkv_kernel, scale=scale, softcap=softcap
    )
    # q-block dim innermost: dk/dv blocks are revisited and accumulate.
    dkv_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, K, S // block_k, T // block_q),
        in_specs=[
            pl.BlockSpec((1, 1, block_q), lambda b, h, j, i, *_: (b, 0, i)),
            pl.BlockSpec((1, 1, block_k), lambda b, h, j, i, *_: (b, 0, j)),
            pl.BlockSpec(
                (1, G, block_q, H), lambda b, h, j, i, *_: (b, h, i, 0)
            ),
            pl.BlockSpec((1, 1, block_k, H), lambda b, h, j, i, *_: (b, h, j, 0)),
            pl.BlockSpec((1, 1, block_k, H), lambda b, h, j, i, *_: (b, h, j, 0)),
            pl.BlockSpec(
                (1, G, block_q, H), lambda b, h, j, i, *_: (b, h, i, 0)
            ),
            pl.BlockSpec(
                (1, G, block_q, 1), lambda b, h, j, i, *_: (b, h, i, 0)
            ),
            pl.BlockSpec(
                (1, G, block_q, 1), lambda b, h, j, i, *_: (b, h, i, 0)
            ),
        ],
        out_specs=(
            pl.BlockSpec((1, 1, block_k, H), lambda b, h, j, i, *_: (b, h, j, 0)),
            pl.BlockSpec((1, 1, block_k, H), lambda b, h, j, i, *_: (b, h, j, 0)),
        ),
    )
    dk_t, dv_t = pl.pallas_call(
        dkv_kernel,
        grid_spec=dkv_spec,
        out_shape=(
            jax.ShapeDtypeStruct(k_t.shape, jnp.float32),
            jax.ShapeDtypeStruct(v_t.shape, jnp.float32),
        ),
        interpret=interpret,
        name="flash_attention_bwd_dkv",
    )(window, valid, qpos, kpos, q_t, k_t, v_t, do_t, lse, delta)

    dq = dq_t.transpose(0, 2, 1, 3)
    dk = dk_t.transpose(0, 2, 1, 3).astype(k.dtype)
    dv = dv_t.transpose(0, 2, 1, 3).astype(v.dtype)
    return dq, dk, dv


# --------------------------------------------------------------------- #
# custom_vjp wiring
# --------------------------------------------------------------------- #

@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2, 3, 4))
def _flash_lse(scale, softcap, block_q, block_k, interpret,
               q, k, v, q_positions, kv_positions, valid, window):
    """THE vjp-carrying op: forward returns (o, lse). Plain
    ``flash_attention`` discards lse (its zero cotangent makes
    ``delta - dlse`` collapse to the standard flash backward), so one
    set of vjp rules serves both entry points."""
    return _fwd_impl(
        q, k, v, q_positions, kv_positions, valid, window,
        scale, softcap, block_q, block_k, interpret,
    )


def _flash_lse_fwd_rule(scale, softcap, block_q, block_k, interpret,
                        q, k, v, q_positions, kv_positions, valid, window):
    o, lse = _fwd_impl(
        q, k, v, q_positions, kv_positions, valid, window,
        scale, softcap, block_q, block_k, interpret,
    )
    return (o, lse), (q, k, v, q_positions, kv_positions, valid, window, o, lse)


def _flash_lse_bwd_rule(scale, softcap, block_q, block_k, interpret, res, ct):
    q, k, v, q_positions, kv_positions, valid, window, o, lse = res
    do, dlse = ct
    dq, dk, dv = _bwd_impl(
        q, k, v, q_positions, kv_positions, valid, window, o, lse, do,
        scale, softcap, block_q, block_k, interpret, dlse=dlse,
    )

    def f0(x):
        return np.zeros(jnp.shape(x), dtype=jax.dtypes.float0)

    return (dq, dk, dv, f0(q_positions), f0(kv_positions), f0(valid), f0(window))


_flash_lse.defvjp(_flash_lse_fwd_rule, _flash_lse_bwd_rule)


def _pad_to_blocks(q, k, v, q_positions, kv_positions, block_q, block_k):
    """Pad T to a block_q multiple and S to a block_k multiple so ragged
    training shapes stay on the Pallas path (VERDICT r2 next-step 8).
    Positions edge-replicate (keeps the causal horizon and block-skip
    bounds sane); K/V pad with zeros and are masked by the kernel's
    ``jidx < valid`` check; padded QUERY rows produce garbage the caller
    slices off — and since the pad/slice pair differentiates cleanly,
    their gradient contribution is exactly zero."""
    T, S = q.shape[1], k.shape[1]
    Tp = -(-T // block_q) * block_q
    Sp = -(-S // block_k) * block_k
    if Tp != T:
        q = jnp.pad(q, ((0, 0), (0, Tp - T), (0, 0), (0, 0)))
        q_positions = jnp.pad(
            q_positions, ((0, 0), (0, Tp - T)), mode="edge"
        )
    if Sp != S:
        k = jnp.pad(k, ((0, 0), (0, Sp - S), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, Sp - S), (0, 0), (0, 0)))
        kv_positions = jnp.pad(
            kv_positions, ((0, 0), (0, Sp - S)), mode="edge"
        )
    return q, k, v, q_positions, kv_positions, T


@functools.partial(
    jax.jit,
    static_argnames=("scale", "softcap", "block_q", "block_k", "interpret"),
)
def flash_attention_with_lse(
    q: jax.Array,             # [B, T, N, H]
    k: jax.Array,             # [B, S, K, H]
    v: jax.Array,             # [B, S, K, H]
    q_positions: jax.Array,   # [B, T]
    kv_positions: jax.Array,  # [B, S]
    valid: jax.Array,         # [B] valid kv length (kv INDEX bound)
    window: jax.Array,
    scale: Optional[float] = None,
    softcap: float = 0.0,
    block_q: int = 128,
    block_k: int = 128,
    interpret: bool = False,
) -> Tuple[jax.Array, jax.Array]:
    """Like ``flash_attention`` but also returns the log-sum-exp rows
    ``[B, T, N, 1]`` (NEG_INF where the row saw no keys) so disjoint
    KV chunks can be merged exactly — ring attention's per-step form.
    Differentiable in (q, k, v) INCLUDING through lse. Ragged T/S pad
    to block multiples internally."""
    H = q.shape[-1]
    scale = scale if scale is not None else H ** -0.5
    q, k, v, q_positions, kv_positions, T = _pad_to_blocks(
        q, k, v, q_positions, kv_positions, block_q, block_k
    )
    o, lse = _flash_lse(
        scale, softcap, block_q, block_k, interpret,
        q, k, v, q_positions, kv_positions, valid, window,
    )
    return o[:, :T], lse.transpose(0, 2, 1, 3)[:, :T]  # lse -> [B, T, N, 1]


@functools.partial(
    jax.jit,
    static_argnames=("scale", "softcap", "block_q", "block_k", "interpret"),
)
def flash_attention(
    q: jax.Array,          # [B, T, N, H]
    k: jax.Array,          # [B, S, K, H]
    v: jax.Array,          # [B, S, K, H]
    q_positions: jax.Array,   # [B, T] absolute positions
    kv_positions: jax.Array,  # [B, S] absolute positions
    valid: jax.Array,         # [B] valid kv length (sequence index bound)
    window: jax.Array,        # scalar int32; 0 = global attention
    scale: Optional[float] = None,
    softcap: float = 0.0,
    block_q: int = 128,
    block_k: int = 128,
    interpret: bool = False,
) -> jax.Array:
    """Causal GQA flash attention, differentiable in (q, k, v). Mask
    semantics match ``models/transformer.py`` prefill: attend iff
    kv_pos <= q_pos, kv index < valid, and (window == 0 or
    q_pos - kv_pos < window). Ragged T/S pad to block multiples
    internally (the pad/slice pair contributes zero gradient)."""
    H = q.shape[-1]
    scale = scale if scale is not None else H ** -0.5
    q, k, v, q_positions, kv_positions, T = _pad_to_blocks(
        q, k, v, q_positions, kv_positions, block_q, block_k
    )
    out, _ = _flash_lse(
        scale, softcap, block_q, block_k, interpret,
        q, k, v, q_positions, kv_positions, valid, window,
    )
    return out[:, :T]


# --------------------------------------------------------------------- #
# Multi-chip dispatch (shard_map)
# --------------------------------------------------------------------- #

def flash_sharding_ok(
    mesh: Mesh,
    B: int,
    n_heads: int,
    n_kv_heads: int,
    batch_axes: Sequence[str] = ("data", "fsdp"),
    head_axis: str = "model",
    seq_axis: str = "seq",
) -> bool:
    """True when the kernel can run per-shard with no cross-device work:
    batch divides the data axes, both head counts divide the TP axis, and
    the sequence axis is unsharded (sequence parallelism goes through
    ``parallel/ring_attention.py`` instead)."""
    shape = dict(mesh.shape)
    db = 1
    for a in batch_axes:
        db *= shape.get(a, 1)
    tp = shape.get(head_axis, 1)
    if shape.get(seq_axis, 1) != 1:
        return False
    return B % db == 0 and n_heads % tp == 0 and n_kv_heads % tp == 0


def flash_attention_sharded(
    mesh: Mesh,
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    q_positions: jax.Array,
    kv_positions: jax.Array,
    valid: jax.Array,
    window: jax.Array,
    scale: Optional[float] = None,
    softcap: float = 0.0,
    block_q: int = 128,
    block_k: int = 128,
    interpret: bool = False,
    batch_axes: Sequence[str] = ("data", "fsdp"),
    head_axis: str = "model",
) -> jax.Array:
    """The flash kernel under ``shard_map``: batch shards over the data
    axes, heads over the TP axis. Attention is independent across both, so
    there are no collectives — each chip runs the single-chip kernel on
    its shard and TP meshes keep the fast path.
    Differentiable: shard_map transposes through the kernel's custom VJP.
    """
    H = q.shape[-1]
    scale = scale if scale is not None else H ** -0.5
    present = [a for a in batch_axes if a in mesh.axis_names]
    bspec = tuple(present) if present else None
    fn = functools.partial(
        flash_attention,
        scale=scale, softcap=softcap,
        block_q=block_q, block_k=block_k, interpret=interpret,
    )
    head = head_axis if head_axis in mesh.axis_names else None
    return jax.shard_map(
        fn,
        mesh=mesh,
        in_specs=(
            P(bspec, None, head, None),   # q
            P(bspec, None, head, None),   # k
            P(bspec, None, head, None),   # v
            P(bspec, None),               # q_positions
            P(bspec, None),               # kv_positions
            P(bspec),                     # valid
            P(),                          # window (replicated scalar)
        ),
        out_specs=P(bspec, None, head, None),
        check_vma=False,
    )(q, k, v, q_positions, kv_positions, valid,
      jnp.asarray(window, jnp.int32))
