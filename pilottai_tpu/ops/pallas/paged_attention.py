"""Paged decode attention as a Pallas TPU kernel.

The paged cache (``ops/paged.py``) stores K/V in a shared page pool with
block-table indirection; this kernel reads ONLY the pages a slot
actually occupies. The trick is scalar-prefetched index maps: the block
table lands in SMEM before the grid runs, and each grid cell's
BlockSpecs *compute their pool coordinates from the table* — pages
stream HBM→VMEM directly by id, no dense [B, S, H] gather ever exists.

Grid is (B, page-strip-count) with the strip dim innermost. Each cell
processes a **strip of ``n_strip`` pages** (round-5 profiling: one page
per cell left the 8K section grid-cell-latency bound — page A/B
64→268, 128→243, 256→309 device ms/step showed a per-cell launch/index
floor, not a bandwidth floor). The strip rides as ``n_strip`` replicated
BlockSpecs over the same pool, each with its own scalar-prefetched index
map, so one cell's prefetch wave covers N pages and the launch/index
overhead amortizes N-fold. The (acc, m, l) online-softmax outputs map to
the same block for every strip step, so they stay VMEM-resident and
accumulate across the whole strip sequence (the same revisited-output
reduction the flash backward uses). Pages that are unallocated, fully
past the valid length, or padding past ``n_blocks`` clamp their DMA to
the scratch page and skip compute with ``pl.when`` — page-for-page the
math is identical to the single-page kernel, so strip results are
bit-identical (pinned by tests/test_paged_strip.py).

Optionally the **in-chunk ring attention fuses into the same
invocation** (``ring_k``/``ring_v``/``ring_step``): the final grid cell
runs the ring block and merges it with the page stats exactly like
``engine/decode.py:_merge_stats``, eliminating the separate per-layer
ring dispatch + combine the plain decode chunk used to pay per step.
The speculative chunk keeps its separate passes (its block attention
carries intra-block causal masking this kernel does not model — the
stats contract does not allow the fusion there).

Returns unnormalized (acc, m, l) stats — with the ring fused the caller
only normalizes; without it the fused decode chunk combines them with
the in-chunk ring attention, same contract as
``decode_attention(return_stats=True)``.

Design follows the ragged paged attention literature cited in PAPERS.md.
No reference counterpart; VERDICT r5 next-step 1 (amortize the paged
kernel's grid-cell latency).
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -2.0**30


def _paged_kernel(
    *refs,
    # refs layout (scalar prefetch first):
    #   table_ref  SMEM (B, max_pages) int32
    #   last_ref   SMEM (B,) int32 — max valid key index per slot
    #   qpos_ref   SMEM (B,) int32 — query absolute position (window)
    #   [rstep_ref SMEM (1,) int32 — valid ring rows - 1, when ring]
    #   q_ref      VMEM (1, K, G, H)
    #   k_refs × n_strip   VMEM (K, 1, P, H) — one page each
    #   v_refs × n_strip   VMEM (K, 1, P, H)
    #   [ks/vs_refs × n_strip  VMEM (1, K, P) when quantized]
    #   [ringk_ref, ringv_ref  VMEM (1, K, R, H) when ring]
    #   acc_ref (1, K, G, H) f32, m_ref (1, K, G, 1), l_ref (1, K, G, 1)
    scale: float,
    softcap: float,
    window: int,
    page_size: int,
    sentinel: int,
    max_pages: int,
    q_blocks: int,
    quantized: bool,
    n_strip: int,
    n_blocks: int,
    ring: bool,
):
    it = iter(range(len(refs)))
    table_ref, last_ref, qpos_ref = (refs[next(it)] for _ in range(3))
    rstep_ref = refs[next(it)] if ring else None
    q_ref = refs[next(it)]
    k_refs = [refs[next(it)] for _ in range(n_strip)]
    v_refs = [refs[next(it)] for _ in range(n_strip)]
    if quantized:
        ks_refs = [refs[next(it)] for _ in range(n_strip)]
        vs_refs = [refs[next(it)] for _ in range(n_strip)]
    else:
        ks_refs = vs_refs = [None] * n_strip
    if ring:
        ringk_ref = refs[next(it)]
        ringv_ref = refs[next(it)]
    acc_ref, m_ref, l_ref = (refs[next(it)] for _ in range(3))

    b = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    last = last_ref[b]
    qpos = qpos_ref[b]

    def _attend_page(k_ref, v_ref, ks_ref, vs_ref, j0):
        """One page's online-softmax update — the same math page for
        page whatever the strip (the parity suite pins this)."""
        q = q_ref[0]                                      # [K, G, H]
        k = k_ref[:, 0]                                   # [K, P, H]
        v = v_ref[:, 0]
        if quantized:
            # The HBM→VMEM stream stays int8-sized, and a page's scales
            # come as one [K, P] block with P on the lanes: a key's
            # scale multiplies its SCORE (``q·(k·s) = (q·k)·s``) and a
            # value's scale its probability, both [K, G, P] with P on
            # the lanes too, so nothing is transposed in here and no
            # pool is re-laid in front of the call (a trailing singleton
            # on the scale pools cost a padded copy of each, 128 times
            # its size, a layer a step: PERF.md §6 PR 30).
            k = k.astype(jnp.float32)
            v = v.astype(jnp.float32)
            q = q.astype(jnp.float32)
            k_sc = ks_ref[0][:, None, :]                  # [K, 1, P]
            v_sc = vs_ref[0][:, None, :]
        s = jax.lax.dot_general(
            q, k,
            dimension_numbers=(((2,), (2,)), ((0,), (0,))),
            preferred_element_type=jnp.float32,
        ) * scale                                         # [K, G, P]
        if quantized:
            s = s * k_sc
        if softcap > 0.0:
            s = jnp.tanh(s / softcap) * softcap
        col = j0 + jax.lax.broadcasted_iota(jnp.int32, s.shape, 2)
        mask = col <= last
        if window > 0:
            # Speculative blocks pack D queries per G row (row = g*D + d,
            # query d at position qpos + d).
            qpos_row = qpos + (
                jax.lax.broadcasted_iota(jnp.int32, s.shape, 1) % q_blocks
                if q_blocks > 1 else 0
            )
            mask &= (qpos_row - col) < window
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_ref[0, :, :, :]                        # [K, G, 1]
        l_prev = l_ref[0, :, :, :]
        acc_prev = acc_ref[0]
        m_blk = jnp.max(s, axis=-1, keepdims=True)        # [K, G, 1]
        m_new = jnp.maximum(m_prev, m_blk)
        p = jnp.where(m_new > NEG_INF / 2, jnp.exp(s - m_new), 0.0)
        corr = jnp.where(
            m_prev > NEG_INF / 2, jnp.exp(m_prev - m_new), 0.0
        )
        l_ref[0, :, :, :] = l_prev * corr + jnp.sum(p, axis=-1, keepdims=True)
        if quantized:
            p = p * v_sc
        pv = jax.lax.dot_general(
            p.astype(v.dtype), v,
            dimension_numbers=(((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32,
        )                                                 # [K, G, H]
        acc_ref[0] = acc_prev * corr + pv
        m_ref[0, :, :, :] = m_new

    # The strip: pages j*n_strip .. j*n_strip + n_strip - 1, in order —
    # same visit order as the single-page grid, so accumulation order
    # (and therefore every float) is unchanged. Dead strip elements
    # (unallocated page, fully past `last`, outside the window, or
    # padding past n_blocks) skip their update entirely.
    for t in range(n_strip):
        jt = j * n_strip + t
        j0 = jt * page_size
        page = table_ref[b, jnp.minimum(jt, max_pages - 1)]
        live = (jt < n_blocks) & (page != sentinel) & (j0 <= last)
        if window > 0:
            # Most-permissive query decides page liveness: (qpos_row -
            # col) < window is EASIEST to satisfy at the smallest
            # position, i.e. row d=0 at qpos — later rows only tighten,
            # and the per-row mask inside applies them exactly.
            live &= (qpos - (j0 + page_size - 1)) < window

        @pl.when(live)
        def _attend(t=t, j0=j0):
            _attend_page(k_refs[t], v_refs[t], ks_refs[t], vs_refs[t], j0)

    if ring:
        # Fused in-chunk ring attention: the LAST cell computes the ring
        # block's own stats and merges them exactly like
        # engine/decode.py:_merge_stats (ring row r sits at
        # chunk-relative offset r; rows 0..step are valid — decode.py's
        # _ring_stats contract). Row `step` is always live, so m_r is
        # never NEG_INF.
        @pl.when(j == pl.num_programs(1) - 1)
        def _ring():
            step = rstep_ref[0]
            q = q_ref[0]                                  # [K, G, H]
            rk = ringk_ref[0]                             # [K, R, H]
            rv = ringv_ref[0]
            s = jax.lax.dot_general(
                q, rk,
                dimension_numbers=(((2,), (2,)), ((0,), (0,))),
                preferred_element_type=jnp.float32,
            ) * scale                                     # [K, G, R]
            if softcap > 0.0:
                s = jnp.tanh(s / softcap) * softcap
            r = jax.lax.broadcasted_iota(jnp.int32, s.shape, 2)
            mask = r <= step
            if window > 0:
                mask &= (step - r) < window
            s = jnp.where(mask, s, NEG_INF)
            m_r = jnp.max(s, axis=-1, keepdims=True)      # [K, G, 1]
            p = jnp.exp(s - m_r)
            l_r = jnp.sum(p, axis=-1, keepdims=True)
            acc_r = jax.lax.dot_general(
                p.astype(rv.dtype), rv,
                dimension_numbers=(((2,), (1,)), ((0,), (0,))),
                preferred_element_type=jnp.float32,
            )
            m_prev = m_ref[0, :, :, :]
            l_prev = l_ref[0, :, :, :]
            acc_prev = acc_ref[0]
            m_new = jnp.maximum(m_prev, m_r)
            wa = jnp.where(
                m_prev > NEG_INF / 2, jnp.exp(m_prev - m_new), 0.0
            )
            wb = jnp.where(m_r > NEG_INF / 2, jnp.exp(m_r - m_new), 0.0)
            acc_ref[0] = acc_prev * wa + acc_r * wb
            l_ref[0, :, :, :] = l_prev * wa + l_r * wb
            m_ref[0, :, :, :] = m_new


@functools.partial(
    jax.jit,
    static_argnames=(
        "n_blocks", "scale", "softcap", "window", "q_blocks", "n_strip",
        "interpret",
    ),
)
def paged_decode_attention(
    q: jax.Array,        # [B, N, H] current-token queries; with q_blocks=D
                         # the N axis packs D block queries per head
                         # (row = head * D + d, query d at position
                         # q_positions + d) — the speculative-decode shape
    k_pool: jax.Array,   # [K, num_pages, P, H]
    v_pool: jax.Array,
    table: jax.Array,    # [B, max_pages] int32 (sentinel = num_pages - 1)
    last_valid: jax.Array,   # [B] int32 — keys at s <= last_valid[b] attend
    q_positions: Optional[jax.Array] = None,  # [B]; defaults to last_valid
    n_blocks: int = 0,   # static — page slots to visit (bounded by host)
    scale: Optional[float] = None,
    softcap: float = 0.0,
    window: int = 0,
    q_blocks: int = 1,   # static — queries per head row (speculation's D)
    k_scales: Optional[jax.Array] = None,  # [K, num_pages, P] — int8 pools
    v_scales: Optional[jax.Array] = None,
    n_strip: int = 1,    # static — pages per grid cell (autotuned by the
                         # batcher at warmup; amortizes per-cell latency)
    ring_k: Optional[jax.Array] = None,  # [B, K, R, H] — fuse the chunk
    ring_v: Optional[jax.Array] = None,  # ring into this invocation
    ring_step: Optional[jax.Array] = None,  # scalar int32 — rows 0..step
                                            # of the ring are valid
    interpret: bool = False,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Ragged paged GQA decode attention. Returns unnormalized
    ``(acc [B,N,H] fp32, m [B,N], l [B,N])`` online-softmax stats over
    each slot's first ``n_blocks`` pages, processed ``n_strip`` pages
    per grid cell — plus the in-chunk ring when ``ring_k`` is given."""
    B, N, H = q.shape
    K, num_pages, P, _ = k_pool.shape
    assert N % K == 0
    G = N // K
    assert G % q_blocks == 0
    max_pages = table.shape[1]
    assert 1 <= n_blocks <= max_pages
    scale = scale if scale is not None else H ** -0.5
    sentinel = num_pages - 1
    # A strip wider than the visit count just re-reads clamped pages for
    # masked-off cells; clamp so the grid never carries dead DMA waves.
    n_strip = max(1, min(n_strip, n_blocks))
    n_cells = -(-n_blocks // n_strip)

    qg = q.reshape(B, K, G, H)
    last_valid = jnp.asarray(last_valid, jnp.int32).reshape(B)
    if q_positions is None:
        q_positions = last_valid
    q_positions = jnp.asarray(q_positions, jnp.int32).reshape(B)
    table = jnp.asarray(table, jnp.int32)

    quantized = k_scales is not None
    assert (k_scales is None) == (v_scales is None)
    ring = ring_k is not None
    if ring:
        assert ring_v is not None and ring_step is not None
        assert q_blocks == 1, "ring fusion is the plain-decode contract"
    kernel = functools.partial(
        _paged_kernel,
        scale=scale, softcap=softcap, window=window,
        page_size=P, sentinel=sentinel, max_pages=max_pages,
        q_blocks=q_blocks, quantized=quantized,
        n_strip=n_strip, n_blocks=n_blocks, ring=ring,
    )

    def page_map(t):
        # Strip element t of cell j covers logical page slot
        # j*n_strip + t. Clamp twice: the slot index to the table width
        # (padding cells past n_blocks) and the sentinel to a real page
        # id (the DMA must target valid memory); the kernel's `live`
        # predicate skips the compute either way.
        def _map(b, j, table_ref, *_):
            jt = jnp.minimum(j * n_strip + t, max_pages - 1)
            return (0, jnp.minimum(table_ref[b, jt], sentinel), 0, 0)
        return _map

    in_specs = [pl.BlockSpec((1, K, G, H), lambda b, j, *_: (b, 0, 0, 0))]
    operands = [qg]
    # The strip rides as n_strip replicated pool operands, one
    # scalar-prefetched index map each: one grid cell's prefetch wave
    # fetches the whole strip.
    in_specs += [pl.BlockSpec((K, 1, P, H), page_map(t)) for t in range(n_strip)]
    operands += [k_pool] * n_strip
    in_specs += [pl.BlockSpec((K, 1, P, H), page_map(t)) for t in range(n_strip)]
    operands += [v_pool] * n_strip
    if quantized:
        # A page's scales are one (K, P) block of the pool seen page-major:
        # TPU lowering wants a block's last two dims (8k, 128k) or the
        # array's own, and (K, P) is the array's. With 8 kv-heads the
        # chip keeps [K, pages, P] float32 page-major already (K fills
        # the 8 sublanes of a tile), so the view moves no byte.
        def scale_map(t):
            def _map(b, j, table_ref, *_):
                return page_map(t)(b, j, table_ref)[1:]
            return _map

        for pool in (k_scales, v_scales):
            in_specs += [
                pl.BlockSpec((1, K, P), scale_map(t)) for t in range(n_strip)
            ]
            operands += [pool.astype(jnp.float32).transpose(1, 0, 2)] * n_strip
    scalars = [table, last_valid, q_positions]
    if ring:
        R = ring_k.shape[2]
        scalars.append(jnp.asarray(ring_step, jnp.int32).reshape(1))
        in_specs += [
            pl.BlockSpec((1, K, R, H), lambda b, j, *_: (b, 0, 0, 0)),
            pl.BlockSpec((1, K, R, H), lambda b, j, *_: (b, 0, 0, 0)),
        ]
        operands += [ring_k, ring_v]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(scalars),  # table, last, qpos[, step]
        grid=(B, n_cells),
        in_specs=in_specs,
        out_specs=(
            pl.BlockSpec((1, K, G, H), lambda b, j, *_: (b, 0, 0, 0)),
            pl.BlockSpec((1, K, G, 1), lambda b, j, *_: (b, 0, 0, 0)),
            pl.BlockSpec((1, K, G, 1), lambda b, j, *_: (b, 0, 0, 0)),
        ),
    )
    acc, m, l = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=(
            jax.ShapeDtypeStruct((B, K, G, H), jnp.float32),
            jax.ShapeDtypeStruct((B, K, G, 1), jnp.float32),
            jax.ShapeDtypeStruct((B, K, G, 1), jnp.float32),
        ),
        interpret=interpret,
        name="paged_decode_attention",
    )(*scalars, *operands)
    return acc.reshape(B, N, H), m.reshape(B, N), l.reshape(B, N)


# What the strip's double-buffered input blocks may take of a v5e core's
# 16 MiB of scoped VMEM; the rest stays for the kernel's own temporaries
# (dequantized pages, scores). Checked against the chip's compiler at
# Llama-3-8B shapes in tests/test_tpu_compile.py.
STRIP_VMEM_BUDGET = 12 * 1024 * 1024
_LANES = 128


def strip_vmem_bytes(
    n_strip: int, page_size: int, n_kv_heads: int, head_dim: int,
    itemsize: int, quantized: bool,
) -> int:
    """VMEM the strip's K/V (and scale) blocks pin per pipeline stage,
    and room for the kernel's float32 copy of an int8 page.

    A scale block is ``(1, K, P)`` float32 (4 KB at 8 heads and pages of
    128) since PR 30; it rode as ``(K, 1, P, 1)``, whose trailing
    singleton padded it to a float32 page of head_dim 128, and is still
    COUNTED at that size: the kernel dequantizes a page to float32 before
    its dots, which takes as much again, and with the count at ``K*P*4``
    bytes the autotuner once offered an int8 pool a strip of 8 pages of
    128 that the chip's compiler refuses (21 MB of VMEM). Which strips an
    int8 pool can now take is a tuning question of its own."""
    kv = 2 * n_kv_heads * page_size * head_dim * itemsize
    sc = 2 * n_kv_heads * page_size * _LANES * 4 if quantized else 0
    return n_strip * (kv + sc)


def max_safe_strip(
    want: int, max_pages: int, page_size: int, n_kv_heads: int,
    head_dim: int, itemsize: int, quantized: bool,
) -> int:
    """Largest strip <= ``want`` (halving) whose double-buffered blocks
    stay within ``STRIP_VMEM_BUDGET`` — blowing VMEM fails at compile
    time, mid-serving."""
    strip = max(1, min(want, max_pages))
    while strip > 1 and 2 * strip_vmem_bytes(
        strip, page_size, n_kv_heads, head_dim, itemsize, quantized
    ) > STRIP_VMEM_BUDGET:
        strip //= 2
    return strip


# --------------------------------------------------------------------- #
# Multi-chip dispatch (shard_map) — ISSUE 13: tensor-parallel serving
# --------------------------------------------------------------------- #

def paged_sharding_ok(
    mesh,
    n_slots: int,
    n_kv_heads: int,
    batch_axes: Tuple[str, ...] = ("data", "fsdp"),
    head_axis: str = "model",
    seq_axis: str = "seq",
) -> bool:
    """True when the paged kernel can run per-shard with no cross-device
    work inside the attention itself: kv-heads divide the TP axis (the
    pool's K dim and the query rows' head-major packing split along the
    same boundary), the slot count divides the batch axes, and the
    sequence axis is unsharded. GQA heads are independent, so sharding
    them needs no collective — the cross-shard merge happens at the
    attention OUTPUT projection, whose row-parallel matmul all-reduces
    over ``model`` (the same contract as ``flash_sharding_ok``)."""
    shape = dict(mesh.shape)
    if int(shape.get(seq_axis, 1)) != 1:
        return False
    tp = int(shape.get(head_axis, 1))
    db = 1
    for a in batch_axes:
        db *= int(shape.get(a, 1))
    return n_kv_heads % tp == 0 and n_slots % db == 0


def paged_decode_attention_sharded(
    mesh,
    q: jax.Array,        # [B, N, H] — N packs (kv_head, group[, q_block])
    k_pool: jax.Array,   # [K, num_pages, P, H]
    v_pool: jax.Array,
    table: jax.Array,    # [B, max_pages]
    last_valid: jax.Array,
    q_positions: Optional[jax.Array] = None,
    n_blocks: int = 0,
    scale: Optional[float] = None,
    softcap: float = 0.0,
    window: int = 0,
    q_blocks: int = 1,
    k_scales: Optional[jax.Array] = None,
    v_scales: Optional[jax.Array] = None,
    n_strip: int = 1,
    ring_k: Optional[jax.Array] = None,
    ring_v: Optional[jax.Array] = None,
    ring_step: Optional[jax.Array] = None,
    interpret: bool = False,
    batch_axes: Tuple[str, ...] = ("data", "fsdp"),
    head_axis: str = "model",
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """:func:`paged_decode_attention` under ``shard_map``: the page
    pool's kv-head dim shards over the TP axis, slots over the data
    axes, and each shard runs the single-chip strip kernel on its own
    heads and pages — the pool never materializes whole on any chip.
    The query rows are head-major (``N = K·G[·D]``), so a contiguous N
    split lands each shard exactly its own kv-heads' queries. Attention
    over heads is embarrassingly parallel: the returned per-head stats
    need no cross-shard combine — the merge over the model axis is the
    attention output projection's all-reduce, emitted by GSPMD around
    this call. Same call contract and bit-identical per-shard math as
    the unsharded kernel (tests/test_multichip.py pins parity)."""
    from jax.sharding import PartitionSpec as P

    shape = dict(mesh.shape)
    present = [
        a for a in batch_axes
        if a in mesh.axis_names and int(shape.get(a, 1)) > 1
    ]
    bspec = tuple(present) if present else None
    head = (
        head_axis
        if head_axis in mesh.axis_names and int(shape.get(head_axis, 1)) > 1
        else None
    )
    if q_positions is None:
        q_positions = jnp.asarray(last_valid, jnp.int32)

    in_specs = [
        P(bspec, head, None),        # q
        P(head, None, None, None),   # k_pool
        P(head, None, None, None),   # v_pool
        P(bspec, None),              # table
        P(bspec),                    # last_valid
        P(bspec),                    # q_positions
    ]
    operands = [q, k_pool, v_pool, table, last_valid, q_positions]
    quantized = k_scales is not None
    if quantized:
        in_specs += [P(head, None, None), P(head, None, None)]
        operands += [k_scales, v_scales]
    ring = ring_k is not None
    if ring:
        in_specs += [
            P(bspec, head, None, None),
            P(bspec, head, None, None),
            P(),                     # ring_step scalar
        ]
        operands += [ring_k, ring_v, jnp.asarray(ring_step, jnp.int32)]

    def fn(q_, kp_, vp_, tb_, lv_, qp_, *rest):
        i = 0
        ks_ = vs_ = None
        if quantized:
            ks_, vs_ = rest[0], rest[1]
            i = 2
        rk_ = rv_ = rs_ = None
        if ring:
            rk_, rv_, rs_ = rest[i], rest[i + 1], rest[i + 2]
        return paged_decode_attention(
            q_, kp_, vp_, tb_, lv_, q_positions=qp_,
            n_blocks=n_blocks, scale=scale, softcap=softcap,
            window=window, q_blocks=q_blocks,
            k_scales=ks_, v_scales=vs_, n_strip=n_strip,
            ring_k=rk_, ring_v=rv_, ring_step=rs_,
            interpret=interpret,
        )

    return jax.shard_map(
        fn,
        mesh=mesh,
        in_specs=tuple(in_specs),
        out_specs=(
            P(bspec, head, None),    # acc [B, N, H]
            P(bspec, head),          # m   [B, N]
            P(bspec, head),          # l   [B, N]
        ),
        check_vma=False,
    )(*operands)


__all__ = [
    "paged_decode_attention",
    "paged_decode_attention_sharded",
    "paged_sharding_ok",
    "strip_vmem_bytes",
]
