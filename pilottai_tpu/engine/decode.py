"""Fused multi-step decode: N tokens per device dispatch.

Why this exists: every dispatch and every host<->device sync has a fixed
cost, and at a small per-step cost that floor dominates; a
one-dispatch-per-token decode loop is latency-bound long before the chip
is. ``decode_chunk`` jits a ``lax.scan`` over N decode steps — sampling,
EOS/budget tracking, and KV writes all on device — so the host touches
the device once per N tokens, and the batcher pipelines chunks so even
that touch overlaps compute (``engine/batcher.py``).

The KV-cache trick: inside the chunk the big per-layer cache panels are
**read-only** (prefix attention via the Pallas decode kernel — a custom
call that wrote carry state would force XLA to copy the panels every
layer, every step). Each step's fresh K/V goes to a tiny per-layer ring
buffer ([B, K, N, H]); in-chunk attention runs dense over the ring and
merges with the prefix pass by the standard online-softmax combine; one
batched scatter per layer lands the ring in the big cache at chunk end.

No reference counterpart: the reference's only decode loop is a remote
HTTP call (``pilott/engine/llm.py:59``). This file is the engine half of
the ≤500 ms p50 agent-step target (BASELINE.md).
"""

from __future__ import annotations

from functools import partial
from typing import Any, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from pilottai_tpu.engine.sampling import (
    SamplingState,
    admit_sampling,
    sample_core,
    split_step_keys,
)
from pilottai_tpu.models.common import ModelConfig, rms_norm, rope_tables
from pilottai_tpu.models.hybrid import forward_prefill_hybrid, walk_layers
from pilottai_tpu.models.qmatmul import qmatmul
from pilottai_tpu.models.quant import Q4Tensor, QTensor
from pilottai_tpu.models.transformer import (
    _attn_out,
    _embed,
    _mlp,
    _qkv,
    _rows_at,
    _unembed,
    forward_prefill,
)
from pilottai_tpu.ops.kvcache import (
    KVCache,
    dequantize_kv,
    quantize_kv,
    write_chunk_rows,
    write_prompts,
)
from pilottai_tpu.ops.paged import (
    PagedKVCache,
    gather_pages,
    install_lengths,
    write_chunk_rows_paged,
    write_prompts_paged,
)
from pilottai_tpu.ops.pallas.decode_attention import decode_attention
from pilottai_tpu.ops.pallas.paged_attention import (
    paged_decode_attention,
    paged_decode_attention_sharded,
)

NEG_INF = -2.0**30


def _refuse_recurrent(cfg: ModelConfig, what: str, asked: bool = True) -> None:
    """A model with recurrent state (``cfg.recurrent``) may not run a path
    that would carry its KV and drop its state: refuse it by name."""
    if asked and cfg.recurrent:
        raise NotImplementedError(
            f"{cfg.name} ({cfg.family}) keeps Mamba-2 state beside its KV "
            f"cache, and {what} would carry the KV and drop the state: not "
            f"built for a model with recurrent state"
        )


def _paged_kernel_for(kv_mesh):
    """The paged-attention entry point for this dispatch: per-shard
    under ``shard_map`` when the pool is model-sharded (``kv_mesh`` set
    by the batcher only when ``paged_sharding_ok``), else the plain
    kernel. ONE selection point — the sharded-dispatch contract must
    not diverge between the decode / spec / model-draft sites."""
    if kv_mesh is not None:
        return partial(paged_decode_attention_sharded, kv_mesh)
    return paged_decode_attention

# ---------------------------------------------------------------------- #
# Packed admission metadata: ONE int32 + ONE float32 staging buffer per
# admission dispatch instead of ~10 per-field host→device transfers.
# Each tiny ``jnp.asarray`` pays a transfer-setup + dispatch floor
# (PERF_NOTES round 8), so the
# per-row scalars ride two fixed-shape buffers and the admit functions
# unpack them FIRST thing inside the jit, where row slicing is free
# (the slices fuse into their consumers — values are bit-identical to
# the old per-field arguments).
# ---------------------------------------------------------------------- #

ADMIT_I32_ROWS = 9
(
    AI_SLOT,     # target slot (OOB = padding row)
    AI_TOPK,     # top-k (0 = disabled)
    AI_SEED,     # PRNG seed
    AI_EOS,      # eos token id (-1 = none)
    AI_BUDGET,   # max_new_tokens - 1
    AI_JSON,     # 1 = grammar-constrained JSON decoding
    AI_LEN,      # true prompt length (full prefill) / tail length (prefix)
    AI_SCHEMA,   # SchemaBank row (-1 = generic grammar)
    AI_PLEN,     # prefix length, broadcast (prefix admissions; else 0)
) = range(ADMIT_I32_ROWS)
ADMIT_F32_ROWS = 2
AF_TEMP, AF_TOPP = range(ADMIT_F32_ROWS)


def pack_admit_meta(
    A: int,
    slots=(),
    temps=(),
    topks=(),
    topps=(),
    seeds=(),
    eos=(),
    jsonm=(),
    budgets=(),
    lens=(),
    schema_ids=(),
    prefix_len: int = 0,
    pad_slot: int = 0,
):
    """Host-side builder for the packed admission staging buffers.

    Returns ``(meta_i32 [ADMIT_I32_ROWS, A], meta_f32 [ADMIT_F32_ROWS,
    A])`` as NUMPY arrays — the caller performs the single
    ``jnp.asarray`` per buffer (that is the point). Unspecified rows
    keep the padding-row defaults (slot = ``pad_slot`` i.e. OOB,
    temp 0, top_p 1, eos/schema −1, everything else 0)."""
    import numpy as _np

    mi = _np.zeros((ADMIT_I32_ROWS, A), _np.int32)
    mf = _np.zeros((ADMIT_F32_ROWS, A), _np.float32)
    mi[AI_SLOT] = pad_slot
    mi[AI_EOS] = -1
    mi[AI_SCHEMA] = -1
    mi[AI_PLEN] = int(prefix_len)
    mf[AF_TOPP] = 1.0
    for row_idx, values in (
        (AI_SLOT, slots), (AI_TOPK, topks), (AI_SEED, seeds),
        (AI_EOS, eos), (AI_BUDGET, budgets), (AI_JSON, jsonm),
        (AI_LEN, lens), (AI_SCHEMA, schema_ids),
    ):
        for col, v in enumerate(values):
            mi[row_idx, col] = int(v)
    for row_idx, values in ((AF_TEMP, temps), (AF_TOPP, topps)):
        for col, v in enumerate(values):
            mf[row_idx, col] = float(v)
    return mi, mf


def _unpack_admit_meta(meta_i32: jax.Array, meta_f32: jax.Array,
                       schema_tables) -> Tuple[jax.Array, ...]:
    """Split the packed staging buffers back into per-field rows
    (traced). ``schema_ids`` surfaces only when schema tables ride the
    dispatch, preserving the two-variant compile discipline the
    per-field signature had (a schema-free deployment never traces the
    schema path)."""
    return (
        meta_i32[AI_SLOT],
        meta_f32[AF_TEMP],
        meta_i32[AI_TOPK],
        meta_f32[AF_TOPP],
        meta_i32[AI_SEED],
        meta_i32[AI_EOS],
        meta_i32[AI_JSON].astype(bool),
        meta_i32[AI_BUDGET],
        meta_i32[AI_LEN],
        meta_i32[AI_SCHEMA] if schema_tables is not None else None,
    )


def _dequant_pair(k, v, scales, dtype):
    """Return full-precision (k, v) panels: identity for unquantized
    caches, fused broadcast-dequant for int8 ones (``scales`` is the
    matching (k_scale, v_scale) pair)."""
    if scales is None:
        return k, v
    return dequantize_kv(k, scales[0], dtype), dequantize_kv(v, scales[1], dtype)


def _bounded_panels(cache, l: int, op):
    """Layer ``l``'s prefix K/V as ``(k, v, scales)``: ``op`` bounds the
    read (a dense ``slice_in_dim`` or a paged ``gather_pages`` — both
    accept the [.., P, H] panels AND the [.., P] scale pools). int8
    caches return the RAW int8 panels plus ``(k_scale, v_scale)``; the
    attention applies scales AFTER its dot products
    (``q·(k·s) == s·(q·k)``, exactly), so per-block panel HBM reads stay
    int8-sized instead of a materialized full-precision copy. The ONE
    place the panel/scale pairing lives — decode_chunk,
    decode_chunk_spec and the paged prefix admission all read through
    it."""
    k_, v_ = cache.layers[l]
    sc = None if cache.scales is None else (
        op(cache.scales[l][0]), op(cache.scales[l][1])
    )
    return op(k_), op(v_), sc


def _layer_tail(cfg: ModelConfig, lp, x: jax.Array, attn: jax.Array) -> jax.Array:
    """Everything after a layer's attention weights: projection,
    optional post-norms, residual, MLP, residual. ONE definition shared
    by the plain chunk, the speculative chunk, the shallow-layer draft
    and the tail prefill — the draft's documented invariant ('the draft
    computes exactly the target's shallow prefix') depends on these
    staying in lockstep (review finding)."""
    out = _attn_out(cfg, lp["attn"], attn)
    if cfg.post_norms:
        out = rms_norm(out, lp["ln1_post"]["scale"], cfg.rms_eps, cfg.rms_offset)
    x = x + out
    h = rms_norm(x, lp["ln2"]["scale"], cfg.rms_eps, cfg.rms_offset)
    out, _ = _mlp(cfg, lp, h)
    if cfg.post_norms:
        out = rms_norm(out, lp["ln2_post"]["scale"], cfg.rms_eps, cfg.rms_offset)
    return x + out


# --------------------------------------------------------------------- #
# Fused greedy epilogue (ISSUE 14): logits projection + sampling as one
# vocab-tiled reduction for the common all-greedy, non-JSON dispatch.
# --------------------------------------------------------------------- #

# Vocab tile for the fused epilogue: big enough that the projection
# matmul stays MXU-shaped, small enough that a [B, tile] fp32 logits
# block lives in registers/VMEM instead of round-tripping HBM.
EPILOGUE_VOCAB_TILE = 8192


def _head_tile(params, off: int, end: int):
    """Columns [off, end) of the unembedding head, preserving the
    weight's quantized representation (the tile's HBM read stays
    int8/int4-sized). The head is ``lm_head`` when untied — possibly a
    ``QTensor`` (int4 mode falls the head back to int8) — else the
    transposed tied embedding."""
    if "lm_head" in params:
        head = params["lm_head"]
        if isinstance(head, QTensor):
            return QTensor(
                q=jax.lax.slice_in_dim(head.q, off, end, axis=-1),
                s=jax.lax.slice_in_dim(head.s, off, end, axis=-1),
            )
        if isinstance(head, Q4Tensor):
            return Q4Tensor(
                q=jax.lax.slice_in_dim(head.q, off, end, axis=-1),
                s=jax.lax.slice_in_dim(head.s, off, end, axis=-1),
                in_dim=head.in_dim, group=head.group,
            )
        return jax.lax.slice_in_dim(head, off, end, axis=-1)
    return jax.lax.slice_in_dim(params["embed"], off, end, axis=0).T


@jax.named_scope("unembed")  # the sampler is fused into it
def fused_greedy_epilogue(
    cfg: ModelConfig, params, h: jax.Array,
    tile: int = EPILOGUE_VOCAB_TILE,
) -> jax.Array:
    """Greedy sampling fused into the logits projection: final-normed
    hidden states ``h`` [B, T, E] → argmax token ids [B, T] int32,
    byte-identical to ``argmax(_unembed(cfg, params, h), -1)``.

    The projection runs tile-by-tile over the vocab with a running
    (max, argmax) carry, so the [B, T, V] fp32 logits buffer — 16 MB+
    per step at a 128K vocab, written and immediately re-read by the
    sampler — never materializes in HBM, and the separate sampler
    small-ops (two full-vocab sorts for the top-k/top-p masks that
    greedy slots never use) disappear entirely. Per-element dot
    products are unchanged (tiling splits the *output* axis, never the
    contraction), softcap applies per tile (same monotonic values), and
    ties resolve to the lowest index exactly like ``jnp.argmax``: the
    in-tile argmax picks the first max and the cross-tile carry only
    replaces on a strictly greater max."""
    B, T, E = h.shape
    V = cfg.vocab_size
    x = h.reshape(B * T, E)
    best = jnp.full((B * T,), -jnp.inf, jnp.float32)
    idx = jnp.zeros((B * T,), jnp.int32)
    for off in range(0, V, tile):
        end = min(off + tile, V)
        logits_t = qmatmul(
            x, _head_tile(params, off, end),
            preferred_element_type=jnp.float32,
        )
        if cfg.logit_softcap > 0.0:
            logits_t = (
                jnp.tanh(logits_t / cfg.logit_softcap) * cfg.logit_softcap
            )
        m = jnp.max(logits_t, axis=-1)
        a = jnp.argmax(logits_t, axis=-1).astype(jnp.int32)
        better = m > best
        idx = jnp.where(better, off + a, idx)
        best = jnp.where(better, m, best)
    return idx.reshape(B, T)


def _advance_keys(sampling: SamplingState) -> SamplingState:
    """PRNG parity with ``sample_core`` for the fused epilogue: the
    SAME key split per step (``sampling.split_step_keys``), keys
    carried, step keys discarded (greedy slots never consume them) —
    the sampling-state trajectory stays bit-identical to the unfused
    path by sharing the scheme, not by copying it."""
    _, carry_keys = split_step_keys(sampling.key)
    return sampling._replace(key=carry_keys)


class DecodeState(NamedTuple):
    """Per-slot generation state living on device across chunks."""

    tokens: jax.Array  # [B] int32 — next input token (last sampled)
    done: jax.Array    # [B] bool — finished or empty slot
    budget: jax.Array  # [B] int32 — generations still allowed

    @classmethod
    def create(cls, n_slots: int) -> "DecodeState":
        return cls(
            tokens=jnp.zeros((n_slots,), jnp.int32),
            done=jnp.ones((n_slots,), bool),
            budget=jnp.zeros((n_slots,), jnp.int32),
        )


@partial(jax.jit, donate_argnames=("state",))
def admit_decode(
    state: DecodeState,
    slots: jax.Array,         # [A] int32; OOB rows dropped
    first_tokens: jax.Array,  # [A] int32 — sampled from the prefill logits
    budgets: jax.Array,       # [A] int32 — max_new_tokens - 1 (first token
                              # already produced); <= 0 admits as done
    live: jax.Array,          # [A] bool — False rows are padding
) -> DecodeState:
    slots = jnp.where(live, slots, state.tokens.shape[0])
    return DecodeState(
        tokens=state.tokens.at[slots].set(first_tokens, mode="drop"),
        done=state.done.at[slots].set(budgets <= 0, mode="drop"),
        budget=state.budget.at[slots].set(jnp.maximum(budgets, 0), mode="drop"),
    )


@partial(jax.jit, donate_argnames=("state",))
def release_decode(state: DecodeState, slots: jax.Array) -> DecodeState:
    """Host-side completion/cancel: stop decoding these slots."""
    return DecodeState(
        tokens=state.tokens,
        done=state.done.at[slots].set(True, mode="drop"),
        budget=state.budget.at[slots].set(0, mode="drop"),
    )


@jax.named_scope("attn")
def _prefix_stats_dense(
    qg: jax.Array,       # [B, K, G, H]
    layer_k: jax.Array,  # [B, K, S, H] (compute dtype, or int8 w/ scales)
    layer_v: jax.Array,
    last: jax.Array,     # [B] max valid key index (may be -1: empty)
    qpos: jax.Array,     # [B] query absolute position
    scale: float,
    softcap: float,
    window: int,
    kv_scales=None,      # (k_scale [B,K,S], v_scale) for int8 panels
):
    """XLA fallback for the Pallas prefix kernel (CPU tests / tiny models).
    Same (acc, m, l) contract. int8 panels stream raw through the dots;
    the per-position scales fold in after (before softcap), which is
    algebraically exact and keeps HBM reads int8-sized."""
    B, K, G, H = qg.shape
    S = layer_k.shape[2]
    if kv_scales is not None:
        layer_k = layer_k.astype(qg.dtype)
    s = jnp.einsum(
        "bkgh,bksh->bkgs", qg, layer_k, preferred_element_type=jnp.float32
    ) * scale
    if kv_scales is not None:
        s = s * kv_scales[0][:, :, None, :]
    if softcap > 0.0:
        s = jnp.tanh(s / softcap) * softcap
    col = jnp.arange(S)[None, None, None, :]
    mask = col <= last[:, None, None, None]
    if window > 0:
        mask &= (qpos[:, None, None, None] - col) < window
    s = jnp.where(mask, s, NEG_INF)
    m = jnp.max(s, axis=-1)                                   # [B, K, G]
    p = jnp.where(
        m[..., None] > NEG_INF / 2, jnp.exp(s - m[..., None]), 0.0
    )
    l = jnp.sum(p, axis=-1)
    if kv_scales is not None:
        p = p * kv_scales[1][:, :, None, :]
        layer_v = layer_v.astype(qg.dtype)
        acc = jnp.einsum(
            "bkgs,bksh->bkgh", p.astype(qg.dtype), layer_v,
            preferred_element_type=jnp.float32,
        )
    else:
        acc = jnp.einsum(
            "bkgs,bksh->bkgh", p.astype(layer_v.dtype), layer_v,
            preferred_element_type=jnp.float32,
        )
    return acc.reshape(B, K * G, H), m.reshape(B, K * G), l.reshape(B, K * G)


@jax.named_scope("attn")
def _ring_stats(
    qg: jax.Array,      # [B, K, G, H]
    ring_k: jax.Array,  # [B, K, N, H]
    ring_v: jax.Array,
    step: jax.Array,    # scalar — current chunk step i (rows 0..i valid)
    scale: float,
    softcap: float,
    window: int,
):
    """In-chunk attention over the ring buffer. Row j holds the token at
    chunk-relative offset j; for an active slot offset == step, so the
    causal mask is j <= step and the window check (step - j) < window."""
    B, K, G, H = qg.shape
    N = ring_k.shape[2]
    s = jnp.einsum(
        "bkgh,bknh->bkgn", qg, ring_k, preferred_element_type=jnp.float32
    ) * scale
    if softcap > 0.0:
        s = jnp.tanh(s / softcap) * softcap
    j = jnp.arange(N)[None, None, None, :]
    mask = j <= step
    if window > 0:
        mask &= (step - j) < window
    s = jnp.where(mask, s, NEG_INF)
    m = jnp.max(s, axis=-1)
    p = jnp.exp(s - m[..., None])  # row 0 always valid -> never all-masked
    l = jnp.sum(p, axis=-1)
    acc = jnp.einsum(
        "bkgn,bknh->bkgh", p.astype(ring_v.dtype), ring_v,
        preferred_element_type=jnp.float32,
    )
    return acc.reshape(B, K * G, H), m.reshape(B, K * G), l.reshape(B, K * G)


@jax.named_scope("attn")
def _combine_stats(acc_a, m_a, l_a, acc_b, m_b, l_b):
    """Merge two online-softmax partials over disjoint key sets and
    normalize (final-merge form of ``_merge_stats``)."""
    acc, _, l = _merge_stats(acc_a, m_a, l_a, acc_b, m_b, l_b)
    return acc / jnp.maximum(l, 1e-30)[..., None]


@partial(
    jax.jit,
    static_argnames=(
        "cfg", "n_steps", "use_pallas", "prefix_bound", "page_strip",
        "kv_mesh", "fused_epilogue",
    ),
    donate_argnames=("cache", "dstate", "sampling"),
)
def decode_chunk(
    params,
    cfg: ModelConfig,
    cache: KVCache,
    dstate: DecodeState,
    sampling: SamplingState,
    n_steps: int,
    use_pallas: bool = True,
    prefix_bound: Optional[int] = None,
    table: Optional[jax.Array] = None,  # [B, max_pages] — paged cache only
    json_tables: Optional[Tuple[jax.Array, jax.Array]] = None,
    schema_tables: Optional[Tuple[jax.Array, jax.Array, jax.Array]] = None,
    # ^ SchemaBank (ALLOWED, NEXT, MINCOST) — schema-constrained slots
    # ^ (token_bytes [Vt, L], token_len [Vt]) — subword JSON grammar mask
    page_strip: int = 1,  # static — pages per paged-kernel grid cell
                          # (autotuned by the batcher at warmup)
    kv_mesh: Any = None,  # static — serving mesh: the paged Pallas path
                          # runs per-shard under shard_map (pool kv-heads
                          # over 'model', slots over 'data'); None = the
                          # single-chip dispatch
    fused_epilogue: bool = False,  # static — all slots greedy + non-JSON
                          # (the batcher checks at dispatch): sampling
                          # fuses into a vocab-tiled projection and the
                          # [B, V] logits never materialize
) -> Tuple[jax.Array, jax.Array, KVCache, DecodeState, SamplingState]:
    """Run ``n_steps`` decode steps for every slot in one dispatch.

    Returns ``(tokens [n, B], valid [n, B], cache, dstate, sampling)``;
    ``valid[i, b]`` marks tokens actually generated (slot active entering
    step i). Slots flip ``done`` on device at EOS / budget / context-full,
    so a finished slot stops writing cache and burning samples mid-chunk.

    ``prefix_bound`` (static) caps how much of each cache panel the prefix
    attention reads: the caller promises every *live* slot's length is
    ≤ bound, so keys past it can only belong to freed slots (whose output
    is discarded). Decode is HBM-bound and the cache read is roughly half
    the traffic at S=512 — reading ``[., ., bound, .]`` instead of the
    full ``[., ., S, .]`` panels makes short-context serving pay for the
    context it *has*, not the capacity it reserved. The host buckets the
    bound to powers of two so compile variants stay O(log S).
    """
    B = dstate.tokens.shape[0]
    paged = isinstance(cache, PagedKVCache)
    kv_scales = None  # scale pools for the Pallas paged kernel only
    if paged:
        assert table is not None, "paged decode needs the block table"
        P = cache.page_size
        S = table.shape[1] * P               # per-slot capacity
        Sb = S if prefix_bound is None else max(1, min(prefix_bound, S))
        n_blocks = -(-Sb // P)
        if use_pallas:
            prefix_panels = tuple(
                (k_, v_, None) for (k_, v_) in cache.layers
            )                                # pools; kernel reads via table
            kv_scales = cache.scales         # int8 pools dequant in-kernel
        else:
            # XLA fallback: materialize bounded panels ONCE per chunk
            # (pool contents are frozen during the scan — decode K/V
            # goes to the ring until chunk end), then run the same
            # dense prefix attention as the unpaged path; int8 panels
            # gather raw with their scales (applied post-dot).
            prefix_panels = tuple(
                _bounded_panels(
                    cache, l, lambda a: gather_pages(a, table, n_blocks),
                )
                for l in range(cfg.n_kv_layers)
            )
    else:
        S = cache.max_len
        Sb = S if prefix_bound is None else max(1, min(prefix_bound, S))
        # Bounded read-only views for the prefix attention (writes at
        # chunk end still land in the full panels); int8 panels slice
        # raw with their scales — applied after the dots, so per-step
        # HBM reads stay int8-sized.
        prefix_panels = tuple(
            _bounded_panels(
                cache, l, lambda a: jax.lax.slice_in_dim(a, 0, Sb, axis=2),
            )
            for l in range(cfg.n_kv_layers)
        )
    start = cache.lengths                    # [B] frozen during the chunk
    windows = cfg.window_sizes()
    qscale = cfg.query_scale if cfg.query_scale is not None else cfg.head_dim**-0.5
    G = cfg.n_heads // cfg.n_kv_heads
    batch_shape = (B, cfg.n_kv_heads, n_steps, cfg.head_dim)
    # Rings hold fresh in-chunk K/V in compute precision even when the
    # resident cache is int8 (they are quantized at the chunk-end write).
    cache_dtype = (
        cfg.dtype if cache.scales is not None else cache.layers[0][0].dtype
    )
    rings = tuple(
        (jnp.zeros(batch_shape, cache_dtype), jnp.zeros(batch_shape, cache_dtype))
        for _ in range(cfg.n_kv_layers)
    )
    prefix_last = start - 1                  # max valid prefix key index

    def step(carry):
        (
            i, tokens, done, budget, offset, sampling, rings, out_t, out_v,
            extra,
        ) = carry
        active = ~done
        pos = start + offset                 # current token's position
        x = _embed(cfg, params, tokens[:, None])          # [B, 1, E]
        sin, cos = rope_tables(pos[:, None], cfg.head_dim, cfg.rope_theta)

        new_rings = []

        def attend(l, p, h, window=0):
            """Layer ``l`` of the cache: this step's K/V into its ring,
            then the token's attention over the prefix and the ring.
            Returns ``(attn [B, 1, heads, H], (ring k, ring v))``."""
            layer_k, layer_v, layer_sc = prefix_panels[l]
            rk, rv = rings[l]
            q, k, v = _qkv(cfg, p, h, sin, cos)  # [B, 1, heads, H]
            rk = jax.lax.dynamic_update_slice(
                rk, k[:, 0][:, :, None].astype(rk.dtype), (0, 0, i, 0)
            )
            rv = jax.lax.dynamic_update_slice(
                rv, v[:, 0][:, :, None].astype(rv.dtype), (0, 0, i, 0)
            )

            qf = q[:, 0]                                  # [B, N, H]
            if paged and use_pallas:
                # One fused kernel invocation per layer: the page strip
                # streams the prefix AND the final grid cell folds the
                # chunk ring in (the separate per-layer ring dispatch +
                # combine this path used to pay per step is gone) — the
                # plain-decode stats contract allows it because the
                # ring's validity is the shared scalar `i`. On a serving
                # mesh the kernel runs per-shard (kv-heads over 'model',
                # slots over 'data'); the cross-shard merge is the
                # output projection's all-reduce, not an attention-side
                # collective (heads are independent).
                kernel = _paged_kernel_for(kv_mesh)
                acc_p, _, l_p = kernel(
                    qf, layer_k, layer_v, table, prefix_last,
                    q_positions=pos, n_blocks=n_blocks, n_strip=page_strip,
                    scale=qscale, softcap=cfg.attn_softcap, window=window,
                    k_scales=None if kv_scales is None else kv_scales[l][0],
                    v_scales=None if kv_scales is None else kv_scales[l][1],
                    ring_k=rk, ring_v=rv, ring_step=i,
                )
                attn = acc_p / jnp.maximum(l_p, 1e-30)[..., None]
            else:
                if use_pallas and not paged:
                    acc_p, m_p, l_p = decode_attention(
                        qf, layer_k, layer_v, prefix_last, q_positions=pos,
                        scale=qscale, softcap=cfg.attn_softcap, window=window,
                        return_stats=True,
                    )
                else:
                    acc_p, m_p, l_p = _prefix_stats_dense(
                        qf.reshape(B, cfg.n_kv_heads, G, cfg.head_dim),
                        layer_k, layer_v, prefix_last, pos,
                        qscale, cfg.attn_softcap, window,
                        kv_scales=layer_sc,
                    )
                acc_c, m_c, l_c = _ring_stats(
                    qf.reshape(B, cfg.n_kv_heads, G, cfg.head_dim),
                    rk, rv, i, qscale, cfg.attn_softcap, window,
                )
                attn = _combine_stats(acc_p, m_p, l_p, acc_c, m_c, l_c)

            return (
                attn.astype(h.dtype).reshape(B, 1, cfg.n_heads, cfg.head_dim),
                (rk, rv),
            )

        if cfg.layer_kinds:
            # One mixer a layer, in the published order; the state-space
            # layers move the state of the rows that are active.
            conv, ssm, routed = extra
            x, new_rings, conv, ssm, n = walk_layers(
                cfg, params, x, attend, conv, ssm, active[:, None],
            )
            extra = (conv, ssm, routed + n)
        else:
            for l in range(cfg.n_layers):
                lp = jax.tree.map(lambda a: a[l], params["layers"])
                h = rms_norm(x, lp["ln1"]["scale"], cfg.rms_eps, cfg.rms_offset)
                attn, ring = attend(l, lp["attn"], h, int(windows[l]))
                x = _layer_tail(cfg, lp, x, attn)
                new_rings.append(ring)

        h = rms_norm(x, params["final_norm"]["scale"], cfg.rms_eps, cfg.rms_offset)
        if fused_epilogue:
            # All-greedy non-JSON dispatch: argmax fused into the
            # vocab-tiled projection (byte-identical to the unfused
            # sampler for these slots — the JSON mask is the identity
            # when no slot enables it, and greedy never reads the
            # step key; the key split still advances for state parity).
            sampled = fused_greedy_epilogue(cfg, params, h)[:, 0]
            sampling = _advance_keys(sampling)
        else:
            logits = _unembed(cfg, params, h)[:, 0]       # [B, V] fp32
            sampled, sampling = sample_core(
                logits, sampling, json_remaining=budget,
                json_token_tables=json_tables,
                json_schema_tables=schema_tables,
            )
        new_budget = budget - active.astype(jnp.int32)
        hit_eos = (sampling.eos_id >= 0) & (sampled == sampling.eos_id)
        ctx_full = (pos + 1) >= (S - 1)
        new_done = done | (active & (hit_eos | (new_budget <= 0) | ctx_full))
        new_tokens = jnp.where(active, sampled, tokens)
        new_offset = offset + active.astype(jnp.int32)
        out_t = jax.lax.dynamic_update_slice(out_t, sampled[None], (i, 0))
        out_v = jax.lax.dynamic_update_slice(out_v, active[None], (i, 0))
        return (
            i + 1, new_tokens, new_done, new_budget, new_offset, sampling,
            tuple(new_rings), out_t, out_v, extra,
        )

    offset0 = jnp.zeros((B,), jnp.int32)
    # The state pool of a stack of unlike layers rides the loop and is
    # updated in place; () for a model whose layers all keep KV.
    state = cache.state
    extra0 = () if state is None else (state.conv, state.ssm, state.routed)
    carry0 = (
        jnp.int32(0), dstate.tokens, dstate.done, dstate.budget, offset0,
        sampling, rings,
        jnp.zeros((n_steps, B), jnp.int32), jnp.zeros((n_steps, B), bool),
        extra0,
    )
    # while_loop with all-done early exit (see decode_chunk_spec): each
    # step streams the full weight set, so steps past the last active
    # slot are pure waste — the dispatch now pays only for steps used.
    (
        _, tokens, done, budget, offset, sampling, rings, out_toks, out_valid,
        extra,
    ) = jax.lax.while_loop(
        lambda c: (c[0] < n_steps) & ~jnp.all(c[2]),
        step,
        carry0,
    )
    if state is not None:
        cache = cache._replace(state=state._replace(
            conv=extra[0], ssm=extra[1], routed=extra[2],
        ))

    if paged:
        cache = write_chunk_rows_paged(
            cache, table, [r[0] for r in rings], [r[1] for r in rings],
            start, offset,
        )
    else:
        cache = write_chunk_rows(
            cache, [r[0] for r in rings], [r[1] for r in rings], start, offset
        )
    dstate = DecodeState(tokens=tokens, done=done, budget=budget)
    return out_toks, out_valid, cache, dstate, sampling


# --------------------------------------------------------------------- #
# Speculative decode: n-gram (prompt-lookup) self-drafting
# --------------------------------------------------------------------- #
#
# One weight pass per token caps llama3-8b at ~605 ms per 48-token step on
# one v5e (8 GB int8 / 634 GB/s HBM) — VERDICT r2 Weak #2. Decode is
# memory-bound on the weight stream, so verifying a D-token block per pass
# streams the same bytes but can emit up to D tokens: the MXU cost of D
# query rows is noise next to the weight read. Drafts come from the
# sequence's own history (2-gram match → copy the continuation), the
# training-free scheme that excels exactly on agent workloads: JSON keys,
# tool names, and prompt spans repeat constantly. Acceptance only ever
# compares the model's OWN (masked) greedy output to the draft, so a bad
# draft costs speed, never correctness.
#
# Scope: greedy (temperature==0) slots speculate; sampled slots emit one
# exact-semantics token per block. Sampled streams are ALSO
# bit-identical to the non-speculative engine: a block advances the
# PRNG exactly once (row 0's sample_core) and emits exactly one sampled
# token, so the key sequence at emission points matches the plain
# chunk's step-per-token advance (pinned by
# tests/test_speculative.py::test_spec_sampled_slots_bit_identical).


def _ngram_drafts(
    history: jax.Array,  # [B, S] token ids by absolute position
    pos: jax.Array,      # [B] current token's position
    cur: jax.Array,      # [B] current token
    n_drafts: int,
) -> jax.Array:
    """Propose ``n_drafts`` continuation tokens per slot by matching the
    latest (prev2, prev, cur) 3-gram earlier in the slot's own history —
    backing off to the latest 2-gram — and copying what followed it. The
    3-gram tier disambiguates repeated contexts (a byte pair like ``",
    "`` recurs with many continuations inside JSON; three bytes usually
    pin the right one), which is where the 2-gram's acceptance plateaued.
    No match → zeros (harmless: acceptance compares against the model's
    output, so junk drafts just miss — draft quality affects speed,
    never content)."""
    B, S = history.shape
    idx = jnp.arange(S)[None, :]
    bidx = jnp.arange(B)[:, None]
    prev = jnp.take_along_axis(
        history, jnp.maximum(pos - 1, 0)[:, None], axis=1
    )                                                     # [B, 1]
    prev2 = jnp.take_along_axis(
        history, jnp.maximum(pos - 2, 0)[:, None], axis=1
    )
    prev_col = jnp.concatenate(
        [jnp.full((B, 1), -1, history.dtype), history[:, :-1]], axis=1
    )
    prev2_col = jnp.concatenate(
        [jnp.full((B, 2), -1, history.dtype), history[:, :-2]], axis=1
    )
    match = (history == cur[:, None]) & (prev_col == prev)
    # Only occurrences whose whole n-draft continuation is already
    # written (j + n_drafts <= pos): matching the frontier proposes
    # zeros from unwritten positions and never accepts — measured on
    # v5e as acceptance ~0 even on a constant output stream.
    match &= (idx <= pos[:, None] - n_drafts) & (idx >= 1)
    match3 = match & (prev2_col == prev2) & (idx >= 2) & (pos[:, None] >= 2)
    found = match.any(axis=1)
    found3 = match3.any(axis=1)
    j2 = jnp.argmax(jnp.where(match, idx, -1), axis=1)    # latest match
    j3 = jnp.argmax(jnp.where(match3, idx, -1), axis=1)
    j = jnp.where(found3, j3, j2)
    dpos = j[:, None] + 1 + jnp.arange(n_drafts)[None, :]
    drafts = history[bidx, jnp.minimum(dpos, S - 1)]
    return jnp.where(found[:, None], drafts, 0)


def _model_drafts(
    params,
    cfg: ModelConfig,
    draft_layers: int,
    n_draft: int,
    cur: jax.Array,      # [B] current token
    pos: jax.Array,      # [B] its absolute position
    prefix_panels,       # per-layer bounded panels (or pools when paged)
    rings,               # per-layer (rk, rv) chunk rings [B, K, R, H]
    start: jax.Array,    # [B] slot length at chunk start
    offset: jax.Array,   # [B] valid ring rows
    last: jax.Array,     # [B] max valid prefix key index
    paged_kernel,        # None, or dict(table=, n_blocks=, kv_scales=)
    windows,
    qscale: float,
) -> jax.Array:
    """Self-speculative drafting: run the target model's own FIRST
    ``draft_layers`` layers (plus final norm + unembed — weights shared,
    zero extra HBM) autoregressively for ``n_draft`` steps. This is the
    draft-model path for traffic the n-gram can't predict (novel prose,
    first-time prompts): a shallow prefix of the network agrees with the
    full forward far more often than a history lookup does, at
    ``draft_layers / n_layers`` of a weight pass per draft token
    (LayerSkip-style early-exit drafting; see PAPERS.md).

    The draft attends exactly what the verify pass will: bounded prefix
    panels + the chunk ring + its own in-block buffer — so the layers it
    DOES run compute the same K/V the target would for those tokens.
    Draft quality only affects speed, never output: acceptance still
    compares the target's masked greedy rows against these proposals."""
    B = cur.shape[0]
    K = cfg.n_kv_heads
    G = cfg.n_heads // cfg.n_kv_heads
    H = cfg.head_dim
    cache_dtype = rings[0][0].dtype
    bufs = tuple(
        (jnp.zeros((B, K, n_draft, H), cache_dtype),
         jnp.zeros((B, K, n_draft, H), cache_dtype))
        for _ in range(draft_layers)
    )

    def dstep(carry, j):
        tok, bufs = carry
        qpos = pos + j                       # input token's position
        x = _embed(cfg, params, tok[:, None])
        sin, cos = rope_tables(qpos[:, None], cfg.head_dim, cfg.rope_theta)
        new_bufs = []
        for l in range(draft_layers):
            lp = jax.tree.map(lambda a: a[l], params["layers"])
            window = int(windows[l])
            rk, rv = rings[l]
            bk, bv = bufs[l]
            h = rms_norm(x, lp["ln1"]["scale"], cfg.rms_eps, cfg.rms_offset)
            q, k, v = _qkv(cfg, lp["attn"], h, sin, cos)
            # Write THIS token's K/V before attending (count j+1): the
            # verify pass's in-block mask (e <= d) includes self, and the
            # draft must compute exactly the target's shallow prefix or
            # acceptance silently degrades (review finding).
            bk = jax.lax.dynamic_update_slice(
                bk, k[:, 0][:, :, None].astype(bk.dtype), (0, 0, j, 0)
            )
            bv = jax.lax.dynamic_update_slice(
                bv, v[:, 0][:, :, None].astype(bv.dtype), (0, 0, j, 0)
            )
            qf = q[:, 0]                                   # [B, N, H]
            qg = qf.reshape(B, K, G, H)
            if paged_kernel is not None:
                sc = paged_kernel["kv_scales"]
                kernel = _paged_kernel_for(paged_kernel.get("kv_mesh"))
                acc_p, m_p, l_p = kernel(
                    qf, prefix_panels[l][0], prefix_panels[l][1],
                    paged_kernel["table"], last, q_positions=qpos,
                    n_blocks=paged_kernel["n_blocks"], scale=qscale,
                    softcap=cfg.attn_softcap, window=window,
                    n_strip=paged_kernel["n_strip"],
                    k_scales=None if sc is None else sc[l][0],
                    v_scales=None if sc is None else sc[l][1],
                )
                acc_p = acc_p.reshape(B, K, G, H)
                m_p = m_p.reshape(B, K, G)
                l_p = l_p.reshape(B, K, G)
            else:
                acc_p, m_p, l_p = _prefix_stats_dense(
                    qg, prefix_panels[l][0], prefix_panels[l][1],
                    last, qpos, qscale, cfg.attn_softcap, window,
                    kv_scales=prefix_panels[l][2],
                )
                acc_p = acc_p.reshape(B, K, G, H)
                m_p = m_p.reshape(B, K, G)
                l_p = l_p.reshape(B, K, G)
            acc_r, m_r, l_r = _ragged_stats(
                qg, rk, rv, offset, start, qpos,
                qscale, cfg.attn_softcap, window,
            )
            acc_b, m_b, l_b = _ragged_stats(
                qg, bk, bv, jnp.full((B,), j + 1, jnp.int32), pos, qpos,
                qscale, cfg.attn_softcap, window,
            )
            acc, m, lsum = _merge_stats(acc_p, m_p, l_p, acc_r, m_r, l_r)
            acc, _, lsum = _merge_stats(acc, m, lsum, acc_b, m_b, l_b)
            attn = acc / jnp.maximum(lsum, 1e-30)[..., None]
            x = _layer_tail(
                cfg, lp, x,
                attn.astype(x.dtype).reshape(B, 1, cfg.n_heads, H),
            )
            new_bufs.append((bk, bv))
        h = rms_norm(
            x, params["final_norm"]["scale"], cfg.rms_eps, cfg.rms_offset
        )
        nxt = jnp.argmax(_unembed(cfg, params, h)[:, 0], axis=-1).astype(
            jnp.int32
        )
        return (nxt, tuple(new_bufs)), nxt

    (_, _), drafts = jax.lax.scan(
        dstep, (cur, bufs), jnp.arange(n_draft)
    )
    return drafts.T                                        # [B, n_draft]


@jax.named_scope("attn")
def _merge_stats(acc_a, m_a, l_a, acc_b, m_b, l_b):
    """Unnormalized online-softmax merge over disjoint key sets (the
    normalizing division happens once, after the last merge)."""
    m = jnp.maximum(m_a, m_b)
    wa = jnp.where(m_a > NEG_INF / 2, jnp.exp(m_a - m), 0.0)
    wb = jnp.where(m_b > NEG_INF / 2, jnp.exp(m_b - m), 0.0)
    return acc_a * wa[..., None] + acc_b * wb[..., None], m, l_a * wa + l_b * wb


@jax.named_scope("attn")
def _ragged_stats(
    qg: jax.Array,     # [B, K, G, H] single-position queries
    ks: jax.Array,     # [B, K, N, H] — row r valid iff r < count[b]
    vs: jax.Array,
    count: jax.Array,  # [B] valid rows
    pos0: jax.Array,   # [B] absolute position of row 0 (sliding window)
    qpos: jax.Array,   # [B] query positions
    scale: float,
    softcap: float,
    window: int,
):
    """Online-softmax partials over a per-slot ragged key buffer — the
    generic form of ``_ring_stats`` (whose validity is a shared scalar).
    Used by the shallow-layer draft for both the chunk ring and its own
    in-block buffer."""
    B, K, G, H = qg.shape
    N = ks.shape[2]
    s = jnp.einsum(
        "bkgh,bknh->bkgn", qg, ks, preferred_element_type=jnp.float32
    ) * scale
    if softcap > 0.0:
        s = jnp.tanh(s / softcap) * softcap
    r = jnp.arange(N)[None, None, None, :]
    mask = r < count[:, None, None, None]
    if window > 0:
        kpos = pos0[:, None, None, None] + r
        mask &= (qpos[:, None, None, None] - kpos) < window
    s = jnp.where(mask, s, NEG_INF)
    m = jnp.max(s, axis=-1)
    p = jnp.where(m[..., None] > NEG_INF / 2, jnp.exp(s - m[..., None]), 0.0)
    l = jnp.sum(p, axis=-1)
    acc = jnp.einsum(
        "bkgn,bknh->bkgh", p.astype(vs.dtype), vs,
        preferred_element_type=jnp.float32,
    )
    return acc, m, l


@jax.named_scope("attn")
def _spec_block_attn(
    qg: jax.Array,       # [B, K, G, D, H] block queries
    layer_k: jax.Array,  # [B, K, Sb, H] bounded prefix panels (None when
    layer_v: jax.Array,  # prefix_stats is given)
    ring_k: jax.Array,   # [B, K, R, H] chunk ring (row r = position start+r)
    ring_v: jax.Array,
    blk_k: jax.Array,    # [B, K, D, H] the block's own keys
    blk_v: jax.Array,
    last: jax.Array,     # [B] max valid prefix key index (may be -1)
    start: jax.Array,    # [B] slot length at chunk start
    offset: jax.Array,   # [B] valid ring rows
    qpos: jax.Array,     # [B, D] absolute query positions
    scale: float,
    softcap: float,
    window: int,
    prefix_stats: Optional[Tuple[jax.Array, jax.Array, jax.Array]] = None,
    # ^ precomputed (acc_p [B,K,G,D,H], m_p [B,K,G,D], l_p) — the Pallas
    # paged kernel's output; skips the dense prefix pass.
    kv_scales=None,      # (k_scale [B,K,S], v_scale) for int8 panels
) -> jax.Array:
    """Three-source attention for a speculative block: bounded prefix
    panels + in-chunk ring (per-slot valid count) + the block itself
    (causal). Dense XLA on purpose: decode attention is HBM-bound and
    dense beat the Pallas prefix kernel at serving context sizes
    (measured on v5e, round 2). The paged-pool path supplies its prefix
    partials via ``prefix_stats`` instead (its pages never materialize
    as dense panels)."""
    B, K, G, D, H = qg.shape

    def softcapped(s):
        return jnp.tanh(s / softcap) * softcap if softcap > 0.0 else s

    if prefix_stats is not None:
        acc_p, m_p, l_p = prefix_stats
    else:
        # Prefix: every block query sees the whole valid prefix. int8
        # panels stream raw; scales fold in after the dots (exact).
        lk = layer_k.astype(qg.dtype) if kv_scales is not None else layer_k
        s = jnp.einsum(
            "bkgdh,bksh->bkgds", qg, lk,
            preferred_element_type=jnp.float32,
        ) * scale
        if kv_scales is not None:
            s = s * kv_scales[0][:, :, None, None, :]
        s = softcapped(s)
        col = jnp.arange(layer_k.shape[2])[None, None, None, None, :]
        mask = col <= last[:, None, None, None, None]
        if window > 0:
            mask &= (qpos[:, None, None, :, None] - col) < window
        s = jnp.where(mask, s, NEG_INF)
        m_p = jnp.max(s, axis=-1)
        p = jnp.where(
            m_p[..., None] > NEG_INF / 2, jnp.exp(s - m_p[..., None]), 0.0
        )
        l_p = jnp.sum(p, axis=-1)
        if kv_scales is not None:
            p = p * kv_scales[1][:, :, None, None, :]
            lv = layer_v.astype(qg.dtype)
        else:
            lv = layer_v
        acc_p = jnp.einsum(
            "bkgds,bksh->bkgdh", p.astype(qg.dtype if kv_scales is not None
                                          else layer_v.dtype), lv,
            preferred_element_type=jnp.float32,
        )

    # Ring: rows < offset are live; row r sits at position start + r.
    R = ring_k.shape[2]
    s = softcapped(jnp.einsum(
        "bkgdh,bkrh->bkgdr", qg, ring_k,
        preferred_element_type=jnp.float32,
    ) * scale)
    r = jnp.arange(R)[None, None, None, None, :]
    rpos = start[:, None, None, None, None] + r
    mask = r < offset[:, None, None, None, None]
    if window > 0:
        mask &= (qpos[:, None, None, :, None] - rpos) < window
    s = jnp.where(mask, s, NEG_INF)
    m_r = jnp.max(s, axis=-1)
    p = jnp.where(m_r[..., None] > NEG_INF / 2, jnp.exp(s - m_r[..., None]), 0.0)
    l_r = jnp.sum(p, axis=-1)
    acc_r = jnp.einsum(
        "bkgdr,bkrh->bkgdh", p.astype(ring_v.dtype), ring_v,
        preferred_element_type=jnp.float32,
    )

    # Block itself: causal within the D candidates (e <= d); query d is
    # always its own key, so this source is never empty.
    s = softcapped(jnp.einsum(
        "bkgdh,bkeh->bkgde", qg, blk_k,
        preferred_element_type=jnp.float32,
    ) * scale)
    e = jnp.arange(D)[None, None, None, None, :]
    d = jnp.arange(D)[None, None, None, :, None]
    mask = e <= d
    if window > 0:
        mask &= (d - e) < window
    s = jnp.where(mask, s, NEG_INF)
    m_b = jnp.max(s, axis=-1)
    p = jnp.exp(s - m_b[..., None])
    l_b = jnp.sum(p, axis=-1)
    acc_b = jnp.einsum(
        "bkgde,bkeh->bkgdh", p.astype(blk_v.dtype), blk_v,
        preferred_element_type=jnp.float32,
    )

    acc, m, l = _merge_stats(acc_p, m_p, l_p, acc_r, m_r, l_r)
    acc, _, l = _merge_stats(acc, m, l, acc_b, m_b, l_b)
    attn = acc / jnp.maximum(l, 1e-30)[..., None]         # [B, K, G, D, H]
    return attn.transpose(0, 3, 1, 2, 4).reshape(B, D, K * G * H)


@partial(
    jax.jit,
    static_argnames=(
        "cfg", "n_steps", "draft_len", "prefix_bound", "use_pallas",
        "draft_layers", "page_strip", "kv_mesh", "fused_epilogue",
    ),
    donate_argnames=("cache", "dstate", "sampling", "history"),
)
def decode_chunk_spec(
    params,
    cfg: ModelConfig,
    cache: KVCache,
    dstate: DecodeState,
    sampling: SamplingState,
    history: jax.Array,      # [B, S] token ids by position
    n_steps: int,
    draft_len: int,          # D >= 2: block width (1 current + D-1 drafts)
    prefix_bound: Optional[int] = None,
    json_tables: Optional[Tuple[jax.Array, jax.Array]] = None,
    schema_tables: Optional[Tuple[jax.Array, jax.Array, jax.Array]] = None,
    table: Optional[jax.Array] = None,  # [B, max_pages] — paged cache only
    use_pallas: bool = False,           # paged prefix reads via the Pallas
                                        # kernel (TPU); else gather fallback
    draft_layers: int = 0,   # >0: shallow-layer self-drafting available
    draft_mode: Optional[jax.Array] = None,  # [B] bool — slots whose
                                        # drafts come from the model
                                        # instead of the n-gram lookup
    page_strip: int = 1,     # static — pages per paged-kernel grid cell
    kv_mesh: Any = None,     # static — serving mesh for the per-shard
                             # paged kernel (see decode_chunk)
    fused_epilogue: bool = False,  # static — all slots greedy + non-JSON:
                             # row 0's sampler AND the verify rows fuse
                             # into one vocab-tiled argmax (see
                             # decode_chunk)
) -> Tuple[jax.Array, jax.Array, KVCache, DecodeState, SamplingState, jax.Array]:
    """Speculative fused chunk: ``n_steps`` verify-blocks of ``draft_len``
    tokens per dispatch. Same contract as ``decode_chunk`` except the
    token stream comes back as ``[n_steps * draft_len, B]`` (block-major,
    draft-minor) and the per-slot emit count varies 1..D per block.

    Greedy slots emit ``accepted + 1`` tokens per weight pass —
    bit-identical to the non-speculative chunk's output. Sampled slots
    emit exactly one sampled token per block, ALSO bit-identical: one
    PRNG advance per block == one advance per emitted token, matching
    the plain chunk's key sequence at every emission position.

    Works on BOTH caches: dense panels are read through bounded slices;
    paged pools through the block table — the extended Pallas paged
    kernel streams each block's D queries against the slot's pages
    (``q_blocks``), or the XLA fallback materializes bounded dense
    panels once per chunk (pool contents are frozen during the scan)."""
    _refuse_recurrent(cfg, "speculative decoding")
    from pilottai_tpu.engine.sampling import _advance_json, fused_verify_rows

    B = dstate.tokens.shape[0]
    D = draft_len
    assert D >= 2, "draft_len < 2 is plain decode_chunk"
    paged = isinstance(cache, PagedKVCache)
    kv_scales = None
    if paged:
        assert table is not None, "paged decode needs the block table"
        P = cache.page_size
        S = table.shape[1] * P
        Sb = S if prefix_bound is None else max(1, min(prefix_bound, S))
        n_blocks = -(-Sb // P)
        if use_pallas:
            prefix_panels = tuple(
                (k_, v_, None) for (k_, v_) in cache.layers
            )                                # pools; kernel reads via table
            kv_scales = cache.scales
        else:
            prefix_panels = tuple(
                _bounded_panels(
                    cache, l, lambda a: gather_pages(a, table, n_blocks),
                )
                for l in range(cfg.n_layers)
            )
    else:
        S = cache.max_len
        Sb = S if prefix_bound is None else max(1, min(prefix_bound, S))
        prefix_panels = tuple(
            _bounded_panels(
                cache, l, lambda a: jax.lax.slice_in_dim(a, 0, Sb, axis=2),
            )
            for l in range(cfg.n_layers)
        )
    start = cache.lengths
    windows = cfg.window_sizes()
    qscale = cfg.query_scale if cfg.query_scale is not None else cfg.head_dim**-0.5
    G = cfg.n_heads // cfg.n_kv_heads
    R = n_steps * D
    # Rings stay in compute precision; the chunk-end write quantizes.
    cache_dtype = (
        cfg.dtype if cache.scales is not None else cache.layers[0][0].dtype
    )
    ring_shape = (B, cfg.n_kv_heads, R, cfg.head_dim)
    rings = tuple(
        (jnp.zeros(ring_shape, cache_dtype), jnp.zeros(ring_shape, cache_dtype))
        for _ in range(cfg.n_layers)
    )
    prefix_last = start - 1
    bidx = jnp.arange(B)

    def step(carry):
        (
            i, tokens, done, budget, offset, sampling, history, rings,
            out_toks, out_valid,
        ) = carry
        active = ~done
        pos = start + offset
        drafts = _ngram_drafts(history, pos, tokens, D - 1)
        if draft_layers > 0:
            # Adaptive drafting: slots whose n-gram acceptance EMA
            # collapsed (host-side hysteresis, engine/batcher.py) draft
            # through the model's own first layers instead. lax.cond
            # skips the shallow forward entirely while every slot is
            # still n-gram-happy.
            pk_info = (
                {"table": table, "n_blocks": n_blocks,
                 "kv_scales": kv_scales, "n_strip": page_strip,
                 "kv_mesh": kv_mesh}
                if (paged and use_pallas) else None
            )
            mode = (
                draft_mode if draft_mode is not None
                else jnp.zeros((B,), bool)
            )
            mdrafts = jax.lax.cond(
                jnp.any(mode),
                lambda: _model_drafts(
                    params, cfg, draft_layers, D - 1, tokens, pos,
                    prefix_panels, rings, start, offset, prefix_last,
                    pk_info, windows, qscale,
                ),
                lambda: drafts,
            )
            drafts = jnp.where(mode[:, None], mdrafts, drafts)
        blk = jnp.concatenate([tokens[:, None], drafts], axis=1)  # [B, D]
        pvec = pos[:, None] + jnp.arange(D)[None, :]
        x = _embed(cfg, params, blk)                              # [B, D, E]
        sin, cos = rope_tables(pvec, cfg.head_dim, cfg.rope_theta)

        new_rings = []
        for l in range(cfg.n_layers):
            lp = jax.tree.map(lambda a: a[l], params["layers"])
            window = int(windows[l])
            layer_k, layer_v, layer_sc = prefix_panels[l]
            rk, rv = rings[l]
            p = lp["attn"]

            h = rms_norm(x, lp["ln1"]["scale"], cfg.rms_eps, cfg.rms_offset)
            q, k, v = _qkv(cfg, p, h, sin, cos)  # [B, D, heads, H]
            blk_k = k.transpose(0, 2, 1, 3).astype(cache_dtype)  # [B, K, D, H]
            blk_v = v.transpose(0, 2, 1, 3).astype(cache_dtype)
            qg = q.transpose(0, 2, 1, 3).reshape(
                B, cfg.n_kv_heads, G, D, cfg.head_dim
            )
            if paged and use_pallas:
                # Pallas paged prefix read with D query rows per slot
                # (q_blocks): the kernel offsets row d's position by d
                # for the sliding-window mask; causality vs the prefix
                # is free (every prefix key precedes the block).
                kernel = _paged_kernel_for(kv_mesh)
                acc_p, m_p, l_p = kernel(
                    qg.reshape(B, cfg.n_kv_heads * G * D, cfg.head_dim),
                    layer_k, layer_v, table, prefix_last,
                    q_positions=pos, n_blocks=n_blocks, q_blocks=D,
                    n_strip=page_strip,
                    scale=qscale, softcap=cfg.attn_softcap, window=window,
                    k_scales=None if kv_scales is None else kv_scales[l][0],
                    v_scales=None if kv_scales is None else kv_scales[l][1],
                )
                pstats = (
                    acc_p.reshape(B, cfg.n_kv_heads, G, D, cfg.head_dim),
                    m_p.reshape(B, cfg.n_kv_heads, G, D),
                    l_p.reshape(B, cfg.n_kv_heads, G, D),
                )
                attn = _spec_block_attn(
                    qg, None, None, rk, rv, blk_k, blk_v,
                    prefix_last, start, offset, pvec,
                    qscale, cfg.attn_softcap, window,
                    prefix_stats=pstats,
                )
            else:
                attn = _spec_block_attn(
                    qg, layer_k, layer_v, rk, rv, blk_k, blk_v,
                    prefix_last, start, offset, pvec,
                    qscale, cfg.attn_softcap, window,
                    kv_scales=layer_sc,
                )
            x = _layer_tail(
                cfg, lp, x,
                attn.astype(x.dtype).reshape(B, D, cfg.n_heads, cfg.head_dim),
            )
            new_rings.append((blk_k, blk_v))

        h = rms_norm(x, params["final_norm"]["scale"], cfg.rms_eps, cfg.rms_offset)

        # ---- verify ---------------------------------------------------
        if fused_epilogue:
            # All-greedy non-JSON dispatch: row 0's sampler AND every
            # verify row reduce to argmax (the grammar mask is the
            # identity with no JSON slot), so all D rows fuse into one
            # vocab-tiled projection+argmax and the [B, D, V] fp32
            # logits never land in HBM. One key split preserves the
            # plain sampler's one-advance-per-block PRNG trajectory.
            emitted = fused_greedy_epilogue(cfg, params, h)    # [B, D]
            sampling = _advance_keys(sampling)
        else:
            logits = _unembed(cfg, params, h)             # [B, D, V] fp32
            # Row 0 runs the full sampler (mask + greedy/sample + key +
            # json advance) — identical per-token semantics to the
            # plain chunk.
            pre_row0 = sampling
            tok0, sampling = sample_core(
                logits[:, 0], sampling, json_remaining=budget,
                json_token_tables=json_tables,
                json_schema_tables=schema_tables,
            )
            # Rows 1..D-1: masked greedy with coords advanced along the
            # DRAFT path (rows only matter while drafts keep being
            # accepted, and then draft == emitted, so the draft-path
            # coords are the right ones). One fused mask+argmax across
            # all verify rows — the per-row dispatch loop was the
            # sampler small-op floor (sampling.fused_verify_rows;
            # byte-identical per row).
            verify = fused_verify_rows(
                logits[:, 1:], blk[:, 1:], pre_row0, budget,
                token_tables=json_tables, schema_tables=schema_tables,
            )
            emitted = jnp.concatenate([tok0[:, None], verify], axis=1)

        # Leading-match acceptance (greedy slots only).
        match = emitted[:, : D - 1] == blk[:, 1:]         # [B, D-1]
        lead = jnp.cumprod(match.astype(jnp.int32), axis=1)
        acc = jnp.sum(lead, axis=1)                       # [B] 0..D-1
        greedy_slot = sampling.temperature <= 0.0
        cand = jnp.where(greedy_slot, acc + 1, 1)         # tokens offered

        # Truncate at EOS / budget / context-full, terminal included.
        jj = jnp.arange(D)[None, :]
        eos_hit = (sampling.eos_id[:, None] >= 0) & (
            emitted == sampling.eos_id[:, None]
        )
        ctx_full = (pvec + 1) >= (S - 1)
        term = eos_hit | ctx_full | (budget[:, None] - (jj + 1) <= 0)
        no_term_before = jnp.cumprod(
            jnp.concatenate(
                [jnp.ones((B, 1), jnp.int32), 1 - term[:, :-1].astype(jnp.int32)],
                axis=1,
            ),
            axis=1,
        ).astype(bool)
        emit_mask = (jj < cand[:, None]) & no_term_before & active[:, None]
        n_emit = jnp.sum(emit_mask.astype(jnp.int32), axis=1)  # [B] 0..D

        terminated = jnp.any(term & emit_mask, axis=1)
        new_done = done | (active & terminated)
        new_budget = budget - n_emit
        new_offset = offset + n_emit
        # Next current token: the last emitted (bonus or terminal; unused
        # when done).
        last_idx = jnp.maximum(n_emit - 1, 0)
        new_tokens = jnp.where(
            active, emitted[bidx, last_idx], tokens
        )

        # Json coords: row 0 already advanced inside sample_core; advance
        # by the remaining emitted tokens. Skipped under the fused
        # epilogue — with no JSON-enabled slot every advance is the
        # identity (``_advance_json`` gates on ``json_enabled``), so the
        # sampling-state trajectory is unchanged by construction.
        if not fused_epilogue:
            for j in range(1, D):
                stepped = _advance_json(
                    sampling, emitted[:, j], json_tables, schema_tables
                )
                take = emit_mask[:, j]
                sampling = sampling._replace(
                    json_state=jnp.where(take, stepped.json_state, sampling.json_state),
                    json_stack=jnp.where(take, stepped.json_stack, sampling.json_stack),
                    json_depth=jnp.where(take, stepped.json_depth, sampling.json_depth),
                )

        # History: emitted token j lives at position pos + 1 + j.
        hpos = jnp.where(emit_mask, pos[:, None] + 1 + jj, S)
        history = history.at[bidx[:, None], hpos].set(emitted, mode="drop")

        # Ring: block token k is in-sequence iff k < n_emit (cur plus the
        # accepted, non-terminal drafts — terminal/bonus tokens get their
        # K/V next block, exactly like the plain chunk).
        rpos = jnp.where(jj < n_emit[:, None], offset[:, None] + jj, R)
        out_rings = []
        for (rk, rv), (bk, bv) in zip(rings, new_rings):
            rk = rk.at[bidx[:, None], :, rpos].set(
                bk.transpose(0, 2, 1, 3), mode="drop"
            )
            rv = rv.at[bidx[:, None], :, rpos].set(
                bv.transpose(0, 2, 1, 3), mode="drop"
            )
            out_rings.append((rk, rv))

        out_toks = jax.lax.dynamic_update_slice(
            out_toks, emitted[None], (i, 0, 0)
        )
        out_valid = jax.lax.dynamic_update_slice(
            out_valid, emit_mask[None], (i, 0, 0)
        )
        return (
            i + 1, new_tokens, new_done, new_budget, new_offset, sampling,
            history, tuple(out_rings), out_toks, out_valid,
        )

    offset0 = jnp.zeros((B,), jnp.int32)
    carry0 = (
        jnp.int32(0), dstate.tokens, dstate.done, dstate.budget, offset0,
        sampling, history, rings,
        jnp.zeros((n_steps, B, D), jnp.int32),
        jnp.zeros((n_steps, B, D), bool),
    )
    # while_loop, not scan: a verify-block costs one full weight pass
    # (the whole point of speculation is that decode is weight-stream
    # bound), so when every slot is done/budget-exhausted mid-chunk the
    # remaining blocks are pure waste — measured on v5e as the dominant
    # overhead above the bandwidth floor at wave tails. Early exit makes
    # a generous chunk_size free: the dispatch pays for the blocks the
    # slowest slot actually needed.
    (
        _, tokens, done, budget, offset, sampling, history, rings,
        out_toks, out_valid,
    ) = jax.lax.while_loop(
        lambda c: (c[0] < n_steps) & ~jnp.all(c[2]),
        step,
        carry0,
    )

    # [n, B, D] -> [n*D, B] block-major so the host fold sees the plain
    # chunk's [rows, B] contract.
    out_toks = out_toks.transpose(0, 2, 1).reshape(n_steps * D, B)
    out_valid = out_valid.transpose(0, 2, 1).reshape(n_steps * D, B)

    if paged:
        cache = write_chunk_rows_paged(
            cache, table, [r[0] for r in rings], [r[1] for r in rings],
            start, offset,
        )
    else:
        cache = write_chunk_rows(
            cache, [r[0] for r in rings], [r[1] for r in rings], start, offset
        )
    dstate = DecodeState(tokens=tokens, done=done, budget=budget)
    return out_toks, out_valid, cache, dstate, sampling, history


# --------------------------------------------------------------------- #
# Prefix-cached admission (engine/prefix_cache.py)
# --------------------------------------------------------------------- #


@jax.named_scope("attn")
def _tail_prefix_attn(
    qg: jax.Array,          # [A, K, G, T, H] tail queries
    pk: jax.Array,          # [K, P, H] shared cached-prefix keys
    pv: jax.Array,
    blk_k: jax.Array,       # [A, K, T, H] tail's own keys
    blk_v: jax.Array,
    prefix_len: jax.Array,  # scalar int32 — true prefix length (<= P)
    valid: jax.Array,       # [A] true tail lengths
    scale: float,
    softcap: float,
    window: int,
) -> jax.Array:
    """Tail-prefill attention: every tail query attends the whole cached
    prefix plus the tail causally. The prefix panels carry no batch dim —
    one cached prompt serves the whole admission group."""
    A, K, G, T, H = qg.shape

    def softcapped(s):
        return jnp.tanh(s / softcap) * softcap if softcap > 0.0 else s

    qpos = prefix_len + jnp.arange(T)                       # tail positions

    def prefix_stats(pkw, pvw, col):
        """Flash partials of the tail queries against one span of prefix
        keys (``col`` are the span's absolute columns)."""
        s = softcapped(jnp.einsum(
            "akgth,kph->akgtp", qg, pkw, preferred_element_type=jnp.float32,
        ) * scale)
        mask = col[None, None, None, None, :] < prefix_len
        if window > 0:
            mask = mask & (
                (qpos[None, None, None, :, None] - col[None, None, None, None, :])
                < window
            )
        s = jnp.where(mask, s, NEG_INF)
        m_w = jnp.max(s, axis=-1)
        p = jnp.where(
            m_w[..., None] > NEG_INF / 2, jnp.exp(s - m_w[..., None]), 0.0
        )
        l_w = jnp.sum(p, axis=-1)
        acc_w = jnp.einsum(
            "akgtp,kph->akgth", p.astype(pvw.dtype), pvw,
            preferred_element_type=jnp.float32,
        )
        return acc_w, m_w, l_w

    P = pk.shape[1]
    # The scores tensor is A·K·G·T·P·4 bytes; one shot at a long prefix
    # (8K chain × 1K tail × 8 rows = 8 GB) OOMs — window the prefix with
    # online-softmax merging (flash over the chain, coarse-grained)
    # whenever the full scores would be big.
    W = 2048
    one_shot_bytes = 4 * A * K * G * T * P
    if P > W and P % W == 0 and one_shot_bytes > (1 << 30):
        nw = P // W
        pk_w = pk.reshape(K, nw, W, H).transpose(1, 0, 2, 3)
        pv_w = pv.reshape(K, nw, W, H).transpose(1, 0, 2, 3)
        cols = (
            jnp.arange(nw)[:, None] * W + jnp.arange(W)[None, :]
        ).astype(jnp.int32)

        def wstep(carry, xs):
            acc, m, l = carry
            acc_w, m_w, l_w = prefix_stats(*xs)
            return _merge_stats(acc, m, l, acc_w, m_w, l_w), None

        init = (
            jnp.zeros((A, K, G, T, H), jnp.float32),
            jnp.full((A, K, G, T), NEG_INF, jnp.float32),
            jnp.zeros((A, K, G, T), jnp.float32),
        )
        (acc_p, m_p, l_p), _ = jax.lax.scan(
            wstep, init, (pk_w, pv_w, cols)
        )
    else:
        acc_p, m_p, l_p = prefix_stats(pk, pv, jnp.arange(P))

    s = softcapped(jnp.einsum(
        "akgth,akeh->akgte", qg, blk_k, preferred_element_type=jnp.float32,
    ) * scale)
    e = jnp.arange(T)[None, None, None, None, :]
    t = jnp.arange(T)[None, None, None, :, None]
    mask = (e <= t) & (e < valid[:, None, None, None, None])
    if window > 0:
        mask = mask & ((t - e) < window)
    s = jnp.where(mask, s, NEG_INF)
    m_b = jnp.max(s, axis=-1)
    p = jnp.exp(s - m_b[..., None])  # e == t always valid → never empty
    l_b = jnp.sum(p, axis=-1)
    acc_b = jnp.einsum(
        "akgte,akeh->akgth", p.astype(blk_v.dtype), blk_v,
        preferred_element_type=jnp.float32,
    )

    acc, _, l = _merge_stats(acc_p, m_p, l_p, acc_b, m_b, l_b)
    attn = acc / jnp.maximum(l, 1e-30)[..., None]
    return attn.transpose(0, 3, 1, 2, 4).reshape(A, T, K * G * H)


def _last_rows(x: jax.Array, valid: jax.Array) -> jax.Array:
    """``x[a, valid[a] - 1]``: the position whose logits admission
    samples from (empty rows read position 0). [A, T, E] → [A, E]."""
    return _rows_at(x, jnp.maximum(valid - 1, 0))


def _tail_prefill_core(
    params,
    cfg: ModelConfig,
    prefix_ks: jax.Array,   # [L, K, P, H] cached prompt-prefix keys
    prefix_vs: jax.Array,
    prefix_len: jax.Array,  # scalar int32 — true prefix length (<= P)
    tail_tokens: jax.Array,  # [A, Tt] right-padded prompt tails
    tail_lens: jax.Array,    # [A] true tail lengths (0 = padding row)
    cache_dtype,
):
    """Shared tail-prefill forward for both prefix-cached admission
    paths (dense panel copy and paged page sharing): tail tokens attend
    the cached prefix plus themselves causally. Returns
    ``(last_logits [A, V], ks [L, A, K, Tt, H], vs)`` — only each row's
    last valid position is unembedded (the one admission samples from)."""
    A, Tt = tail_tokens.shape
    positions = prefix_len + jnp.broadcast_to(
        jnp.arange(Tt, dtype=jnp.int32)[None], (A, Tt)
    )
    x = _embed(cfg, params, tail_tokens)
    sin, cos = rope_tables(positions, cfg.head_dim, cfg.rope_theta)
    windows = jnp.asarray(cfg.window_sizes())
    qscale = cfg.query_scale if cfg.query_scale is not None else cfg.head_dim**-0.5
    G = cfg.n_heads // cfg.n_kv_heads

    def layer_fn(carry, scanned):
        x = carry
        lp, window, pk, pv = scanned
        h = rms_norm(x, lp["ln1"]["scale"], cfg.rms_eps, cfg.rms_offset)
        q, k, v = _qkv(cfg, lp["attn"], h, sin, cos)
        qg = q.transpose(0, 2, 1, 3).reshape(
            A, cfg.n_kv_heads, G, Tt, cfg.head_dim
        )
        blk_k = k.transpose(0, 2, 1, 3).astype(cache_dtype)
        blk_v = v.transpose(0, 2, 1, 3).astype(cache_dtype)
        # Per-layer window under lax.cond: ``window`` is a traced scan
        # element, and only one attention variant runs per layer (the
        # jnp.where form computed BOTH every layer — advisor r3).
        if cfg.sliding_window > 0:
            attn = jax.lax.cond(
                window > 0,
                lambda: _tail_prefix_attn(
                    qg, pk, pv, blk_k, blk_v, prefix_len, tail_lens,
                    qscale, cfg.attn_softcap, int(cfg.sliding_window),
                ),
                lambda: _tail_prefix_attn(
                    qg, pk, pv, blk_k, blk_v, prefix_len, tail_lens,
                    qscale, cfg.attn_softcap, 0,
                ),
            )
        else:
            attn = _tail_prefix_attn(
                qg, pk, pv, blk_k, blk_v, prefix_len, tail_lens,
                qscale, cfg.attn_softcap, 0,
            )
        x = _layer_tail(
            cfg, lp, x,
            attn.astype(x.dtype).reshape(A, Tt, cfg.n_heads, cfg.head_dim),
        )
        return x, (blk_k, blk_v)

    x, (ks, vs) = jax.lax.scan(
        layer_fn, x, (params["layers"], windows, prefix_ks, prefix_vs)
    )
    x = _last_rows(x, tail_lens)
    x = rms_norm(x, params["final_norm"]["scale"], cfg.rms_eps, cfg.rms_offset)
    logits = _unembed(cfg, params, x)                    # [A, V] fp32
    return logits, ks, vs


def _tail_prefill_lazy(
    params,
    cfg: ModelConfig,
    gather_layer,            # l -> (pk [K, Pb, H], pv) in compute dtype
    prefix_len: jax.Array,
    tail_tokens: jax.Array,  # [A, Tt]
    tail_lens: jax.Array,    # [A]
    cache_dtype,
):
    """``_tail_prefill_core`` with PER-LAYER prefix gathering (python
    loop, no scan): stacking all L layers' dequantized chain panels
    up front costs ``2·L·K·Pb·H·2`` bytes — 17+ GB for an 8B model at an
    8K prefix, a measured OOM next to the weights. Here each layer
    gathers its own panels transiently (~0.5 GB at 8K) and XLA reuses
    the buffer across layers. Used by the paged admission paths whenever
    the stacked gather would exceed the gather budget."""
    A, Tt = tail_tokens.shape
    positions = prefix_len + jnp.broadcast_to(
        jnp.arange(Tt, dtype=jnp.int32)[None], (A, Tt)
    )
    x = _embed(cfg, params, tail_tokens)
    sin, cos = rope_tables(positions, cfg.head_dim, cfg.rope_theta)
    windows = cfg.window_sizes()
    qscale = cfg.query_scale if cfg.query_scale is not None else cfg.head_dim**-0.5
    G = cfg.n_heads // cfg.n_kv_heads

    ks_l, vs_l = [], []
    for l in range(cfg.n_layers):
        lp = jax.tree.map(lambda a: a[l], params["layers"])
        window = int(windows[l])
        pk, pv = gather_layer(l)
        h = rms_norm(x, lp["ln1"]["scale"], cfg.rms_eps, cfg.rms_offset)
        q, k, v = _qkv(cfg, lp["attn"], h, sin, cos)
        qg = q.transpose(0, 2, 1, 3).reshape(
            A, cfg.n_kv_heads, G, Tt, cfg.head_dim
        )
        blk_k = k.transpose(0, 2, 1, 3).astype(cache_dtype)
        blk_v = v.transpose(0, 2, 1, 3).astype(cache_dtype)
        attn = _tail_prefix_attn(
            qg, pk, pv, blk_k, blk_v, prefix_len, tail_lens,
            qscale, cfg.attn_softcap, window,
        )
        x = _layer_tail(
            cfg, lp, x,
            attn.astype(x.dtype).reshape(A, Tt, cfg.n_heads, cfg.head_dim),
        )
        ks_l.append(blk_k)
        vs_l.append(blk_v)
    x = _last_rows(x, tail_lens)
    x = rms_norm(x, params["final_norm"]["scale"], cfg.rms_eps, cfg.rms_offset)
    logits = _unembed(cfg, params, x)                    # [A, V] fp32
    return logits, jnp.stack(ks_l), jnp.stack(vs_l)


@partial(
    jax.jit,
    static_argnames=("cfg",),
    donate_argnames=("cache", "dstate", "sampling", "history"),
)
def admit_group_prefix(
    params,
    cfg: ModelConfig,
    cache: KVCache,
    dstate: "DecodeState",
    sampling: SamplingState,
    prefix_ks: jax.Array,   # [L, K, P, H] cached prompt-prefix keys
    prefix_vs: jax.Array,
    tail_tokens: jax.Array,  # [A, Tt] right-padded prompt tails
    full_tokens: jax.Array,  # [A, Tf] full prompts (history install)
    meta_i32: jax.Array,     # [ADMIT_I32_ROWS, A] — AI_LEN = tail lens,
                             # AI_PLEN = true prefix length (broadcast)
    meta_f32: jax.Array,     # [ADMIT_F32_ROWS, A]
    json_tables: Optional[Tuple[jax.Array, jax.Array]] = None,
    schema_tables: Optional[Tuple[jax.Array, jax.Array, jax.Array]] = None,
    history: Optional[jax.Array] = None,
):
    """Admission with a cached prefix: copy the prefix K/V into each
    slot, prefill ONLY the tail with prefix-aware attention, sample the
    first token — one fused dispatch, like ``admit_group``. An exact
    repeat admits with a one-token tail: the 2048-position 8B prefill
    (~33 TFLOP, the dominant share of the agent-step wave measured on
    v5e) collapses to a single position."""
    A, Tt = tail_tokens.shape
    _refuse_recurrent(cfg, "a prefix hit (the dense prefix store)")
    (
        slots, temps, topks, topps, seeds, eos, jsonm, budgets, tail_lens,
        schema_ids,
    ) = _unpack_admit_meta(meta_i32, meta_f32, schema_tables)
    prefix_len = meta_i32[AI_PLEN, 0]
    quantized = cache.scales is not None
    cache_dtype = cfg.dtype if quantized else cache.layers[0][0].dtype
    logits, ks, vs = _tail_prefill_core(
        params, cfg, prefix_ks, prefix_vs, prefix_len,
        tail_tokens, tail_lens, cache_dtype,
    )

    # Cache install: prefix panels (shared) + tail (per slot). Padding
    # rows route to row 0's slot and are overwritten by its later write
    # (write_prompts' reversed-dus trick). Quantized caches re-quantize
    # the store entries on the way in — lossless ONLY because the store
    # exports in float32 (a bf16 round would shift the recomputed scale
    # and break hit-path determinism), so quantize from the raw entry,
    # never from a cache_dtype cast.
    live = tail_lens > 0
    safe_slots = jnp.where(live, slots, slots[0])
    plen_start = jnp.clip(prefix_len, 0, cache.max_len - 1)
    new_layers = []
    new_scales = [] if quantized else None
    for l, (k_panel, v_panel) in enumerate(cache.layers):
        pk = prefix_ks[l][None]                         # [1, K, P, H]
        pv = prefix_vs[l][None]
        tk, tv = ks[l], vs[l]                           # [A, K, Tt, H]
        if quantized:
            pk, pk_s = quantize_kv(pk)
            pv, pv_s = quantize_kv(pv)
            tk, tk_s = quantize_kv(tk)
            tv, tv_s = quantize_kv(tv)
            ks_panel, vs_panel = cache.scales[l]
            for a in reversed(range(A)):
                sstart = (safe_slots[a], 0, 0)
                ks_panel = jax.lax.dynamic_update_slice(ks_panel, pk_s, sstart)
                vs_panel = jax.lax.dynamic_update_slice(vs_panel, pv_s, sstart)
                tstart = (safe_slots[a], 0, plen_start)
                ks_panel = jax.lax.dynamic_update_slice(
                    ks_panel, tk_s[a][None], tstart
                )
                vs_panel = jax.lax.dynamic_update_slice(
                    vs_panel, tv_s[a][None], tstart
                )
            new_scales.append((ks_panel, vs_panel))
        else:
            pk = pk.astype(cache_dtype)
            pv = pv.astype(cache_dtype)
            tk = tk.astype(cache_dtype)
            tv = tv.astype(cache_dtype)
        for a in reversed(range(A)):
            start = (safe_slots[a], 0, 0, 0)
            k_panel = jax.lax.dynamic_update_slice(k_panel, pk, start)
            v_panel = jax.lax.dynamic_update_slice(v_panel, pv, start)
            # Scan outputs are already K-major: ks[l][a] is [K, Tt, H].
            tstart = (safe_slots[a], 0, plen_start, 0)
            k_panel = jax.lax.dynamic_update_slice(
                k_panel, tk[a][None], tstart
            )
            v_panel = jax.lax.dynamic_update_slice(
                v_panel, tv[a][None], tstart
            )
        new_layers.append((k_panel, v_panel))
    new_lengths = cache.lengths
    full_lens = jnp.where(live, prefix_len + tail_lens, 0)
    for a in reversed(range(A)):
        new_lengths = jax.lax.dynamic_update_slice(
            new_lengths, full_lens[a][None], (safe_slots[a],)
        )
    cache = cache._replace(
        layers=tuple(new_layers), lengths=new_lengths,
        scales=tuple(new_scales) if new_scales is not None else None,
    )

    sampling = admit_sampling(
        sampling, slots, temps, topks, topps, seeds, eos, jsonm,
        schema_ids=schema_ids,
    )
    first, sampling = sample_prefill_tokens(
        logits, slots, sampling, remaining=budgets + 1,
        json_tables=json_tables, schema_tables=schema_tables,
    )
    dstate = admit_decode(dstate, slots, first, budgets, live)
    if history is not None:
        history = install_history(
            history, slots, full_tokens, full_lens, first
        )
    return cache, dstate, sampling, first, history


@partial(
    jax.jit,
    static_argnames=("cfg", "n_prefix_bucket"),
    donate_argnames=("cache", "dstate", "sampling", "history"),
)
def admit_group_prefix_paged(
    params,
    cfg: ModelConfig,
    cache: PagedKVCache,
    dstate: "DecodeState",
    sampling: SamplingState,
    prefix_pages: jax.Array,  # [n_prefix_bucket] int32 — shared chain pages
                              # in order, sentinel-padded past the true count
    tail_tokens: jax.Array,   # [A, Tt] right-padded prompt tails
    full_tokens: jax.Array,   # [A, Tf] full prompts (history install)
    page_rows: jax.Array,     # [A, max_pages] full block tables (shared
                              # prefix pages at the head, private after)
    meta_i32: jax.Array,      # [ADMIT_I32_ROWS, A] — AI_LEN = tail lens,
                              # AI_PLEN = true prefix length (page-aligned:
                              # chain pages are always full)
    meta_f32: jax.Array,      # [ADMIT_F32_ROWS, A]
    n_prefix_bucket: int = 1,
    json_tables: Optional[Tuple[jax.Array, jax.Array]] = None,
    schema_tables: Optional[Tuple[jax.Array, jax.Array, jax.Array]] = None,
    history: Optional[jax.Array] = None,
):
    """Block-granular prefix-cached admission on the paged pool
    (``engine/page_prefix.py``). Unlike the dense variant, the prefix is
    **not copied anywhere**: the shared pages are already mapped into
    each slot's block table by the host allocator — this dispatch only
    gathers them read-only for the tail's prefix attention, prefills the
    tail, and scatters the tail K/V into the slots' private pages (the
    shared pages are immutable: decode writes start at ``prompt_len``,
    past every fully-covered block)."""
    (
        slots, temps, topks, topps, seeds, eos, jsonm, budgets, tail_lens,
        schema_ids,
    ) = _unpack_admit_meta(meta_i32, meta_f32, schema_tables)
    prefix_len = meta_i32[AI_PLEN, 0]
    P = cache.page_size
    K = cache.n_kv_heads
    H = cache.head_dim
    Pb = n_prefix_bucket * P
    # The shared chain is read as prefix panels (sentinel-padded pages
    # gather scratch garbage — masked by ``col < prefix_len`` in the
    # tail attention). int8 pools dequantize on the way out; the pages
    # themselves stay quantized and untouched. Large chains gather per
    # layer instead of stacking (see _chain_tail_prefill).
    cache_dtype = (
        cfg.dtype if cache.scales is not None else cache.layers[0][0].dtype
    )
    if cfg.layer_kinds:
        # Only ever a slot's OWN chain (the last segment of a prompt
        # admitted in segments): a shared prefix is KV alone, and the
        # batcher takes no prefix hit for a model with recurrent state.
        _refuse_recurrent(cfg, "speculative decoding", history is not None)
        logits, ks, vs, cache = _tail_prefill_kinds(
            params, cfg, cache, prefix_pages, prefix_len, tail_tokens,
            tail_lens, cache_dtype, slots,
        )
    else:
        logits, ks, vs = _chain_tail_prefill(
            params, cfg, cache, prefix_pages, prefix_len, tail_tokens,
            tail_lens, cache_dtype,
        )

    # Tail install: position t of the tail lives at absolute position
    # prefix_len + t — write through the slot's own table with that
    # offset (prefix_len is page-aligned, so only private blocks past
    # the shared chain are ever touched).
    ks_w = ks.transpose(0, 1, 3, 2, 4)  # [L, A, Tt, K, H]
    vs_w = vs.transpose(0, 1, 3, 2, 4)
    cache = write_prompts_paged(
        cache, page_rows, ks_w, vs_w, tail_lens, pos_offset=prefix_len
    )
    live = tail_lens > 0
    cache = install_lengths(
        cache, slots, jnp.where(live, prefix_len + tail_lens, 0)
    )

    sampling = admit_sampling(
        sampling, slots, temps, topks, topps, seeds, eos, jsonm,
        schema_ids=schema_ids,
    )
    first, sampling = sample_prefill_tokens(
        logits, slots, sampling, remaining=budgets + 1,
        json_tables=json_tables, schema_tables=schema_tables,
    )
    dstate = admit_decode(dstate, slots, first, budgets, live)
    if history is not None:
        history = install_history(
            history, slots, full_tokens,
            jnp.where(live, prefix_len + tail_lens, 0), first,
        )
    return cache, dstate, sampling, first, history


@partial(jax.jit, static_argnames=("cfg",), donate_argnames=("cache",))
def extend_prompt_paged(
    params,
    cfg: ModelConfig,
    cache: PagedKVCache,
    prefix_pages: jax.Array,  # [n_prefix_bucket] int32 — pages already
                              # written for this slot, sentinel-padded
    prefix_len: jax.Array,    # scalar int32 — page-aligned tokens written
    seg_tokens: jax.Array,    # [1, Ts] right-padded prompt segment
    seg_lens: jax.Array,      # [1] true segment length
    page_rows: jax.Array,     # [1, max_pages] the slot's block table
    slot: Optional[jax.Array] = None,  # [1] int32 — the slot, for a model
                              # whose state pool the segment goes on from
):
    """One chunked-prefill segment of a long prompt (VERDICT r5 #6):
    prefill ``seg_tokens`` attending to the KV already written for this
    slot, scatter its K/V into the slot's private pages — and nothing
    else. No sampling, no decode install, no length install: the slot
    stays decode-inactive until the FINAL segment admits through
    ``admit_group_prefix_paged``. The batcher dispatches one segment per
    device-loop cycle, so live slots' decode chunks interleave instead
    of stalling behind a monolithic multi-thousand-token prefill."""
    cache_dtype = (
        cfg.dtype if cache.scales is not None else cache.layers[0][0].dtype
    )
    if cfg.layer_kinds:
        assert slot is not None, "a segment of such a model names its slot"
        _logits, ks, vs, cache = _tail_prefill_kinds(
            params, cfg, cache, prefix_pages, prefix_len, seg_tokens,
            seg_lens, cache_dtype, slot,
        )
    else:
        _logits, ks, vs = _chain_tail_prefill(
            params, cfg, cache, prefix_pages, prefix_len, seg_tokens,
            seg_lens, cache_dtype,
        )
    ks_w = ks.transpose(0, 1, 3, 2, 4)  # [L, 1, Ts, K, H]
    vs_w = vs.transpose(0, 1, 3, 2, 4)
    return write_prompts_paged(
        cache, page_rows, ks_w, vs_w, seg_lens, pos_offset=prefix_len
    )


def _chain_gatherer(cfg, cache, prefix_pages):
    """``l -> (pk [K, Pb, H], pv)``: layer ``l`` of the cache's chain pages
    as prefix panels in compute dtype."""
    K = cache.n_kv_heads
    Pb = prefix_pages.shape[0] * cache.page_size

    def _chain_gather(a):
        return a[:, prefix_pages].reshape((K, Pb) + a.shape[3:])

    def gather_layer(l):
        k_, v_, sc = _bounded_panels(cache, l, _chain_gather)
        return _dequant_pair(k_, v_, sc, cfg.dtype)

    return gather_layer


def _tail_prefill_kinds(
    params, cfg, cache, prefix_pages, prefix_len, tail_tokens, tail_lens,
    cache_dtype, rows,
):
    """The tail prefill of a stack of unlike layers (``cfg.layer_kinds``)
    against the slot's OWN page chain, which is how a prompt admitted in
    segments goes on: attention reads the chain as ``_tail_prefill_lazy``
    does, and the state-space layers go on from the conv and state-space
    state the pool holds for ``rows`` (zeros when nothing came before).
    Returns ``(last_logits, ks, vs, cache)`` with the pool's rows
    rewritten."""
    A, Tt = tail_tokens.shape
    K, H = cfg.n_kv_heads, cfg.head_dim
    G = cfg.n_heads // K
    qscale = cfg.query_scale if cfg.query_scale is not None else H**-0.5
    gather_layer = _chain_gatherer(cfg, cache, prefix_pages)
    real = jnp.arange(Tt)[None, :] < tail_lens[:, None]

    def attend(l, p, h):
        pk, pv = gather_layer(l)
        q, k, v = _qkv(cfg, p, h, None, None)
        qg = q.transpose(0, 2, 1, 3).reshape(A, K, G, Tt, H)
        blk_k = k.transpose(0, 2, 1, 3).astype(cache_dtype)
        blk_v = v.transpose(0, 2, 1, 3).astype(cache_dtype)
        attn = _tail_prefix_attn(
            qg, pk, pv, blk_k, blk_v, prefix_len, tail_lens, qscale,
            cfg.attn_softcap, 0,
        )
        return attn.astype(h.dtype).reshape(A, Tt, cfg.n_heads, H), (blk_k, blk_v)

    conv0, ssm0 = cache.state.rows(rows, prefix_len == 0)
    x = _embed(cfg, params, tail_tokens)
    x, kv, conv, ssm, routed = walk_layers(
        cfg, params, x, attend, conv0, ssm0, real, lens=tail_lens
    )
    x = _last_rows(x, tail_lens)
    x = rms_norm(x, params["final_norm"]["scale"], cfg.rms_eps, cfg.rms_offset)
    logits = _unembed(cfg, params, x)
    cache = cache._replace(state=cache.state.write(rows, conv, ssm, routed))
    return logits, jnp.stack([k for k, _ in kv]), jnp.stack([v for _, v in kv]), cache


def _chain_tail_prefill(
    params, cfg, cache, prefix_pages, prefix_len, tail_tokens, tail_lens,
    cache_dtype,
):
    """Tail prefill against a page chain, choosing the gather strategy by
    HBM cost: small chains stack all layers' dequantized panels up front
    (one scanned forward — the fast, proven path); chains whose stacked
    panels would exceed the gather budget (PILOTTAI_GATHER_BUDGET, the
    same knob the decode chunk uses) gather per layer instead
    (``_tail_prefill_lazy``) — an 8K chain on an 8B model is 17+ GB
    stacked, a measured OOM."""
    import os as _os

    K = cache.n_kv_heads
    Pb = prefix_pages.shape[0] * cache.page_size
    gather_layer = _chain_gatherer(cfg, cache, prefix_pages)
    budget = int(_os.environ.get("PILOTTAI_GATHER_BUDGET", 5 * 1024**3))
    stacked_bytes = (
        2 * cfg.n_layers * K * Pb * cache.head_dim
        * jnp.dtype(cfg.dtype).itemsize
    )
    if stacked_bytes > budget:
        return _tail_prefill_lazy(
            params, cfg, gather_layer, prefix_len, tail_tokens, tail_lens,
            cache_dtype,
        )
    panels = [gather_layer(l) for l in range(cfg.n_layers)]
    pks = jnp.stack([p[0] for p in panels])
    pvs = jnp.stack([p[1] for p in panels])
    return _tail_prefill_core(
        params, cfg, pks, pvs, prefix_len, tail_tokens, tail_lens,
        cache_dtype,
    )


@partial(jax.jit, static_argnames=("p_bucket", "dtype"))
def export_prefix(cache: KVCache, slot, p_bucket: int, dtype=None):
    """Read one slot's first ``p_bucket`` cache rows out as stacked
    [L, K, p_bucket, H] arrays (the prefix-store entry payload). Runs
    right after the admission dispatch, before any decode chunk touches
    the slot, so the rows hold exactly the prompt's K/V. int8 caches
    export DEQUANTIZED panels: admit_group_prefix re-quantizes on
    install, which round-trips losslessly (same scales recomputed)."""
    def grab(panel):
        K, _, H = panel.shape[1:]
        return jax.lax.dynamic_slice(
            panel, (slot, 0, 0, 0), (1, K, p_bucket, H)
        )[0]

    def grab_scale(panel):
        K = panel.shape[1]
        return jax.lax.dynamic_slice(
            panel, (slot, 0, 0), (1, K, p_bucket)
        )[0]

    dt = dtype if dtype is not None else jnp.float32
    ks_l, vs_l = [], []
    for l, (k, v) in enumerate(cache.layers):
        gk, gv = grab(k), grab(v)
        if cache.scales is not None:
            gk = dequantize_kv(gk, grab_scale(cache.scales[l][0]), dt)
            gv = dequantize_kv(gv, grab_scale(cache.scales[l][1]), dt)
        ks_l.append(gk)
        vs_l.append(gv)
    return jnp.stack(ks_l), jnp.stack(vs_l)


def install_history(
    history: jax.Array,   # [B, S]
    slots: jax.Array,     # [A] (OOB rows dropped)
    tokens: jax.Array,    # [A, T] right-padded prompts
    lens: jax.Array,      # [A] true lengths
    first: jax.Array,     # [A] prefill-sampled first tokens
) -> jax.Array:
    """Admission-side history install: prompt ids at positions [0, len)
    and the first generated token at position len. Plain function — runs
    inside admit_group's single fused dispatch."""
    B, S = history.shape
    A, T = tokens.shape
    live = lens > 0
    rows = jnp.where(live, slots, B)
    col = jnp.arange(T)[None, :]
    # Wipe the row, then lay down the prompt and the first token.
    history = history.at[rows].set(0, mode="drop")
    wcol = jnp.where(col < lens[:, None], col, S)
    history = history.at[rows[:, None], wcol].set(tokens, mode="drop")
    history = history.at[
        rows, jnp.minimum(lens, S - 1)
    ].set(first, mode="drop")
    return history


@partial(
    jax.jit,
    static_argnames=("cfg", "use_flash", "flash_mesh"),
    donate_argnames=("cache", "dstate", "sampling", "history"),
)
def admit_group(
    params,
    cfg: ModelConfig,
    cache: KVCache,
    dstate: "DecodeState",
    sampling: SamplingState,
    tokens: jax.Array,     # [A, T] right-padded prompt ids
    meta_i32: jax.Array,   # [ADMIT_I32_ROWS, A] packed int metadata
    meta_f32: jax.Array,   # [ADMIT_F32_ROWS, A] packed float metadata
    use_flash: bool = True,
    flash_mesh: Any = None,
    page_rows: Optional[jax.Array] = None,  # [A, max_pages] — paged cache
    json_tables: Optional[Tuple[jax.Array, jax.Array]] = None,
    schema_tables: Optional[Tuple[jax.Array, jax.Array, jax.Array]] = None,
    history: Optional[jax.Array] = None,    # [B, S] — speculative decode
):
    """The whole admission path — prefill forward, batched cache write,
    sampler install, on-device first-token sample, decode-state install —
    as ONE device dispatch. Each dispatch has a fixed host cost; five
    per admission group was a measurable slice of the p50 budget. The
    per-row scalars arrive packed in two staging buffers (one H2D
    transfer each — ``pack_admit_meta``); positions are derived on
    device, so a full-prefill admission moves exactly three host arrays.

    Returns (cache, dstate, sampling, first_tokens [A])."""
    A, T = tokens.shape
    (
        slots, temps, topks, topps, seeds, eos, jsonm, budgets, lens,
        schema_ids,
    ) = _unpack_admit_meta(meta_i32, meta_f32, schema_tables)
    if cfg.layer_kinds:
        # A stack of unlike layers: KV for its attention layers only, and
        # each row's conv and state-space state after its true length,
        # written over whatever the slot held.
        _refuse_recurrent(cfg, "speculative decoding", history is not None)
        logits, ks, vs, conv, ssm, routed = forward_prefill_hybrid(
            params, cfg, tokens, lens, use_flash=use_flash,
            logit_positions=jnp.maximum(lens - 1, 0),
        )
        cache = cache._replace(
            state=cache.state.write(slots, conv, ssm, routed)
        )
    else:
        positions = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32)[None], (A, T))
        logits, ks, vs = forward_prefill(
            params, cfg, tokens, positions, lens,
            use_flash=use_flash, flash_mesh=flash_mesh,
            logit_positions=jnp.maximum(lens - 1, 0),
        )
    if isinstance(cache, PagedKVCache):
        assert page_rows is not None, "paged admission needs page rows"
        cache = write_prompts_paged(cache, page_rows, ks, vs, lens)
        cache = install_lengths(cache, slots, lens)
    else:
        cache = write_prompts(cache, slots, ks, vs, lens)
    sampling = admit_sampling(
        sampling, slots, temps, topks, topps, seeds, eos, jsonm,
        schema_ids=schema_ids,
    )
    first, sampling = sample_prefill_tokens(
        logits, slots, sampling, remaining=budgets + 1,
        json_tables=json_tables, schema_tables=schema_tables,
    )
    dstate = admit_decode(dstate, slots, first, budgets, lens > 0)
    if history is not None:
        history = install_history(history, slots, tokens, lens, first)
    return cache, dstate, sampling, first, history


@partial(jax.jit, donate_argnames=("sampling",))
def sample_prefill_tokens(
    last: jax.Array,      # [A, V] fp32 — logits at each prompt's last
                          # position (the only prefill row ever read)
    slots: jax.Array,     # [A] slot each prompt was admitted into
    sampling: SamplingState,
    remaining: Optional[jax.Array] = None,  # [A] total generation budget
    json_tables: Optional[Tuple[jax.Array, jax.Array]] = None,
    schema_tables: Optional[Tuple[jax.Array, jax.Array, jax.Array]] = None,
) -> Tuple[jax.Array, SamplingState]:
    """Sample each admitted prompt's first generated token on device,
    using (and advancing) the slot's sampling params — one sampler for
    the first token and every later one."""
    sub = jax.tree.map(lambda a: a[slots], sampling)
    tokens, sub = sample_core(
        last, sub, json_remaining=remaining, json_token_tables=json_tables,
        json_schema_tables=schema_tables,
    )
    # Write back everything the sampler advanced: the PRNG keys and the
    # JSON automaton coords (the first token is the automaton's first
    # transition).
    return tokens, sampling._replace(
        key=sampling.key.at[slots].set(sub.key, mode="drop"),
        json_state=sampling.json_state.at[slots].set(sub.json_state, mode="drop"),
        json_stack=sampling.json_stack.at[slots].set(sub.json_stack, mode="drop"),
        json_depth=sampling.json_depth.at[slots].set(sub.json_depth, mode="drop"),
    )
