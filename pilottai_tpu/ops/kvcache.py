"""Slot-based KV cache for continuous batching.

Layout: one ``(k, v)`` pair per layer, each ``[B, K, S, H]`` — B serving
*slots*, K kv-heads, S max context, H head dim. Two deliberate choices:

* **K-major panels.** Each (slot, kv-head) owns a contiguous ``[S, H]``
  region, so the decode-attention kernel's S-reduction streams HBM
  sequentially instead of striding across heads (the transposed layout
  measured ~5x slower cache reads on v5e).
* **Per-layer arrays, not one stacked ``[L, ...]``.** The decode chunk
  unrolls layers and feeds each layer's panels to a Pallas call; separate
  arrays mean the operands are the buffers themselves — a stacked array
  would force a per-layer dynamic-slice copy of the whole layer cache in
  front of every custom call.

Shapes are static (jit-stable). ``lengths[b]`` counts valid entries; the
stale bytes past it are masked at attention time, so freeing a slot is a
single scalar write. Admission/eviction happen on the host between device
chunks; the device only ever sees full, fixed-shape arrays.

New TPU-native surface (the reference has no KV anything). This dense
cache is the default for short contexts; long ragged contexts use the
paged (block-table) cache in ``ops/paged.py`` with the Pallas kernel in
``ops/pallas/paged_attention.py`` (``LLMConfig.engine_paged_kv``).
"""

from __future__ import annotations

from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp


def quantize_kv(x: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """Symmetric per-token-per-head int8: ``x[..., H] -> (q int8[..., H],
    scale f32[...])``. Round-trips losslessly through dequantize →
    requantize (the recomputed scale is bit-identical), which is what
    lets the prefix store hand full-precision panels around while the
    resident cache stays int8."""
    s = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1) / 127.0
    s = jnp.maximum(s, 1e-8)
    q = jnp.clip(
        jnp.round(x.astype(jnp.float32) / s[..., None]), -127, 127
    ).astype(jnp.int8)
    return q, s


def dequantize_kv(q: jax.Array, scale: jax.Array, dtype) -> jax.Array:
    """Inverse of :func:`quantize_kv`; XLA fuses the broadcast multiply
    into the consuming attention contraction, so the HBM read stays
    int8-sized."""
    return (q.astype(jnp.float32) * scale[..., None].astype(jnp.float32)).astype(dtype)


def write_rows(store: jax.Array, idx: jax.Array, rows: jax.Array) -> jax.Array:
    """Write ``rows`` into the donated KV ``store`` in place: THE write of
    every chunk end and every paged admission, for panels, page pools and
    their scale pools alike.

    ``store`` is ``[b.., i.., w..]``, ``idx`` ``[b.., R, n_i]`` int32 and
    ``rows`` ``[b.., R, w..]``: row ``r`` of batch ``b..`` lands at
    ``store[b.., idx[b.., r, 0], .., idx[b.., r, n_i - 1]]``; a row whose
    index lies outside ``store`` is dropped. The leading dimensions are
    batching dimensions of the scatter (kv-heads of a pool; slots and
    kv-heads of a dense panel) and the indexed ones follow them, so no
    compiler has to move a dimension to reach a row: the TPU's folds
    batch and index into one row number and updates the buffer it was
    given, the CPU's scatters in place, and the partitioner of a serving
    mesh hands each shard its own heads' rows with no collective.

    What this replaced: ``pool.at[:, pages, off].set(...)`` (and the dense
    ``k.at[slot, :, pos]``), an advanced-index scatter whose indexed
    dimensions did not lead. XLA moves those to the front, so the whole
    pool was transposed, scattered into and copied back on every
    dispatch: ``copy_s8_8_193_128_128`` 0.638 s and ``copy_f32_8_193_128``
    0.073 s of a 3 s slice, 12 ms of every 40 ms decode step at 7B
    (ledger, PR 29, ``mistral-7b.agent-loop``). A ``dynamic_update_slice``
    a row is in place too but is 1,536 operations a step there, and a
    Pallas DMA of one row is refused for int8 and bfloat16, whose rows
    share a 32-bit sublane word with their neighbours (PERF.md §6 PR 30).
    """
    n_batch = idx.ndim - 2
    n_idx = idx.shape[-1]
    return jax.lax.scatter(
        store, idx, rows.astype(store.dtype),
        jax.lax.ScatterDimensionNumbers(
            update_window_dims=tuple(range(n_batch + 1, rows.ndim)),
            inserted_window_dims=tuple(range(n_batch, n_batch + n_idx)),
            scatter_dims_to_operand_dims=tuple(range(n_batch, n_batch + n_idx)),
            operand_batching_dims=tuple(range(n_batch)),
            scatter_indices_batching_dims=tuple(range(n_batch)),
        ),
        mode=jax.lax.GatherScatterMode.FILL_OR_DROP,
    )


def write_layers(cache, idx: jax.Array, new_kv) -> dict:
    """``write_rows`` over every layer of ``cache`` (dense or paged):
    ``new_kv`` yields each layer's ``(k, v)`` rows in compute precision,
    quantized here when the store is int8 (same ``quantize_kv`` on the same
    values wherever they land). Returns the fields to ``_replace``."""
    layers = []
    scales = [] if cache.scales is not None else None
    for li, ((k, v), (k_new, v_new)) in enumerate(zip(cache.layers, new_kv)):
        if scales is not None:
            k_new, ksc = quantize_kv(k_new)
            v_new, vsc = quantize_kv(v_new)
            ks_p, vs_p = cache.scales[li]
            scales.append(
                (write_rows(ks_p, idx, ksc), write_rows(vs_p, idx, vsc))
            )
        layers.append((write_rows(k, idx, k_new), write_rows(v, idx, v_new)))
    return dict(
        layers=tuple(layers),
        scales=tuple(scales) if scales is not None else None,
    )


class StatePool(NamedTuple):
    """Per-slot state of the layers that keep no KV (``ModelConfig.
    layer_kinds``' ``M`` layers, ``models/ssm.py``), beside the KV of the
    attention layers in the same cache object: the cache is what every
    admission and decode dispatch donates and gets back, so the pool rides
    with it and has one owner.

    An admission OVERWRITES its slot's rows (a fresh prompt starts from
    zeros inside the program, never from what the slot held). A decode
    step reads and writes the state-space rows of the live slots alone, one
    row at a time in place on the pool the decode loop carries
    (``models/ssm.py:mamba_step``); a row that is not live is neither read
    nor written, and its conv rows stand still. So a released slot needs
    no clearing.

    ``routed`` is no state of a slot: two running counts, summed on the
    device by the expert layers (token-expert pairs routed; those that
    landed on experts held here), wrapping at 2**32; the batcher reads
    their differences where it counts decode steps."""

    conv: Tuple[jax.Array, ...]   # per M layer [B, conv - 1, channels]
    ssm: Tuple[jax.Array, ...]    # per M layer [B, heads, head_dim, state] f32
    routed: jax.Array             # [2] uint32

    @classmethod
    def create(cls, cfg, n_slots: int, dtype=jnp.bfloat16) -> Optional["StatePool"]:
        """The pool ``cfg`` needs; None for a model whose layers all keep
        KV and route nothing to count."""
        if not cfg.layer_kinds:
            return None
        n = sum(k == "M" for k in cfg.layer_kinds)
        return cls(
            conv=tuple(
                jnp.zeros((n_slots, cfg.ssm_conv - 1, cfg.ssm_conv_dim), dtype)
                for _ in range(n)
            ),
            ssm=tuple(
                jnp.zeros(
                    (n_slots, cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state),
                    jnp.float32,
                )
                for _ in range(n)
            ),
            routed=jnp.zeros((2,), jnp.uint32),
        )

    def rows(self, slots: jax.Array, fresh: jax.Array):
        """``(conv, ssm)`` of ``slots`` (out-of-range rows read row 0),
        zeros where ``fresh``: a prompt's first tokens start from nothing,
        whatever the slot's last occupant left."""
        def take(a):
            got = a[jnp.clip(slots, 0, a.shape[0] - 1)]
            keep = ~jnp.broadcast_to(fresh, slots.shape)
            return jnp.where(keep.reshape((-1,) + (1,) * (a.ndim - 1)), got, 0)

        return tuple(take(a) for a in self.conv), tuple(take(a) for a in self.ssm)

    def write(self, slots: jax.Array, conv, ssm, routed: jax.Array) -> "StatePool":
        """Overwrite the rows of ``slots`` (out-of-range rows are dropped)
        and add ``routed`` to the running counts."""
        return StatePool(
            conv=tuple(
                a.at[slots].set(c.astype(a.dtype), mode="drop")
                for a, c in zip(self.conv, conv)
            ),
            ssm=tuple(
                a.at[slots].set(s, mode="drop") for a, s in zip(self.ssm, ssm)
            ),
            routed=self.routed + routed,
        )


class KVCache(NamedTuple):
    layers: Tuple[Tuple[jax.Array, jax.Array], ...]  # per-layer (k, v) [B, K, S, H]
    lengths: jax.Array                               # [B] int32 — valid entries
    # Per-layer (k_scale, v_scale) [B, K, S] when the panels are int8
    # (symmetric per-token-per-head); None for full-precision panels.
    # Decode is HBM-bound and the cache is ~1/3 of its traffic at short
    # contexts — int8 halves that for ~1e-3 relative attention error.
    scales: Optional[Tuple[Tuple[jax.Array, jax.Array], ...]] = None
    # State of the layers that keep no KV; ``layers`` then holds the
    # attention layers only. None for a model whose layers all keep KV.
    state: Optional[StatePool] = None

    @property
    def n_layers(self) -> int:
        return len(self.layers)

    @property
    def n_slots(self) -> int:
        return self.layers[0][0].shape[0]

    @property
    def max_len(self) -> int:
        return self.layers[0][0].shape[2]

    @property
    def n_kv_heads(self) -> int:
        return self.layers[0][0].shape[1]

    @property
    def head_dim(self) -> int:
        return self.layers[0][0].shape[3]

    @classmethod
    def create(
        cls,
        n_layers: int,
        n_slots: int,
        max_len: int,
        n_kv_heads: int,
        head_dim: int,
        dtype=jnp.bfloat16,
        quantized: bool = False,
        state: Optional[StatePool] = None,
    ) -> "KVCache":
        shape = (n_slots, n_kv_heads, max_len, head_dim)
        store_dtype = jnp.int8 if quantized else dtype
        layers = tuple(
            (jnp.zeros(shape, dtype=store_dtype),
             jnp.zeros(shape, dtype=store_dtype))
            for _ in range(n_layers)
        )
        scales = (
            tuple(
                (jnp.zeros(shape[:-1], jnp.float32),
                 jnp.zeros(shape[:-1], jnp.float32))
                for _ in range(n_layers)
            )
            if quantized else None
        )
        return cls(
            layers=layers, lengths=jnp.zeros((n_slots,), dtype=jnp.int32),
            scales=scales, state=state,
        )


@jax.named_scope("kv_write")
def write_prompts(
    cache: KVCache,
    slots: jax.Array,      # [A] int32 — target slot per admitted prompt
    ks: jax.Array,         # [L, A, T, K, H] — prefill K for every layer
    vs: jax.Array,         # [L, A, T, K, H]
    lengths: jax.Array,    # [A] int32 — true (unpadded) prompt lengths;
                           # <= 0 marks a padding row (dropped)
) -> KVCache:
    """Insert a batch of freshly prefilled prompts (host-driven admission).

    T may be padded; entries beyond ``lengths[a]`` are zeros and masked out
    at attention time. Padding rows (``lengths[a] <= 0``) are routed to an
    out-of-bounds slot index so XLA scatter semantics drop them.
    """
    A = ks.shape[1]
    # One dynamic_update_slice a row: a whole [K, T, H] panel head is a
    # slice, XLA aliases it in place on the donated cache, and A rows a
    # layer are few. (``slots`` index the LEADING dimension, so a scatter
    # here would be in place as well; what copied the whole store, 12 ms of
    # a 40 ms decode step at 7B by the ledger's PR 29 line, was a scatter
    # whose indexed dimensions did not lead: ``write_rows``.) dus clamps
    # out-of-range starts instead of dropping, so padding rows are routed
    # to the *first* row's slot and written before it (reversed order):
    # row 0 is always a live request, and its later write overwrites the
    # padding garbage.
    safe_slots = jnp.where(lengths > 0, slots, slots[0])
    new_layers = []
    new_scales = [] if cache.scales is not None else None
    for layer_idx, (k, v) in enumerate(cache.layers):
        # [A, T, K, H] -> [A, K, T, H] to match the K-major panels.
        k_new = jnp.swapaxes(ks[layer_idx], 1, 2)
        v_new = jnp.swapaxes(vs[layer_idx], 1, 2)
        if cache.scales is not None:
            k_new, ksc = quantize_kv(k_new)
            v_new, vsc = quantize_kv(v_new)
            ks_p, vs_p = cache.scales[layer_idx]
            for a in reversed(range(A)):
                sstart = (safe_slots[a], 0, 0)
                ks_p = jax.lax.dynamic_update_slice(ks_p, ksc[a][None], sstart)
                vs_p = jax.lax.dynamic_update_slice(vs_p, vsc[a][None], sstart)
            new_scales.append((ks_p, vs_p))
        else:
            k_new = k_new.astype(k.dtype)
            v_new = v_new.astype(v.dtype)
        for a in reversed(range(A)):
            start = (safe_slots[a], 0, 0, 0)
            k = jax.lax.dynamic_update_slice(k, k_new[a][None], start)
            v = jax.lax.dynamic_update_slice(v, v_new[a][None], start)
        new_layers.append((k, v))
    new_lengths = cache.lengths
    for a in reversed(range(A)):
        new_lengths = jax.lax.dynamic_update_slice(
            new_lengths, jnp.maximum(lengths[a], 0)[None], (safe_slots[a],)
        )
    return cache._replace(
        layers=tuple(new_layers), lengths=new_lengths,
        scales=tuple(new_scales) if new_scales is not None else None,
    )


@jax.named_scope("kv_write")
def write_chunk_rows(
    cache: KVCache,
    ring_ks,               # list per layer: [B, K, n, H] chunk ring
    ring_vs,
    start: jax.Array,      # [B] int32 — slot length at chunk start
    accepted: jax.Array,   # [B] int32 — rows actually generated this chunk
) -> KVCache:
    """Write one decode chunk's ring buffers into the panels, in place
    (``write_rows``: slots and kv-heads batch, the position is the index).

    Row j of slot b lands at position start[b] + j when j < accepted[b];
    rejected rows (beyond EOS/budget) are routed past S and dropped, so
    the panel stays as it was there.
    """
    S = cache.max_len
    B, K, n, _ = ring_ks[0].shape
    j = jnp.arange(n)[None, :]                               # [1, n]
    pos = jnp.where(j < accepted[:, None], start[:, None] + j, S)  # [B, n]
    idx = jnp.broadcast_to(pos[:, None, :, None], (B, K, n, 1))
    return cache._replace(
        lengths=jnp.minimum(cache.lengths + accepted, S),
        **write_layers(cache, idx, zip(ring_ks, ring_vs)),
    )


def free_slots(cache: KVCache, slots: jax.Array) -> KVCache:
    """Mark slots empty (host calls when sequences finish). The stale K/V
    bytes stay — masked out by lengths — so no panel writes needed."""
    return cache._replace(
        lengths=cache.lengths.at[slots].set(0, mode="drop")
    )
