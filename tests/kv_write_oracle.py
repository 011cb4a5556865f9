"""The KV writes as they stood before PR 30, kept as the tests' oracle.

Each is the advanced-index scatter the step programs used: right in what
it writes, wrong in what it costs (its indexed dimensions do not lead, so
XLA transposed and copied the whole store around it). The in-place writes
in ``ops/paged.py`` and ``ops/kvcache.py`` are held to these, bit for bit.
"""

import jax.numpy as jnp

from pilottai_tpu.ops.kvcache import quantize_kv


# ``accepted`` of four slots after a chunk of n rows, by case.
ACCEPTED = {
    "none": lambda n: [0, 0, 0, 0],
    "partial": lambda n: [max(n // 2, 1), 1, 0, n - 1],
    "full": lambda n: [n, n, n, n],
}


def write_prompts_paged(cache, table, ks, vs, lengths, pos_offset=None):
    L, A, T, K, H = ks.shape
    P = cache.page_size
    n_blocks = -(-T // P)
    Tp = n_blocks * P
    pos = jnp.arange(Tp)
    live = pos[None, :] < lengths[:, None]
    if pos_offset is not None:
        pos = pos + pos_offset
    max_pos = table.shape[1] * P - 1
    blk = jnp.minimum(pos, max_pos) // P
    pages = jnp.take_along_axis(
        table, jnp.broadcast_to(blk[None, :], (A, Tp)), axis=1
    )
    pages = jnp.where(live, pages, cache.num_pages - 1)
    off = jnp.broadcast_to((pos % P)[None, :], (A, Tp))
    pages_f = pages.reshape(-1)
    off_f = off.reshape(-1)

    new_layers = []
    new_scales = [] if cache.scales is not None else None
    for li, (kp, vp) in enumerate(cache.layers):
        k_new = ks[li]
        v_new = vs[li]
        if Tp != T:
            pad = ((0, 0), (0, Tp - T), (0, 0), (0, 0))
            k_new = jnp.pad(k_new, pad)
            v_new = jnp.pad(v_new, pad)
        k_new = k_new.transpose(2, 0, 1, 3).reshape(K, A * Tp, H)
        v_new = v_new.transpose(2, 0, 1, 3).reshape(K, A * Tp, H)
        if cache.scales is not None:
            k_new, ksc = quantize_kv(k_new)
            v_new, vsc = quantize_kv(v_new)
            ks_p, vs_p = cache.scales[li]
            ks_p = ks_p.at[:, pages_f, off_f].set(ksc, mode="drop")
            vs_p = vs_p.at[:, pages_f, off_f].set(vsc, mode="drop")
            new_scales.append((ks_p, vs_p))
        kp = kp.at[:, pages_f, off_f].set(k_new.astype(kp.dtype), mode="drop")
        vp = vp.at[:, pages_f, off_f].set(v_new.astype(vp.dtype), mode="drop")
        new_layers.append((kp, vp))
    return cache._replace(
        layers=tuple(new_layers),
        scales=tuple(new_scales) if new_scales is not None else None,
    )


def write_chunk_rows_paged(cache, table, ring_ks, ring_vs, start, accepted):
    B = cache.n_slots
    P = cache.page_size
    n = ring_ks[0].shape[2]
    j = jnp.arange(n)[None, :]
    pos = start[:, None] + j
    max_pos = table.shape[1] * P - 1
    blk = jnp.minimum(pos, max_pos) // P
    pages = jnp.take_along_axis(table, blk, axis=1)
    pages = jnp.where(j < accepted[:, None], pages, cache.num_pages - 1)
    pages_f = pages.reshape(-1)
    off_f = (pos % P).reshape(-1)

    new_layers = []
    new_scales = [] if cache.scales is not None else None
    for li, ((kp, vp), rk, rv) in enumerate(
        zip(cache.layers, ring_ks, ring_vs)
    ):
        k_new = rk.transpose(1, 0, 2, 3).reshape(
            cache.n_kv_heads, B * n, cache.head_dim
        )
        v_new = rv.transpose(1, 0, 2, 3).reshape(
            cache.n_kv_heads, B * n, cache.head_dim
        )
        if cache.scales is not None:
            k_new, ksc = quantize_kv(k_new)
            v_new, vsc = quantize_kv(v_new)
            ks_p, vs_p = cache.scales[li]
            ks_p = ks_p.at[:, pages_f, off_f].set(ksc, mode="drop")
            vs_p = vs_p.at[:, pages_f, off_f].set(vsc, mode="drop")
            new_scales.append((ks_p, vs_p))
        kp = kp.at[:, pages_f, off_f].set(k_new.astype(kp.dtype), mode="drop")
        vp = vp.at[:, pages_f, off_f].set(v_new.astype(vp.dtype), mode="drop")
        new_layers.append((kp, vp))
    new_lengths = jnp.minimum(
        cache.lengths + jnp.minimum(accepted, n), table.shape[1] * P
    )
    return cache._replace(
        layers=tuple(new_layers), lengths=new_lengths,
        scales=tuple(new_scales) if new_scales is not None else None,
    )


def write_chunk_rows(cache, ring_ks, ring_vs, start, accepted):
    B = cache.n_slots
    S = cache.max_len
    n = ring_ks[0].shape[2]
    j = jnp.arange(n)[None, :]
    pos = jnp.where(j < accepted[:, None], start[:, None] + j, S)
    bidx = jnp.arange(B)[:, None]
    new_layers = []
    new_scales = [] if cache.scales is not None else None
    for li, ((k, v), rk, rv) in enumerate(zip(cache.layers, ring_ks, ring_vs)):
        if cache.scales is not None:
            rk, ksc = quantize_kv(rk)
            rv, vsc = quantize_kv(rv)
            ks_p, vs_p = cache.scales[li]
            ks_p = ks_p.at[bidx, :, pos].set(
                ksc.transpose(0, 2, 1), mode="drop"
            )
            vs_p = vs_p.at[bidx, :, pos].set(
                vsc.transpose(0, 2, 1), mode="drop"
            )
            new_scales.append((ks_p, vs_p))
        k = k.at[bidx, :, pos].set(
            rk.transpose(0, 2, 1, 3).astype(k.dtype), mode="drop"
        )
        v = v.at[bidx, :, pos].set(
            rv.transpose(0, 2, 1, 3).astype(v.dtype), mode="drop"
        )
        new_layers.append((k, v))
    new_lengths = jnp.minimum(cache.lengths + accepted, S)
    return cache._replace(
        layers=tuple(new_layers), lengths=new_lengths,
        scales=tuple(new_scales) if new_scales is not None else None,
    )
