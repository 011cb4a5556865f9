"""The plain reference: the published forward pass in ``jax.numpy`` and
float32 at ``highest`` matmul precision, with no kernel, cache or batching.

Mistral / Mixtral as their public code states them: RMSNorm, rotate-half RoPE,
grouped-query causal attention, SwiGLU; for Mixtral a softmax router over all
experts, top-k, weights renormalised over the chosen k. It imports nothing of
the program and takes nothing the program made: the weights come from
``weights.py`` and the seed, one layer at a time, so the reference runs after
the program's state is freed and never holds more than one layer.

``mode`` computes the same pass in a lower precision, for the control:

- ``"f32"``  the reference itself;
- ``"act8"`` every input of a weight matmul rounded to int8 with one scale a
  row (the step below bfloat16 activations);
- ``"w4"``   every layer weight rounded to int4 with one scale per group of
  128 input rows (the step below int8 weights); the head stays int8.
"""

from __future__ import annotations

import functools
from typing import Dict, List, Sequence

import numpy as np

from perfbench.weights import Model, layer_leaves, outer_leaves, seed_key

PAD_TO = 1024  # sequences are right-padded to a multiple: at most four programs


def _deq(pair, mode: str, layer_weight: bool = True):
    import jax.numpy as jnp

    q, s = pair
    w = q.astype(jnp.float32) * s.astype(jnp.float32)
    if mode == "w4" and layer_weight:
        rows, cols = w.shape[-2], w.shape[-1]
        g = w.reshape(w.shape[:-2] + (rows // 128, 128, cols))
        scale = jnp.maximum(jnp.max(jnp.abs(g), axis=-2, keepdims=True), 1e-8) / 7.0
        w = (jnp.clip(jnp.round(g / scale), -8, 7) * scale).reshape(w.shape)
    return w


def _mm(x, w, mode: str):
    import jax
    import jax.numpy as jnp

    if mode == "act8":
        sx = jnp.maximum(jnp.max(jnp.abs(x), axis=-1, keepdims=True), 1e-8) / 127.0
        x = jnp.clip(jnp.round(x / sx), -127, 127) * sx
    return jnp.matmul(x, w, precision=jax.lax.Precision.HIGHEST)


def _rms(x, scale, eps):
    import jax
    import jax.numpy as jnp

    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale.astype(jnp.float32)


def _rope(x, theta):
    """Rotate-half RoPE over ``x [T, N, D]`` at positions 0..T-1."""
    import jax.numpy as jnp

    T, _, D = x.shape
    half = D // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * freqs
    sin, cos = jnp.sin(ang)[:, None, :], jnp.cos(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def _attention(m: Model, q, k, v):
    """Causal grouped-query attention, one KV head's group at a time."""
    import jax
    import jax.numpy as jnp

    T = q.shape[0]
    rep = m.heads // m.kv_heads
    hi = jax.lax.Precision.HIGHEST
    causal = jnp.arange(T)[:, None] >= jnp.arange(T)[None, :]

    def group(args):
        qg, kg, vg = args                      # [T, rep, D], [T, D], [T, D]
        s = jnp.einsum("tnd,sd->nts", qg, kg, precision=hi) * m.head_dim ** -0.5
        s = jnp.where(causal[None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("nts,sd->tnd", p, vg, precision=hi)

    qg = q.reshape(T, m.kv_heads, rep, m.head_dim).transpose(1, 0, 2, 3)
    out = jax.lax.map(group, (qg, k.transpose(1, 0, 2), v.transpose(1, 0, 2)))
    return out.transpose(1, 0, 2, 3).reshape(T, m.q_dim)


def _mlp(m: Model, lw, h, mode: str):
    import jax
    import jax.numpy as jnp

    if not m.experts:
        gate = jax.nn.silu(_mm(h, _deq(lw["wg"], mode), mode))
        out = _mm(gate * _mm(h, _deq(lw["wu"], mode), mode), _deq(lw["wd"], mode), mode)
        return out, jnp.full((h.shape[0],), jnp.inf, jnp.float32)
    logits = jnp.matmul(
        h, lw["router"].astype(jnp.float32), precision=jax.lax.Precision.HIGHEST
    )
    probs = jax.nn.softmax(logits, axis=-1)
    k = m.experts_per_tok
    ranked = jax.lax.top_k(logits, k + 1)[0]
    margin = ranked[..., k - 1] - ranked[..., k]      # last chosen over first left out
    top_w, top_i = jax.lax.top_k(probs, k)
    top_w = top_w / jnp.sum(top_w, axis=-1, keepdims=True)
    combine = jnp.sum(
        jax.nn.one_hot(top_i, m.experts, dtype=jnp.float32) * top_w[..., None], axis=-2
    )                                                       # [T, X]

    def expert(acc, args):
        wg, wu, wd, c = args
        gate = jax.nn.silu(_mm(h, _deq(wg, mode), mode))
        y = _mm(gate * _mm(h, _deq(wu, mode), mode), _deq(wd, mode), mode)
        return acc + y * c[:, None], None

    out, _ = jax.lax.scan(
        expert, jnp.zeros_like(h), (lw["wg"], lw["wu"], lw["wd"], combine.T)
    )
    return out, margin


@functools.lru_cache(maxsize=None)
def _layer_fn(m: Model, mode: str):
    import jax

    def layer(x, key, index):
        lw = layer_leaves(m, key, index)
        T = x.shape[0]
        h = _rms(x, lw["ln1"], m.rms_eps)
        q = _mm(h, _deq(lw["wq"], mode), mode).reshape(T, m.heads, m.head_dim)
        k = _mm(h, _deq(lw["wk"], mode), mode).reshape(T, m.kv_heads, m.head_dim)
        v = _mm(h, _deq(lw["wv"], mode), mode).reshape(T, m.kv_heads, m.head_dim)
        attn = _attention(m, _rope(q, m.rope_theta), _rope(k, m.rope_theta), v)
        x = x + _mm(attn, _deq(lw["wo"], mode), mode)
        out, margin = _mlp(m, lw, _rms(x, lw["ln2"], m.rms_eps), mode)
        return x + out, margin

    return jax.jit(layer)


@functools.lru_cache(maxsize=None)
def _head_fn(m: Model, mode: str):
    import jax

    def head(x, rows, key):
        outer = outer_leaves(m, key)
        h = _rms(x[rows], outer["final_norm"], m.rms_eps)
        return _mm(h, _deq(outer["lm_head"], mode, layer_weight=False), mode)

    return jax.jit(head)


@functools.lru_cache(maxsize=None)
def _embed_fn(m: Model):
    import jax
    import jax.numpy as jnp

    return jax.jit(
        lambda tokens, key: outer_leaves(m, key)["embed"][tokens].astype(jnp.float32)
    )


def logits_at(
    m: Model, seed: int, sequences: Sequence[Sequence[int]], n_last: Sequence[int],
    mode: str = "f32",
) -> List[np.ndarray]:
    """For each token sequence, the logits ``[n_last[i], vocab]`` at its last
    ``n_last[i]`` positions, and beside them the least margin, over the layers,
    by which the router at that position chose its experts (inf for a dense
    model). Layers are the outer loop and each call makes
    its layer's weights from the seed inside the program, so no more than one
    layer's weights are ever held."""
    import jax.numpy as jnp

    key = seed_key(seed)
    xs, lens = [], []
    for seq in sequences:
        n = len(seq)
        padded = -(-n // PAD_TO) * PAD_TO
        tokens = np.zeros((padded,), np.int32)
        tokens[:n] = np.asarray(seq, np.int32)
        xs.append(_embed_fn(m)(jnp.asarray(tokens), key))
        lens.append(n)
    layer = _layer_fn(m, mode)
    margins = [jnp.full((x.shape[0],), jnp.inf, jnp.float32) for x in xs]
    for index in range(m.layers):
        stepped = [layer(x, key, jnp.int32(index)) for x in xs]
        xs = [x for x, _ in stepped]
        margins = [jnp.minimum(a, b) for a, (_, b) in zip(margins, stepped)]
    out = []
    for x, mg, n, last in zip(xs, margins, lens, n_last):
        rows = jnp.arange(n - last, n, dtype=jnp.int32)
        out.append((np.asarray(_head_fn(m, mode)(x, rows, key)), np.asarray(mg[n - last:n])))
    return out


def served_gaps(
    m: Model, seed: int, samples: Sequence[Dict[str, List[int]]], mode: str = "f32",
    against: Sequence = (), router_tie: float = 0.0,
) -> Dict[str, object]:
    """Teacher-forced over ``prompt + served`` of each sample: by how much
    each served token's logit lies under the reference's best.

    Read only at positions whose own routing is clear of a tie: where the
    reference's router chose its last expert over the next by less than
    ``router_tie`` logits in some layer (the cell's limits file gives it), the
    program's bfloat16 router may as rightly choose the other, and the logits
    then differ by a whole expert, not by rounding. A dense model has no such
    positions.

    With ``mode != "f32"`` this is the control: ``against`` holds the float32
    pass's ``logits`` (with their margins) for the same samples, and the gap
    read is that of the token the lower precision puts first, no decoding
    needed. ``per_token`` holds every position's gap and margin, tie or not."""
    seqs = [s["prompt"] + s["served"][:-1] for s in samples]
    n_last = [len(s["served"]) for s in samples]
    logits = logits_at(m, seed, seqs, n_last, mode)
    gaps, margins = [], []
    for i, (s, (lg, _)) in enumerate(zip(samples, logits)):
        ref, margin = logits[i] if mode == "f32" else against[i]
        chosen = np.asarray(s["served"]) if mode == "f32" else lg.argmax(-1)
        gaps.append(ref.max(-1) - ref[np.arange(len(chosen)), chosen])
        margins.append(margin)
    gap, margin = np.concatenate(gaps), np.concatenate(margins)
    flat = gap[margin >= router_tie]
    return {
        "gap_max": float(flat.max()) if flat.size else None,
        "gap_mean": float(flat.mean()) if flat.size else None,
        "tokens": int(flat.size),
        "tokens_at_a_router_tie": int(gap.size - flat.size),
        "argmax_agree": int((flat == 0).sum()) / max(flat.size, 1),
        "per_token": {
            "gap": [float(g) for g in gap],
            "margin": [min(float(x), 1e9) for x in margin],
        },
        "logits": logits,
    }
