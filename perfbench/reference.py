"""The plain reference: the published forward pass in ``jax.numpy`` and
float32 at ``highest`` matmul precision, with no kernel, cache or batching.

The forward pass itself is the model's own module's ``logits_at``
(``perfbench/archs/<arch>.py``); here are the pieces every family's pass is
written with, and what is read from its logits. It imports nothing of the
program and takes nothing the program made: the weights come from the module
and the seed, one layer at a time, so the reference runs after the program's
state is freed and never holds more than one layer.

``mode`` computes the same pass in a lower precision, for the control:

- ``"f32"``  the reference itself;
- ``"act8"`` every input of a weight matmul rounded to int8 with one scale a
  row (the step below bfloat16 activations);
- ``"w4"``   every layer weight rounded to int4 with one scale per group of
  128 input rows (the step below int8 weights); the head stays int8.
"""

from __future__ import annotations

from typing import Any, Dict, List, Sequence

import numpy as np

from perfbench import archs

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


def served_gaps(
    m: Any, seed: int, samples: Sequence[Dict[str, List[int]]], mode: str = "f32",
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
    logits = archs.of(m).logits_at(m, seed, seqs, n_last, mode)
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
