"""Mixture-of-experts feed-forward: two paths, chosen by the configuration's
fields and by no flag.

**Dense dispatch** (``moe_mlp``; ``ModelConfig.moe_router == "softmax"``: the
``llama`` family's Mixtral configurations). A softmax router over all
experts, top-k, weights renormalised. Every expert computes every token
(static shapes, pure einsums, no sort or gather) and the top-k gate zeroes the
contributions not chosen at combine time: ``n_experts / k`` times the work the
model requires, in exchange for a trivially shardable expert axis (experts
over the ``expert`` logical axis, the combine one psum). Right for 8 experts,
wrong for hundreds; its turn comes in ROADMAP Queue 1 item 2.

**Grouped dispatch over the experts held** (``latent_moe``;
``moe_router == "sigmoid"``: the ``nemotron_h`` family). The router, in
float32, scores all ``n_experts`` with a sigmoid, chooses the top-k of
``score + bias``, and weighs the chosen by their scores, normalised and
scaled. This chip holds the experts ``experts_held = (lo, hi)``: assignments
to experts it does not hold are dropped before the sort (the partial sum goes
on; the chips that hold the rest are not here, and nothing stands in for
them), the rest are sorted by expert and the two matmuls of the held experts
run as grouped products (``jax.lax.ragged_dot``, which XLA lowers to one
grouped-matmul kernel on the TPU), in a ``moe_latent``-wide space between a
projection in and a projection out. A shared expert is added. No expert
computes a token that was not routed to it.

No reference counterpart (the reference has no model execution,
SURVEY.md §2.13).
"""

from __future__ import annotations

from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from pilottai_tpu.models.qmatmul import qmatmul

from pilottai_tpu.parallel.sharding import with_logical_constraint


@jax.named_scope("moe")
def moe_mlp(
    cfg: Any,                 # ModelConfig (n_experts, n_active_experts, act)
    p: Dict[str, Any],        # layer slice: router [E,X], wg/wu [X,E,F], wd [X,F,E]
    x: jax.Array,             # [B, T, E]
    activation,               # callable matching the dense MLP's activation
) -> Tuple[jax.Array, jax.Array]:
    """Top-k routed MoE feed-forward.

    Returns (out [B, T, E], aux_loss scalar). aux_loss is the Switch-style
    load-balancing term (mean fraction routed × mean router probability ×
    n_experts, = 1.0 at perfect balance); the trainer weights and adds it.
    """
    X = cfg.n_experts
    k = min(cfg.n_active_experts, X)

    router_logits = jnp.einsum("bte,ex->btx", x, p["router"]).astype(jnp.float32)
    probs = jax.nn.softmax(router_logits, axis=-1)            # [B, T, X]
    top_w, top_idx = jax.lax.top_k(probs, k)                  # [B, T, k]
    top_w = top_w / jnp.maximum(
        jnp.sum(top_w, axis=-1, keepdims=True), 1e-9
    )
    # Dense combine weights: scatter top-k back to [B, T, X] via one-hot.
    one_hot = jax.nn.one_hot(top_idx, X, dtype=top_w.dtype)   # [B, T, k, X]
    combine = jnp.einsum("btk,btkx->btx", top_w, one_hot)     # [B, T, X]

    frac_routed = jnp.mean(one_hot[..., 0, :].reshape(-1, X), axis=0)
    mean_prob = jnp.mean(probs.reshape(-1, X), axis=0)
    aux_loss = X * jnp.sum(frac_routed * mean_prob)

    # All experts, all tokens; expert axis sharded -> each device computes
    # its local experts only. Expert matmuls go through the qmatmul
    # dispatch point with their einsum specs — the batched expert axis
    # keeps them on the fused-dequant arm for now (models/qmatmul.py).
    gate = activation(qmatmul(x, p["wg"], spec="bte,xef->btxf"))
    up = qmatmul(x, p["wu"], spec="bte,xef->btxf")
    h = gate * up
    h = with_logical_constraint(h, ("batch", "seq", "expert", None))
    y = qmatmul(h, p["wd"], spec="btxf,xfe->btxe")              # [B, T, X, E]
    out = jnp.einsum("btxe,btx->bte", y, combine.astype(y.dtype))
    return out, aux_loss


# Tokens a grouped dispatch sorts at once: its temporaries are k rows a token
# of the experts' width ([block * k, moe_intermediate]), so a long prefill
# runs block by block.
LATENT_MOE_BLOCK = 2048


@jax.named_scope("moe_route")
def route_sigmoid(cfg: Any, p: Dict[str, Any], x: jax.Array):
    """``(experts [N, k], weights [N, k] float32)`` for ``x [N, E]``: float32
    scores at ``highest`` (a bfloat16 pass over a 4096-wide contraction
    would flip choices the published router does not), the selection bias
    in the choice only."""
    s = jax.nn.sigmoid(jnp.matmul(
        x.astype(jnp.float32), p["router"].astype(jnp.float32),
        precision=jax.lax.Precision.HIGHEST,
    ))
    k = min(cfg.n_active_experts, cfg.n_experts)
    _, idx = jax.lax.top_k(s + p["bias"].astype(jnp.float32), k)
    w = jnp.take_along_axis(s, idx, axis=-1)
    w = w / jnp.maximum(jnp.sum(w, axis=-1, keepdims=True), 1e-20)
    return idx, w * cfg.moe_scale


@jax.named_scope("moe_experts")
def held_experts(cfg: Any, p: Dict[str, Any], activation, lat: jax.Array,
                 idx: jax.Array, w: jax.Array, real: jax.Array):
    """The held experts' share of ``sum_i w_i e_i(lat)`` for ``lat [N, Z]``,
    and how many of the real tokens' assignments landed on them. Absent
    experts sort last under one sentinel group that no product reads."""
    N, Z = lat.shape
    k = idx.shape[-1]
    lo, hi = cfg.experts_held or (0, cfg.n_experts)
    X = hi - lo
    flat = idx.reshape(N * k)
    held = (flat >= lo) & (flat < hi) & jnp.repeat(real, k)
    key = jnp.where(held, flat - lo, X)
    order = jnp.argsort(key, stable=True)
    sizes = jnp.zeros((X + 1,), jnp.int32).at[key].add(1)[:X]
    rows = lat[order // k]                                     # [N k, Z], by expert
    h = activation(jax.lax.ragged_dot(rows, p["w_up"], sizes))
    y = jax.lax.ragged_dot(h, p["w_down"], sizes)
    gate = jnp.where(held, w.reshape(N * k), 0.0)[order]
    # rows past the last group belong to no expert: whatever the product
    # left there is not read
    y = jnp.where(gate[:, None] != 0.0, y.astype(jnp.float32) * gate[:, None], 0.0)
    back = jnp.argsort(order)
    return y[back].reshape(N, k, Z).sum(axis=1), jnp.sum(held)


def latent_moe(
    cfg: Any,              # ModelConfig (moe_router == "sigmoid")
    p: Dict[str, Any],     # router [E, X] f32, bias [X], w_in [E, Z],
                           # w_up [held, Z, F], w_down [held, F, Z],
                           # w_out [Z, E], shared {wu [E, S], wd [S, E]}
    x: jax.Array,          # [B, T, E] normed input
    real: jax.Array,       # [B, T] bool: tokens that are no padding
    activation,            # callable: the experts' activation (no gate)
    shared_mlp,            # callable [N, E] -> [N, E]: the shared expert
) -> Tuple[jax.Array, jax.Array]:
    """``(out [B, T, E], counts [2] uint32)``: the routed part over the
    experts held plus the shared expert; ``counts`` are the real tokens'
    token-expert pairs routed and those of them that landed here."""
    B, T, E = x.shape
    N = B * T
    xf, rf = x.reshape(N, E), real.reshape(N)
    idx, w = route_sigmoid(cfg, p, xf)
    lat = qmatmul(xf, p["w_in"])
    blk = min(LATENT_MOE_BLOCK, N)
    if N % blk:
        raise ValueError(f"{N} tokens are no multiple of the dispatch block {blk}")
    if N == blk:
        routed, n_held = held_experts(cfg, p, activation, lat, idx, w, rf)
    else:
        nb = N // blk
        routed, n_held = jax.lax.map(
            lambda a: held_experts(cfg, p, activation, *a),
            (lat.reshape(nb, blk, -1), idx.reshape(nb, blk, -1),
             w.reshape(nb, blk, -1), rf.reshape(nb, blk)),
        )
        routed, n_held = routed.reshape(N, -1), jnp.sum(n_held)
    out = qmatmul(routed.astype(x.dtype), p["w_out"])
    with jax.named_scope("moe_shared"):
        out = out + shared_mlp(xf)
    counts = jnp.stack([jnp.sum(rf) * idx.shape[-1], n_held]).astype(jnp.uint32)
    return out.reshape(B, T, E), counts
