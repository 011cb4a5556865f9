"""Mixture-of-experts MLP with top-k routing and expert parallelism.

TPU-first design choice: *dense dispatch*. Every expert computes every
token (static shapes, pure einsums onto the MXU, no ragged gather or
host round-trips) and the top-k gate zeroes non-selected contributions
at combine time. Costs n_experts/k more MLP FLOPs than sparse dispatch,
in exchange for zero dynamic shapes and a trivially shardable expert
axis: with experts sharded over the ``expert`` logical axis (mesh
``model`` by default), each device runs only its local experts and the
combine's sum over experts becomes one XLA psum over ICI — expert
parallelism without an all-to-all. A grouped-GEMM Pallas kernel is the
planned upgrade path for large expert counts.

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
