"""Shared transformer forward: prefill and single-token decode.

Both paths ``lax.scan`` over stacked layer params (static shapes, O(1)
compile in depth) and express GQA/RoPE/soft-caps per ``ModelConfig``.
Activation shardings are declared with logical axes; under a mesh, XLA
inserts the TP all-reduces over ICI on its own.

No reference counterpart — this replaces the remote API call at
``pilott/engine/llm.py:59`` with on-device compute.
"""

from __future__ import annotations

from functools import partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from pilottai_tpu.models.common import (
    ModelConfig,
    apply_rope,
    rms_norm,
    rope_tables,
)
from pilottai_tpu.ops.attention import (
    dot_product_attention,
    flash_enabled,
    flash_shapes_ok,
)
from pilottai_tpu.ops.pallas.flash_attention import flash_sharding_ok
from pilottai_tpu.models.qmatmul import qmatmul
from pilottai_tpu.ops.kvcache import KVCache
from pilottai_tpu.parallel.sharding import with_logical_constraint


def _activation(cfg: ModelConfig, x: jax.Array) -> jax.Array:
    if cfg.act == "gelu_tanh":
        return jax.nn.gelu(x, approximate=True)
    if cfg.act == "relu2":
        return jnp.square(jax.nn.relu(x))
    return jax.nn.silu(x)


def _mlp(
    cfg: ModelConfig, lp: Dict[str, Any], x: jax.Array
) -> Tuple[jax.Array, jax.Array]:
    """Feed-forward: dense SwiGLU (``lp["mlp"]``), or top-k MoE when the
    layer carries a ``moe`` sub-tree (cfg.n_experts > 0).

    Returns (out, aux_loss) — aux_loss is 0.0 for dense layers and the
    load-balancing term for MoE (collected by forward_train's scan)."""
    if "moe" in lp:
        from pilottai_tpu.models.moe import moe_mlp

        return moe_mlp(cfg, lp["moe"], x, lambda h: _activation(cfg, h))
    p = lp["mlp"]
    if not cfg.mlp_gated:
        with jax.named_scope("mlp"):
            up = _activation(cfg, qmatmul(x, p["wu"]))
            return qmatmul(up, p["wd"]), jnp.zeros((), jnp.float32)
    with jax.named_scope("mlp"):
        gate = _activation(cfg, qmatmul(x, p["wg"]))
        up = qmatmul(x, p["wu"])
        return qmatmul(gate * up, p["wd"]), jnp.zeros((), jnp.float32)


# The ``jax.named_scope`` on each phase's builder (embed, attn, mlp or moe,
# kv_write, unembed, sampler; here and in engine/decode.py, ops/) names the
# step programs' operations in a profiler trace. Op metadata only: the
# programs, and the compile cache's keys, are what they were.
@jax.named_scope("attn")
def _qkv(
    cfg: ModelConfig,
    p: Dict[str, Any],
    x: jax.Array,
    sin: jax.Array,
    cos: jax.Array,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    B, T, _ = x.shape
    q = qmatmul(x, p["wq"]).reshape(B, T, cfg.n_heads, cfg.head_dim)
    k = qmatmul(x, p["wk"]).reshape(B, T, cfg.n_kv_heads, cfg.head_dim)
    v = qmatmul(x, p["wv"]).reshape(B, T, cfg.n_kv_heads, cfg.head_dim)
    if cfg.rope:
        q = apply_rope(q, sin, cos)
        k = apply_rope(k, sin, cos)
    return q, k, v


@jax.named_scope("attn")
def _attn_out(cfg: ModelConfig, p: Dict[str, Any], attn: jax.Array) -> jax.Array:
    B, T = attn.shape[:2]
    return qmatmul(attn.reshape(B, T, cfg.q_dim), p["wo"])


@jax.named_scope("embed")
def _embed(cfg: ModelConfig, params: Dict[str, Any], tokens: jax.Array) -> jax.Array:
    x = params["embed"][tokens]
    if cfg.scale_embed:
        x = x * jnp.asarray(cfg.hidden_size**0.5, x.dtype)
    return x


def _rows_at(x: jax.Array, positions: jax.Array) -> jax.Array:
    """``x[b, positions[b]]`` for every row: [B, T, E] → [B, E]."""
    return jnp.take_along_axis(x, positions[:, None, None], axis=1)[:, 0]


@jax.named_scope("unembed")
def _unembed(cfg: ModelConfig, params: Dict[str, Any], x: jax.Array) -> jax.Array:
    head = params["lm_head"] if "lm_head" in params else params["embed"].T
    # No spec: the logits projection is the plain last-axis contraction,
    # so a quantized head keeps the native integer-operand lowering.
    logits = qmatmul(x, head, preferred_element_type=jnp.float32)
    if cfg.logit_softcap > 0.0:
        logits = jnp.tanh(logits / cfg.logit_softcap) * cfg.logit_softcap
    return logits


def _full_seq_block(
    cfg: ModelConfig,
    qscale: float,
    x: jax.Array,
    lp: Dict[str, Any],
    window: jax.Array,
    sin: jax.Array,
    cos: jax.Array,
    ipos: jax.Array,
    jpos: jax.Array,
    base_mask: jax.Array,
    positions: Optional[jax.Array] = None,  # [B, T]; enables flash dispatch
    valid: Optional[jax.Array] = None,      # [B]
    ring_mesh: Any = None,                  # Mesh → ring attention over 'seq'
    allow_flash: bool = True,               # False when running off-TPU
    flash_mesh: Any = None,                 # Mesh → shard_map'd flash (TP/DP)
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """One transformer block over a full sequence (shared by prefill and
    the training forward). Returns (x, k, v)."""
    h = rms_norm(x, lp["ln1"]["scale"], cfg.rms_eps, cfg.rms_offset)
    q, k, v = _qkv(cfg, lp["attn"], h, sin, cos)
    T = q.shape[1]
    use_flash = (
        positions is not None
        and valid is not None
        and allow_flash
        and flash_enabled()
        and flash_shapes_ok(T, T, head_dim=cfg.head_dim, itemsize=q.dtype.itemsize)
    )
    if ring_mesh is not None and positions is not None and valid is not None:
        # Context parallelism: K/V rotate around the 'seq' ring (ICI);
        # differentiable, so the training path uses it directly.
        from pilottai_tpu.parallel.ring_attention import ring_attention

        attn = ring_attention(
            q, k, v, positions, valid, window,
            scale=qscale, softcap=cfg.attn_softcap, mesh=ring_mesh,
        )
    # Pallas flash kernel (fwd + custom-VJP bwd). A caller on ONE device
    # passes no flash_mesh and calls it directly — however many chips
    # the host has (the process-wide device count says nothing about
    # this computation); on a mesh it runs per-shard under shard_map
    # (batch over data/fsdp, heads over model) when the shapes divide.
    elif use_flash and flash_mesh is None:
        from pilottai_tpu.ops.pallas.flash_attention import flash_attention

        attn = flash_attention(
            q, k, v, positions, positions, valid, window,
            scale=qscale, softcap=cfg.attn_softcap,
        )
    elif use_flash and flash_sharding_ok(
        flash_mesh, q.shape[0], cfg.n_heads, cfg.n_kv_heads
    ):
        from pilottai_tpu.ops.pallas.flash_attention import (
            flash_attention_sharded,
        )

        attn = flash_attention_sharded(
            flash_mesh, q, k, v, positions, positions, valid, window,
            scale=qscale, softcap=cfg.attn_softcap,
        )
    else:
        win_mask = jnp.where(
            window > 0, (ipos - jpos) < jnp.maximum(window, 1), True
        )
        mask = base_mask & win_mask
        attn = dot_product_attention(
            q, k, v, mask=mask, scale=qscale, logit_softcap=cfg.attn_softcap
        )
    out = _attn_out(cfg, lp["attn"], attn)
    if cfg.post_norms:
        out = rms_norm(out, lp["ln1_post"]["scale"], cfg.rms_eps, cfg.rms_offset)
    x = x + out
    h = rms_norm(x, lp["ln2"]["scale"], cfg.rms_eps, cfg.rms_offset)
    out, aux = _mlp(cfg, lp, h)
    if cfg.post_norms:
        out = rms_norm(out, lp["ln2_post"]["scale"], cfg.rms_eps, cfg.rms_offset)
    x = x + out
    x = with_logical_constraint(x, ("batch", "seq", None))
    return x, k, v, aux


# --------------------------------------------------------------------- #
# Prefill
# --------------------------------------------------------------------- #

@partial(jax.jit, static_argnames=("cfg", "use_flash", "flash_mesh"))
def forward_prefill(
    params: Dict[str, Any],
    cfg: ModelConfig,
    tokens: jax.Array,      # [B, T] (right-padded)
    positions: jax.Array,   # [B, T] absolute positions (pad slots arbitrary)
    valid: jax.Array,       # [B] true prompt lengths
    use_flash: bool = True,  # callers running off-TPU (e.g. the cpu
                             # provider on a machine whose DEFAULT backend
                             # is a TPU) must pass False — flash_enabled()
                             # only sees the default backend
    flash_mesh: Any = None,  # static Mesh → shard_map'd flash on multi-chip
    logit_positions: Optional[jax.Array] = None,  # [B] — unembed only
                             # this position of each row (admission reads
                             # one row; [B, T, V] fp32 at a 128K vocab is
                             # 8 GB for 8 prompts of 2048)
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Full-prompt forward. Returns (logits fp32, k, v): logits are
    [B, T, V], or [B, V] at ``logit_positions`` when given (the same
    arithmetic on those rows); k/v are [L, B, T, K, H] ready to insert
    into a KVCache."""
    x = _embed(cfg, params, tokens)
    x = with_logical_constraint(x, ("batch", "seq", None))
    sin, cos = rope_tables(positions, cfg.head_dim, cfg.rope_theta)
    windows = jnp.asarray(cfg.window_sizes())
    qscale = cfg.query_scale if cfg.query_scale is not None else cfg.head_dim**-0.5

    # Causal mask within the prompt, from the *absolute* positions argument
    # (not arange), restricted to valid tokens — so prefill at a nonzero
    # offset masks consistently with its RoPE.
    T = tokens.shape[1]
    jpos = positions[:, None, :]          # [B, 1, T] key positions
    ipos = positions[:, :, None]          # [B, T, 1] query positions
    base_mask = (jpos <= ipos) & (
        jnp.arange(T)[None, None, :] < valid[:, None, None]
    )

    def layer_fn(carry, scanned):
        x = carry
        lp, window = scanned
        x, k, v, _ = _full_seq_block(
            cfg, qscale, x, lp, window, sin, cos, ipos, jpos, base_mask,
            positions=positions, valid=valid, allow_flash=use_flash,
            flash_mesh=flash_mesh,
        )
        return x, (k, v)

    x, (ks, vs) = jax.lax.scan(
        layer_fn, x, (params["layers"], windows)
    )
    if logit_positions is not None:
        x = _rows_at(x, logit_positions)                 # [B, E]
    x = rms_norm(x, params["final_norm"]["scale"], cfg.rms_eps, cfg.rms_offset)
    logits = _unembed(cfg, params, x)
    return logits, ks, vs


# --------------------------------------------------------------------- #
# Decode
# --------------------------------------------------------------------- #

@partial(jax.jit, static_argnames=("cfg",), donate_argnames=("cache",))
def forward_decode(
    params: Dict[str, Any],
    cfg: ModelConfig,
    tokens: jax.Array,     # [B] current token per slot
    cache: KVCache,        # donated; positions written at cache.lengths
    active: jax.Array,     # [B] bool — which slots hold live sequences
) -> Tuple[jax.Array, KVCache]:
    """One decode step for every slot. Returns (logits [B, V] fp32, cache).

    This is the dense single-step *reference* path (pure XLA, per-layer
    K-major panels); production serving runs the fused multi-step
    ``engine/decode.py:decode_chunk`` which is parity-tested against it.

    Inactive slots still flow through the matmuls (static shapes — one
    compilation serves the whole serving lifetime) but their cache writes
    are routed out-of-bounds (dropped by XLA scatter semantics) and their
    lengths stay frozen, so a freed slot is bit-identical until readmission.
    """
    B = tokens.shape[0]
    S = cache.max_len
    # Write index == current length; inactive slots write at S (dropped).
    positions = jnp.where(active, cache.lengths, S)
    x = _embed(cfg, params, tokens[:, None])  # [B, 1, E]
    sin, cos = rope_tables(positions[:, None], cfg.head_dim, cfg.rope_theta)
    windows = cfg.window_sizes()
    qscale = cfg.query_scale if cfg.query_scale is not None else cfg.head_dim**-0.5
    bidx = jnp.arange(B)
    G = cfg.n_heads // cfg.n_kv_heads
    col = jnp.arange(S)[None, None, None, :]              # [1, 1, 1, S]
    pos_b = positions[:, None, None, None]                # [B, 1, 1, 1]

    new_layers = []
    for l in range(cfg.n_layers):
        lp = jax.tree.map(lambda a: a[l], params["layers"])
        window = int(windows[l])
        layer_k, layer_v = cache.layers[l]
        h = rms_norm(x, lp["ln1"]["scale"], cfg.rms_eps, cfg.rms_offset)
        q, k_new, v_new = _qkv(cfg, lp["attn"], h, sin, cos)
        # K-major panels: write [B, K, H] at each slot's position.
        layer_k = layer_k.at[bidx, :, positions].set(k_new[:, 0], mode="drop")
        layer_v = layer_v.at[bidx, :, positions].set(v_new[:, 0], mode="drop")

        qg = q[:, 0].reshape(B, cfg.n_kv_heads, G, cfg.head_dim)
        s = jnp.einsum(
            "bkgh,bksh->bkgs", qg, layer_k, preferred_element_type=jnp.float32
        ) * qscale
        if cfg.attn_softcap > 0.0:
            s = jnp.tanh(s / cfg.attn_softcap) * cfg.attn_softcap
        mask = col <= pos_b
        if window > 0:
            mask &= (pos_b - col) < window
        s = jnp.where(mask, s, -2.0**30)
        w = jax.nn.softmax(s, axis=-1).astype(layer_v.dtype)
        attn = jnp.einsum(
            "bkgs,bksh->bkgh", w, layer_v, preferred_element_type=jnp.float32
        ).astype(x.dtype)

        out = _attn_out(cfg, lp["attn"], attn.reshape(B, 1, cfg.n_heads, cfg.head_dim))
        if cfg.post_norms:
            out = rms_norm(out, lp["ln1_post"]["scale"], cfg.rms_eps, cfg.rms_offset)
        x = x + out
        h = rms_norm(x, lp["ln2"]["scale"], cfg.rms_eps, cfg.rms_offset)
        out, _ = _mlp(cfg, lp, h)
        if cfg.post_norms:
            out = rms_norm(out, lp["ln2_post"]["scale"], cfg.rms_eps, cfg.rms_offset)
        x = x + out
        new_layers.append((layer_k, layer_v))

    x = rms_norm(x, params["final_norm"]["scale"], cfg.rms_eps, cfg.rms_offset)
    logits = _unembed(cfg, params, x)[:, 0]  # [B, V]
    new_lengths = jnp.where(active, cache.lengths + 1, cache.lengths)
    return logits, KVCache(layers=tuple(new_layers), lengths=new_lengths)


# --------------------------------------------------------------------- #
# Training forward
# --------------------------------------------------------------------- #

@partial(jax.jit, static_argnames=("cfg", "remat", "ring_mesh", "flash_mesh"))
def forward_train(
    params: Dict[str, Any],
    cfg: ModelConfig,
    tokens: jax.Array,      # [B, T] (right-padded)
    positions: jax.Array,   # [B, T]
    valid: jax.Array,       # [B] true lengths
    remat: bool = True,
    ring_mesh: Any = None,  # static Mesh → ring attention over the seq axis
    flash_mesh: Any = None,  # static Mesh → shard_map'd flash (no seq shard)
) -> Tuple[jax.Array, jax.Array]:
    """Full-sequence forward for training: (logits, moe_aux_loss), no KV
    outputs. moe_aux_loss is the mean load-balancing term over layers
    (0.0 for dense models).

    With ``remat=True`` each layer body is wrapped in ``jax.checkpoint``
    so the backward pass recomputes activations instead of storing T×L of
    them — the HBM-for-FLOPs trade that makes long-sequence training fit.
    No reference counterpart (the reference has no training at all,
    SURVEY.md §1 "What the reference is NOT").
    """
    x = _embed(cfg, params, tokens)
    x = with_logical_constraint(x, ("batch", "seq", None))
    sin, cos = rope_tables(positions, cfg.head_dim, cfg.rope_theta)
    windows = jnp.asarray(cfg.window_sizes())
    qscale = cfg.query_scale if cfg.query_scale is not None else cfg.head_dim**-0.5

    T = tokens.shape[1]
    jpos = positions[:, None, :]
    ipos = positions[:, :, None]
    base_mask = (jpos <= ipos) & (
        jnp.arange(T)[None, None, :] < valid[:, None, None]
    )

    def block(x, lp, window):
        # positions/valid always flow in; _full_seq_block's dispatch picks
        # ring (seq-sharded mesh) > flash kernel (TPU, shapes fit; direct
        # on one chip, shard_map'd via flash_mesh on many) > XLA dense.
        x, _, _, aux = _full_seq_block(
            cfg, qscale, x, lp, window, sin, cos, ipos, jpos, base_mask,
            positions=positions, valid=valid,
            ring_mesh=ring_mesh,
            flash_mesh=flash_mesh if ring_mesh is None else None,
        )
        return x, aux

    if remat:
        block = jax.checkpoint(
            block, policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable
        )

    def layer_fn(carry, scanned):
        lp, window = scanned
        x, aux = block(carry, lp, window)
        return x, aux

    x, aux_per_layer = jax.lax.scan(layer_fn, x, (params["layers"], windows))
    x = rms_norm(x, params["final_norm"]["scale"], cfg.rms_eps, cfg.rms_offset)
    # Mean MoE load-balance loss over layers (0.0 for dense models).
    return _unembed(cfg, params, x), jnp.mean(aux_per_layer)
