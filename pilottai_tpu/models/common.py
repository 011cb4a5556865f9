"""Model configuration, parameter init and sharding declarations.

One ``ModelConfig`` covers the Llama and Gemma families; family-specific
behaviors (activation, embed scaling, RMSNorm offset, logit soft-caps,
alternating sliding windows, post-norms) are explicit fields rather than
subclasses, so the single ``transformer.py`` forward stays scan-friendly.

A stack whose layers are not all alike (family ``nemotron_h``) names each
layer's kind in ``layer_kinds``: one mixer behind one norm, ``M`` (Mamba-2,
``models/ssm.py``), ``E`` (latent experts, ``models/moe.py``) or ``*``
(attention). Its parameters are one dict per layer, in the published order,
and ``models/hybrid.py`` walks them; the llama scan is not involved.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    name: str = "tiny-test"
    family: str = "llama"  # "llama" | "gemma" | "gemma2" | "nemotron_h"
    vocab_size: int = 512
    hidden_size: int = 256
    n_layers: int = 4
    n_heads: int = 4
    n_kv_heads: int = 2
    head_dim: int = 64
    intermediate_size: int = 512
    max_seq_len: int = 2048
    rope_theta: float = 500_000.0
    rms_eps: float = 1e-5
    tie_embeddings: bool = True

    # Family behaviors
    act: str = "silu"              # "silu" (llama) | "gelu_tanh" (gemma)
                                   # | "relu2" (squared ReLU, nemotron_h)
    mlp_gated: bool = True         # False: down(act(up(x))), no gate
    rope: bool = True              # False: attention applies no rotary
                                   # embedding (nemotron_h: the state-space
                                   # layers carry position)
    scale_embed: bool = False      # gemma: x *= sqrt(hidden)
    rms_offset: bool = False       # gemma: scale = (1 + w)
    post_norms: bool = False       # gemma2: post-attn / post-mlp norms
    logit_softcap: float = 0.0     # gemma2: 30.0
    attn_softcap: float = 0.0      # gemma2: 50.0
    sliding_window: int = 0        # gemma2: 4096 on alternating layers
    sliding_pattern: int = 0       # every Nth layer is global (gemma2: 2)
    query_scale: Optional[float] = None  # default head_dim**-0.5

    # Mixture-of-experts (0 = dense MLP). Experts shard over the 'expert'
    # logical axis (mesh 'model' by default) — expert parallelism.
    n_experts: int = 0
    n_active_experts: int = 2      # top-k routing
    # Latent experts (``moe_router == "sigmoid"``, models/moe.py's second
    # path): the router scores all ``n_experts``; this chip holds the
    # experts ``experts_held = (lo, hi)`` (None = all) and computes only
    # assignments that land on them, in a ``moe_latent``-wide space.
    moe_router: str = "softmax"    # "softmax" (Mixtral) | "sigmoid"
    experts_held: Optional[Tuple[int, int]] = None
    moe_intermediate: int = 0      # one routed expert's width
    moe_latent: int = 0            # width the routed experts compute in
    moe_shared_intermediate: int = 0  # the shared expert's width
    moe_scale: float = 1.0         # routed_scaling_factor

    # Per-layer kinds of a stack whose layers are not all alike: "M"
    # Mamba-2, "E" experts, "*" attention; () = every layer attention
    # then MLP (the llama scan). len(layer_kinds) == n_layers.
    layer_kinds: Tuple[str, ...] = ()
    ssm_heads: int = 0             # Mamba-2 heads
    ssm_head_dim: int = 0
    ssm_groups: int = 0            # B/C groups (head h reads group
                                   # h // (heads / groups))
    ssm_state: int = 0             # state size N
    ssm_conv: int = 4              # depthwise conv kernel
    ssm_chunk: int = 128           # chunk of the blocked scan

    dtype: Any = jnp.bfloat16

    @property
    def q_dim(self) -> int:
        return self.n_heads * self.head_dim

    @property
    def kv_dim(self) -> int:
        return self.n_kv_heads * self.head_dim

    @property
    def recurrent(self) -> bool:
        """Whether some layer keeps state that is not KV (a prefix of
        such a model cannot be shared by mapping pages alone)."""
        return "M" in self.layer_kinds

    @property
    def n_kv_layers(self) -> int:
        """Layers that keep KV: the attention layers only."""
        if self.layer_kinds:
            return sum(k == "*" for k in self.layer_kinds)
        return self.n_layers

    @property
    def n_held(self) -> int:
        lo, hi = self.experts_held or (0, self.n_experts)
        return hi - lo

    @property
    def ssm_inner(self) -> int:
        return self.ssm_heads * self.ssm_head_dim

    @property
    def ssm_conv_dim(self) -> int:
        """Channels through the convolution: x, B and C."""
        return self.ssm_inner + 2 * self.ssm_groups * self.ssm_state

    def kind_params(self, kind: str, held_only: bool = True) -> int:
        """Parameters of one layer of a ``layer_kinds`` stack, its norm
        included; ``held_only=False`` counts every routed expert."""
        E = self.hidden_size
        if kind == "*":
            return 2 * E * self.q_dim + 2 * E * self.kv_dim + E
        if kind == "M":
            I, H = self.ssm_inner, self.ssm_heads
            C = self.ssm_conv_dim
            return (
                E * (I + C + H) + (self.ssm_conv + 1) * C + 3 * H + I
                + I * E + E
            )
        if kind == "E":
            X = self.n_held if held_only else self.n_experts
            one = 2 * self.moe_latent * self.moe_intermediate
            return (
                E * self.n_experts + self.n_experts       # router, bias
                + 2 * E * self.moe_latent                  # in / out of latent
                + X * one
                + 2 * E * self.moe_shared_intermediate + E
            )
        raise ValueError(f"unknown layer kind {kind!r}")

    def window_sizes(self) -> np.ndarray:
        """Per-layer sliding-window sizes; 0 = global attention."""
        if self.sliding_window <= 0 or self.sliding_pattern <= 0:
            return np.zeros((self.n_layers,), dtype=np.int32)
        out = np.full((self.n_layers,), self.sliding_window, dtype=np.int32)
        out[self.sliding_pattern - 1 :: self.sliding_pattern] = 0
        return out

    def replace(self, **kwargs: Any) -> "ModelConfig":
        return dataclasses.replace(self, **kwargs)

    def param_count(self) -> int:
        E, F, V, L = self.hidden_size, self.intermediate_size, self.vocab_size, self.n_layers
        if self.layer_kinds:
            # What this chip holds: its share of the routed experts.
            head = 0 if self.tie_embeddings else E * V
            return (
                V * E + sum(self.kind_params(k) for k in self.layer_kinds)
                + E + head
            )
        mlp = 2 * E * F + F * E
        if self.n_experts > 0:
            mlp = self.n_experts * mlp + E * self.n_experts  # experts + router
        per_layer = (
            E * self.q_dim + 2 * E * self.kv_dim + self.q_dim * E  # attn
            + mlp
            + 2 * E + (2 * E if self.post_norms else 0)             # norms
        )
        head = 0 if self.tie_embeddings else E * V
        return V * E + L * per_layer + E + head

    def active_param_count(self) -> int:
        """Parameters touched per token on the forward pass: equals
        ``param_count`` for dense models; for MoE, only the router plus
        the top-k routed experts' MLPs count — the inactive experts'
        weights never stream from HBM for that token."""
        if self.layer_kinds:
            # A token's top-k experts lie anywhere among n_experts; the
            # held share of them is what this chip computes, on average.
            one = 2 * self.moe_latent * self.moe_intermediate
            idle = self.n_held - (
                self.n_active_experts * self.n_held / max(self.n_experts, 1)
            )
            n_e = sum(k == "E" for k in self.layer_kinds)
            return int(self.param_count() - n_e * idle * one)
        if self.n_experts <= 0:
            return self.param_count()
        E, F = self.hidden_size, self.intermediate_size
        mlp_one = 2 * E * F + F * E
        inactive = max(self.n_experts - self.n_active_experts, 0)
        return self.param_count() - self.n_layers * inactive * mlp_one

    def flops_per_token(self) -> float:
        """Model FLOPs per generated/prefilled token: 2 (multiply +
        accumulate) per active parameter — the standard weight-bound
        approximation (attention-score FLOPs are context-dependent and
        a few percent at serving context lengths; MFU derived from this
        is therefore a slight *under*-estimate, consistently so).

        This is THE formula for every MFU the repo reports: the live
        ``engine.mfu`` gauge (obs/attribution.py) and the bench sections
        both call it, so the numbers reconcile by construction."""
        return 2.0 * self.active_param_count()


def init_params(
    cfg: ModelConfig, key: jax.Array, dtype: Optional[Any] = None,
    quantize: bool = False,
) -> Dict[str, Any]:
    """Random-init a parameter pytree with stacked layers.

    Layer params carry a leading [L] axis so the forward pass can
    ``lax.scan`` over depth — compile time stays O(1) in n_layers, which
    matters on TPU where the first jit is the slow step.

    ``quantize=True`` emits matmul weights directly as int8 ``QTensor``s
    (models/quant.py), quantizing each leaf eagerly as it is generated —
    the bf16 intermediate frees leaf by leaf, so an 8B model peaks at
    ~(int8 tree + one layer-stack leaf) instead of the full bf16 tree
    plus the int8 copy. That is what lets llama3-8b random-init fit a
    single 16 GB v5e. Norms, embeds, and the MoE router stay dense,
    matching ``quantize_params``.
    """
    dtype = dtype or cfg.dtype
    if cfg.layer_kinds:
        if quantize:
            raise ValueError(
                f"{cfg.name} ({cfg.family}): weight quantization is not built "
                "for Mamba-2 and latent-expert layers; serve it in bfloat16"
            )
        return _init_layer_kinds(cfg, key, dtype)
    E, F, V, L = cfg.hidden_size, cfg.intermediate_size, cfg.vocab_size, cfg.n_layers
    keys = jax.random.split(key, 8)

    def dense(k, shape, fan_in):
        return (jax.random.normal(k, shape, dtype=jnp.float32) * fan_in**-0.5).astype(dtype)

    def normal(k, shape, fan_in):
        if not (quantize and len(shape) >= 3):
            return dense(k, shape, fan_in)
        # Generate + quantize one leading (layer) slice per fused dispatch:
        # eager whole-leaf generation keeps multiple fp32 intermediates of
        # the biggest MLP leaf alive at once (~15 GB for 8B) — per-slice,
        # the transient is a few hundred MB and the int8 result is all
        # that accumulates.
        from pilottai_tpu.models.quant import QTensor, quantize_array

        @functools.partial(jax.jit, static_argnames=("shp", "fi"))
        def gen_chunk(k, shp, fi):
            w = (
                jax.random.normal(k, shp, dtype=jnp.float32) * fi**-0.5
            ).astype(dtype)
            return quantize_array(w, dtype)

        chunks = [
            gen_chunk(kk, shape[1:], fan_in)
            for kk in jax.random.split(k, shape[0])
        ]
        return QTensor(
            q=jnp.stack([c.q for c in chunks]),
            s=jnp.stack([c.s for c in chunks]),
        )

    layers: Dict[str, Any] = {
        "ln1": {"scale": jnp.zeros((L, E), dtype) if cfg.rms_offset else jnp.ones((L, E), dtype)},
        "ln2": {"scale": jnp.zeros((L, E), dtype) if cfg.rms_offset else jnp.ones((L, E), dtype)},
        "attn": {
            "wq": normal(keys[0], (L, E, cfg.q_dim), E),
            "wk": normal(keys[1], (L, E, cfg.kv_dim), E),
            "wv": normal(keys[2], (L, E, cfg.kv_dim), E),
            "wo": normal(keys[3], (L, cfg.q_dim, E), cfg.q_dim),
        },
    }
    if cfg.n_experts > 0:
        X = cfg.n_experts
        layers["moe"] = {
            # Router stays dense even under quantize — its logits pick
            # which experts run (see quantize_params).
            "router": dense(jax.random.fold_in(keys[4], 7), (L, E, X), E),
            "wg": normal(keys[4], (L, X, E, F), E),
            "wu": normal(keys[5], (L, X, E, F), E),
            "wd": normal(keys[6], (L, X, F, E), F),
        }
    else:
        layers["mlp"] = {
            "wg": normal(keys[4], (L, E, F), E),
            "wu": normal(keys[5], (L, E, F), E),
            "wd": normal(keys[6], (L, F, E), F),
        }
    if cfg.post_norms:
        zero_or_one = jnp.zeros if cfg.rms_offset else jnp.ones
        layers["ln1_post"] = {"scale": zero_or_one((L, E), dtype)}
        layers["ln2_post"] = {"scale": zero_or_one((L, E), dtype)}

    params: Dict[str, Any] = {
        "embed": normal(keys[7], (V, E), 1.0),
        "layers": layers,
        "final_norm": {
            "scale": jnp.zeros((E,), dtype) if cfg.rms_offset else jnp.ones((E,), dtype)
        },
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = normal(jax.random.fold_in(keys[7], 1), (E, V), E)
    return params


def _init_layer_kinds(cfg: ModelConfig, key: jax.Array, dtype: Any) -> Dict[str, Any]:
    """Random init of a ``layer_kinds`` stack: one dict per layer, in the
    published order (``"norm"`` and the mixer's own sub-tree: ``"ssm"``,
    ``"moe"`` or ``"attn"``). ``A``, ``dt_bias`` and ``D`` as the Mamba-2
    family initialises them: A uniform in 1..16, dt log-uniform in
    [1e-3, 1e-1] floored at 1e-4, D = 1."""
    E, V = cfg.hidden_size, cfg.vocab_size

    def dense(k, shape, fan_in, dt=dtype):
        return (jax.random.normal(k, shape, dtype=jnp.float32) * fan_in**-0.5).astype(dt)

    def norm():
        return {"scale": jnp.ones((E,), dtype)}

    layers = []
    for l, kind in enumerate(cfg.layer_kinds):
        k = jax.random.split(jax.random.fold_in(key, l + 1), 8)
        if kind == "*":
            mixer = {"attn": {
                "wq": dense(k[0], (E, cfg.q_dim), E),
                "wk": dense(k[1], (E, cfg.kv_dim), E),
                "wv": dense(k[2], (E, cfg.kv_dim), E),
                "wo": dense(k[3], (cfg.q_dim, E), cfg.q_dim),
            }}
        elif kind == "M":
            I, H, C = cfg.ssm_inner, cfg.ssm_heads, cfg.ssm_conv_dim
            dt = jnp.maximum(jnp.exp(
                jax.random.uniform(k[2], (H,)) * (np.log(0.1) - np.log(1e-3))
                + np.log(1e-3)
            ), 1e-4)
            mixer = {"ssm": {
                "in_proj": dense(k[0], (E, I + C + H), E),
                "conv_w": dense(k[1], (cfg.ssm_conv, C), cfg.ssm_conv),
                "conv_b": jnp.zeros((C,), dtype),
                "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),     # softplus^-1
                "A_log": jnp.log(jax.random.uniform(k[3], (H,), minval=1.0, maxval=16.0)),
                "D": jnp.ones((H,), jnp.float32),
                "norm": jnp.ones((I,), dtype),
                "out_proj": dense(k[4], (I, E), I),
            }}
        elif kind == "E":
            X, Z, F = cfg.n_held, cfg.moe_latent, cfg.moe_intermediate
            S = cfg.moe_shared_intermediate
            mixer = {"moe": {
                # float32: its scores pick which experts run
                "router": dense(k[0], (E, cfg.n_experts), E, jnp.float32),
                "bias": jnp.zeros((cfg.n_experts,), jnp.float32),
                "w_in": dense(k[1], (E, Z), E),
                "w_up": dense(k[2], (X, Z, F), Z),
                "w_down": dense(k[3], (X, F, Z), F),
                "w_out": dense(k[4], (Z, E), Z),
                "shared": {
                    "wu": dense(k[5], (E, S), E), "wd": dense(k[6], (S, E), S),
                },
            }}
        else:
            raise ValueError(f"unknown layer kind {kind!r}")
        layers.append(dict(mixer, norm=norm()))
    params: Dict[str, Any] = {
        "embed": dense(jax.random.fold_in(key, 0), (V, E), 1.0),
        "layers": tuple(layers),
        "final_norm": norm(),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = dense(jax.random.fold_in(key, 10_000), (E, V), E)
    return params


def param_logical_axes(cfg: ModelConfig) -> Dict[str, Any]:
    """Parallel pytree of logical-axis tuples for ``shard_params``.

    Layer leaves have a leading "layers" axis (never sharded). TP shards
    heads/mlp/vocab over the ``model`` mesh axis; FSDP shards the embed
    axis; see ``parallel/sharding.DEFAULT_RULES``.
    """
    if cfg.layer_kinds:
        raise NotImplementedError(
            f"{cfg.name} ({cfg.family}): a stack of Mamba-2 and latent-expert "
            "layers runs on one chip (its share is ModelConfig.experts_held); "
            "the exchange across chips is not built (ROADMAP Queue 2 item 2)"
        )
    layers: Dict[str, Any] = {
        "ln1": {"scale": ("layers", None)},
        "ln2": {"scale": ("layers", None)},
        "attn": {
            "wq": ("layers", "embed", "heads"),
            "wk": ("layers", "embed", "kv_heads"),
            "wv": ("layers", "embed", "kv_heads"),
            "wo": ("layers", "heads", "embed"),
        },
    }
    if cfg.n_experts > 0:
        layers["moe"] = {
            "router": ("layers", "embed", None),
            "wg": ("layers", "expert", "embed", "mlp_expert"),
            "wu": ("layers", "expert", "embed", "mlp_expert"),
            "wd": ("layers", "expert", "mlp_expert", "embed"),
        }
    else:
        layers["mlp"] = {
            "wg": ("layers", "embed", "mlp"),
            "wu": ("layers", "embed", "mlp"),
            "wd": ("layers", "mlp", "embed"),
        }
    if cfg.post_norms:
        layers["ln1_post"] = {"scale": ("layers", None)}
        layers["ln2_post"] = {"scale": ("layers", None)}
    axes: Dict[str, Any] = {
        "embed": ("vocab", "embed"),
        "layers": layers,
        "final_norm": {"scale": (None,)},
    }
    if not cfg.tie_embeddings:
        axes["lm_head"] = ("embed", "vocab")
    return axes


def rms_norm(x: jax.Array, scale: jax.Array, eps: float, offset: bool) -> jax.Array:
    """RMSNorm in fp32 statistics (Gemma adds 1 to the learned scale)."""
    dtype = x.dtype
    xf = x.astype(jnp.float32)
    normed = xf * jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    s = scale.astype(jnp.float32)
    if offset:
        s = s + 1.0
    return (normed * s).astype(dtype)


def rope_tables(
    positions: jax.Array, head_dim: int, theta: float
) -> Tuple[jax.Array, jax.Array]:
    """sin/cos tables for rotate-half RoPE. positions [B, T] →
    sin/cos [B, T, head_dim/2] in fp32."""
    half = head_dim // 2
    freqs = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    angles = positions.astype(jnp.float32)[..., None] * freqs  # [B, T, half]
    return jnp.sin(angles), jnp.cos(angles)


def apply_rope(x: jax.Array, sin: jax.Array, cos: jax.Array) -> jax.Array:
    """Rotate-half RoPE: x [B, T, N, H], sin/cos [B, T, H/2]."""
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    sin = sin[:, :, None, :].astype(jnp.float32)
    cos = cos[:, :, None, :].astype(jnp.float32)
    x1f, x2f = x1.astype(jnp.float32), x2.astype(jnp.float32)
    out = jnp.concatenate(
        [x1f * cos - x2f * sin, x2f * cos + x1f * sin], axis=-1
    )
    return out.astype(x.dtype)
