"""A stack whose layers are not all alike (``ModelConfig.layer_kinds``, the
``nemotron_h`` family): every layer is ``x <- x + Mixer(RMSNorm(x))`` with one
mixer of three kinds, ``M`` Mamba-2 (``models/ssm.py``), ``E`` latent experts
(``models/moe.py:latent_moe``) or ``*`` attention without rotary embedding,
walked in the published order; after the last layer a norm and the head.

``walk_layers`` is the one walk. The step programs (``engine/decode.py``)
hand it how attention reads its keys, which is all that differs between a
full prefill, a tail against cached pages and a decode step, and whether the
state-space layers run their prefill or their one-token update. Parameters are
one dict per layer (``models/common.py:_init_layer_kinds``), no stacked axis:
eleven layers unroll, and the llama scan is not involved.
"""

from __future__ import annotations

from functools import partial
from typing import Any, Callable, Dict, Optional, Sequence

import jax
import jax.numpy as jnp

from pilottai_tpu.models.common import ModelConfig, rms_norm
from pilottai_tpu.models.moe import latent_moe
from pilottai_tpu.models.ssm import mamba_prefill, mamba_step
from pilottai_tpu.models.transformer import (
    _activation,
    _attn_out,
    _embed,
    _mlp,
    _qkv,
    _rows_at,
    _unembed,
)
from pilottai_tpu.ops.kvcache import StatePool
from pilottai_tpu.ops.attention import (
    dot_product_attention,
    flash_enabled,
    flash_shapes_ok,
)


def walk_layers(
    cfg: ModelConfig,
    params: Dict[str, Any],
    x: jax.Array,                       # [A, T, E]
    attend: Callable,                   # (kv index, attn params, normed h)
                                        # -> (attn [A, T, heads, head_dim], aux)
    conv: Sequence[jax.Array],          # per M layer, the rows' conv state
    ssm: Sequence[jax.Array],           # per M layer, the rows' ssm state
    real: jax.Array,                    # [A, T] bool: tokens that are no padding
    lens: Optional[jax.Array] = None,   # [A]: prefill of that many tokens a row;
                                        # None: one decode step, rows with
                                        # real[:, 0] move their state
):
    """``(x, [attend's aux per attention layer], conv', ssm', counts)``."""
    aux, conv_out, ssm_out = [], [], []
    counts = jnp.zeros((2,), jnp.uint32)
    mi = 0
    for l, kind in enumerate(cfg.layer_kinds):
        lp = params["layers"][l]
        h = rms_norm(x, lp["norm"]["scale"], cfg.rms_eps, cfg.rms_offset)
        if kind == "*":
            attn, a = attend(len(aux), lp["attn"], h)
            aux.append(a)
            out = _attn_out(cfg, lp["attn"], attn)
        elif kind == "M":
            if lens is None:
                out, c, s = mamba_step(cfg, lp["ssm"], h, conv[mi], ssm[mi], real[:, 0])
            else:
                out, c, s = mamba_prefill(cfg, lp["ssm"], h, lens, conv[mi], ssm[mi])
            conv_out.append(c)
            ssm_out.append(s)
            mi += 1
        elif kind == "E":
            shared = {"mlp": lp["moe"]["shared"]}
            out, n = latent_moe(
                cfg, lp["moe"], h, real, partial(_activation, cfg),
                lambda t: _mlp(cfg, shared, t)[0],
            )
            counts = counts + n
        else:
            raise ValueError(f"unknown layer kind {kind!r}")
        x = x + out.astype(x.dtype)
    return x, aux, tuple(conv_out), tuple(ssm_out), counts


@partial(jax.jit, static_argnames=("cfg", "use_flash"))
def forward_prefill_hybrid(
    params: Dict[str, Any],
    cfg: ModelConfig,
    tokens: jax.Array,      # [A, T] right-padded
    lens: jax.Array,        # [A] true prompt lengths
    use_flash: bool = True,
    logit_positions: Optional[jax.Array] = None,
):
    """A whole prompt from nothing: ``(logits, ks, vs, conv, ssm, counts)``.
    ``ks`` / ``vs`` are ``[attention layers, A, T, K, H]``, the states are
    each row's after its ``lens`` tokens. Logits as ``forward_prefill``'s:
    ``[A, T, V]``, or ``[A, V]`` at ``logit_positions``."""
    A, T = tokens.shape
    positions = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32)[None], (A, T))
    real = positions < lens[:, None]
    qscale = cfg.query_scale if cfg.query_scale is not None else cfg.head_dim**-0.5
    flash = (
        use_flash and flash_enabled()
        and flash_shapes_ok(T, T, head_dim=cfg.head_dim, itemsize=jnp.dtype(cfg.dtype).itemsize)
    )

    def attend(_, p, h):
        q, k, v = _qkv(cfg, p, h, None, None)
        if flash:
            from pilottai_tpu.ops.pallas.flash_attention import flash_attention

            attn = flash_attention(
                q, k, v, positions, positions, lens, jnp.int32(0),
                scale=qscale, softcap=cfg.attn_softcap,
            )
        else:
            mask = (positions[:, None, :] <= positions[:, :, None]) & real[:, None, :]
            attn = dot_product_attention(
                q, k, v, mask=mask, scale=qscale, logit_softcap=cfg.attn_softcap
            )
        return attn, (k, v)

    fresh = StatePool.create(cfg, A, cfg.dtype)     # every prompt starts from nothing
    x = _embed(cfg, params, tokens)
    x, kv, conv, ssm, counts = walk_layers(
        cfg, params, x, attend, fresh.conv, fresh.ssm, real, lens=lens
    )
    if logit_positions is not None:
        x = _rows_at(x, logit_positions)
    x = rms_norm(x, params["final_norm"]["scale"], cfg.rms_eps, cfg.rms_offset)
    logits = _unembed(cfg, params, x)
    ks = jnp.stack([k for k, _ in kv])
    vs = jnp.stack([v for _, v in kv])
    return logits, ks, vs, conv, ssm, counts
