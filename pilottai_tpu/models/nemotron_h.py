"""The ``nemotron_h`` family: a stack of Mamba-2, latent-expert and attention
layers in a published order (``ModelConfig.layer_kinds``), squared-ReLU
without a gate, attention without rotary embedding, a sigmoid router with a
selection bias. ``models/hybrid.py`` walks the stack; ``models/ssm.py`` and
``models/moe.py:latent_moe`` are its mixers.

Served in bfloat16 on one chip, which holds ``experts_held`` of the routed
experts; without prefix sharing and without speculation (a shared prefix is
only KV, and nothing snapshots the recurrent state at its boundary).
"""

from __future__ import annotations

from pilottai_tpu.models.common import ModelConfig


def nemotron_h(name: str, pattern: str, **sizes) -> ModelConfig:
    """A configuration of the family from its ``hybrid_override_pattern``
    (``M`` Mamba-2, ``E`` experts, ``*`` attention) and its sizes."""
    if set(pattern) - set("ME*"):
        raise ValueError(f"pattern {pattern!r}: layers are M, E or *")
    return ModelConfig(
        name=name, family="nemotron_h", n_layers=len(pattern),
        layer_kinds=tuple(pattern), act="relu2", mlp_gated=False, rope=False,
        tie_embeddings=False, moe_router="sigmoid", **sizes,
    )


# The family at a size a CPU test runs: every kind of layer, a share of the
# experts (4 of 16 held, top-3), two rows of state a slot.
NEMOTRON_H_TINY = nemotron_h(
    "nemotron-h-tiny", "MEM*EME",
    vocab_size=384, hidden_size=64, n_heads=4, n_kv_heads=2, head_dim=16,
    max_seq_len=512, rms_eps=1e-5,
    n_experts=16, n_active_experts=3, experts_held=(0, 4),
    moe_intermediate=48, moe_latent=16, moe_shared_intermediate=96,
    moe_scale=2.5,
    ssm_heads=4, ssm_head_dim=16, ssm_groups=2, ssm_state=8, ssm_conv=4,
    ssm_chunk=16,
)
