"""Operations and bytes the model and its kernels *require*, from the
configuration's shapes alone, and the table of peaks. Nothing here asks the
program: ``models/common.py:flops_per_token`` and
``models/quant.py:weight_stream_bytes`` are the program's own arithmetic, which
a later PR may change.

For a mixture of experts only the routed experts count: the program's dense
dispatch computes all of them, and that is work the model does not require.
"""

from __future__ import annotations

from typing import Dict

from perfbench.weights import Model

# Published peaks of one chip, keyed by jax's device_kind. A kind that is not
# here is an error, never a default.
PEAKS: Dict[str, Dict[str, object]] = {
    "TPU v5 lite": {
        "bf16_flops_per_s": 197e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "source": "Google Cloud documentation, 'TPU v5e': 197 TFLOP/s bf16, 819 GB/s, 16 GB HBM",
    },
}


def peaks(device_kind: str) -> Dict[str, object]:
    if device_kind not in PEAKS:
        raise KeyError(f"no published peaks for device kind {device_kind!r}")
    return PEAKS[device_kind]


def attn_params(m: Model) -> int:
    """Projection weights of one layer's attention."""
    return 2 * m.hidden * m.q_dim + 2 * m.hidden * m.kv_dim


def mlp_params_one(m: Model) -> int:
    """One SwiGLU feed-forward (dense, or one expert)."""
    return 3 * m.hidden * m.ffn


def params_held(m: Model) -> int:
    """Every parameter the configuration holds on the chip."""
    mlp = mlp_params_one(m) * max(m.experts, 1) + m.hidden * m.experts
    layer = attn_params(m) + mlp + 2 * m.hidden
    return m.layers * layer + 2 * m.vocab * m.hidden + m.hidden


def params_active(m: Model, with_head: bool = True) -> int:
    """Matmul parameters one token passes through: attention, the router,
    its routed experts (or the dense MLP), and the head if it is read."""
    mlp = mlp_params_one(m) * (m.experts_per_tok if m.experts else 1)
    layer = attn_params(m) + mlp + m.hidden * m.experts
    return m.layers * layer + (m.vocab * m.hidden if with_head else 0)


def attention_flops(m: Model, context_sum: float) -> float:
    """QK^T and PV over all layers: 4 * heads * head_dim for every (query,
    key) pair; ``context_sum`` is the sum over queries of the keys each sees."""
    return 4.0 * m.heads * m.head_dim * m.layers * context_sum


def request_flops(m: Model, prompt: int, output: int, cached_prefix: int = 0) -> float:
    """Operations one request requires: ``prompt - cached_prefix`` prompt
    tokens through the trunk (the head is read at the last one only), then
    ``output - 1`` decode steps with the head, each token attending causally."""
    fresh = prompt - cached_prefix
    trunk = 2.0 * params_active(m, with_head=False)
    head = 2.0 * m.vocab * m.hidden
    ctx_prefill = (prompt * (prompt + 1) - cached_prefix * (cached_prefix + 1)) / 2.0
    n_dec = max(output - 1, 0)
    ctx_decode = n_dec * prompt + n_dec * (n_dec + 1) / 2.0
    return (
        trunk * (fresh + n_dec) + head * max(output, 0)
        + attention_flops(m, ctx_prefill + ctx_decode)
    )


def decode_step_weight_bytes(m: Model) -> float:
    """Bytes of weights one decode step has to stream when they are int8 with
    a bfloat16 scale per output channel: every layer matmul (for a mixture,
    every expert: a full batch routes somewhere in each), the norms' scales
    and the head. The embedding is a gather of one row a slot."""
    E, F = m.hidden, m.ffn
    per_mlp = 3 * E * F + 2 * (2 * F + E)
    attn = attn_params(m) + 2 * (m.q_dim + 2 * m.kv_dim + E)
    mlp = per_mlp * max(m.experts, 1) + 2 * E * m.experts
    layer = attn + mlp + 2 * 2 * E
    return float(m.layers * layer + m.vocab * E + 2 * m.vocab + 2 * E)


def flash_prefill_flops(m: Model, context_sum: float) -> float:
    """Operations of causal prefill attention; ``context_sum`` as above."""
    return attention_flops(m, context_sum)


def flash_prefill_bytes(m: Model, q_tokens: float) -> float:
    """q, k, v read and the output written once, bfloat16, all layers."""
    return 2.0 * m.layers * q_tokens * (2 * m.q_dim + 2 * m.kv_dim)
