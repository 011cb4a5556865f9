"""The table of peaks, and the operations and bytes the model and its kernels
*require*, from the configuration's shapes alone. The counts are the model's
own module's (``perfbench/archs/<arch>.py``); the functions here hand on to it
under the same names, so a metric reader is the same for every family. Nothing
here asks the program: ``models/common.py:flops_per_token`` and
``models/quant.py:weight_stream_bytes`` are the program's own arithmetic, which
a later PR may change.
"""

from __future__ import annotations

from typing import Any, Dict

from perfbench import archs

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


def attn_params(m: Any) -> int:
    return archs.of(m).attn_params(m)


def mlp_params_one(m: Any) -> int:
    return archs.of(m).mlp_params_one(m)


def params_held(m: Any) -> int:
    return archs.of(m).params_held(m)


def params_active(m: Any, with_head: bool = True) -> int:
    return archs.of(m).params_active(m, with_head)


def attention_flops(m: Any, context_sum: float) -> float:
    return archs.of(m).attention_flops(m, context_sum)


def request_flops(m: Any, prompt: int, output: int, cached_prefix: int = 0) -> float:
    return archs.of(m).request_flops(m, prompt, output, cached_prefix)


def decode_step_weight_bytes(m: Any) -> float:
    return archs.of(m).decode_step_weight_bytes(m)


def flash_prefill_flops(m: Any, context_sum: float) -> float:
    return archs.of(m).flash_prefill_flops(m, context_sum)


def flash_prefill_bytes(m: Any, q_tokens: float) -> float:
    return archs.of(m).flash_prefill_bytes(m, q_tokens)
