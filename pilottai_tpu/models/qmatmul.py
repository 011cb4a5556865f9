"""Quantized-operand matmul dispatch — the ONE site model code calls.

``transformer.py`` / ``moe.py`` route every weight matmul through
:func:`qmatmul` instead of spelling ``x @ dequant(w)`` at each site, so
*how* a quantized weight is consumed is a single platform decision
instead of eight copy-pasted ones (ISSUE 14):

* **Native quantized-operand path** (opt-in on every platform through
  ``PILOTTAI_QMATMUL=native``): the
  activation quantizes dynamically to int8 with per-row symmetric
  scales and the contraction runs as an integer
  ``lax.dot_general(..., preferred_element_type=int32)`` against the
  stored int8 weights (int4 weights unpack to int8 nibble values
  first — the HBM read is still the packed buffer). Scales fold in
  after the dot: per-output-channel weight scales commute with the
  contraction exactly; int4's per-group scales are applied per group
  via a grouped dot (the contraction splits into scale groups, each
  accumulated in int32 and scaled before the cross-group sum). No
  full-precision copy of the weight ever exists.
* **Fused dequant** (the default on every platform, and always for the
  einsum-shaped MoE expert matmuls): ``x @ dequant(w)`` — XLA fuses the
  convert+mul (and int4 nibble shifts) into the matmul's operand read
  on fusing backends. The HLO-inspector test
  (tests/test_quant_parity.py) pins that the native lowering carries
  no dense fp32 weight buffer, PR 12's ``collective_ops`` pattern
  applied to operand dtypes.

The native path changes numerics: activations round to 8 bits, so it
is int8 x int8, not the "weight-only int8" the CLI and the docs promise.
Against a float32 reference at Llama-3-8B widths its logits are off by
~2.5% relative RMS where the dequant form is off by ~0.6% (chip_smoke.py
holds the serving path to 1.2%). It used to be the default wherever the
backend was a TPU — an arm no test ran, because the suite runs on the
CPU. Now one arm runs everywhere unless the operator asks for the
other; whether the native arm is worth its precision is a measurement
for the benchmark to make (ROADMAP Queue 1 item 2). The byte-identity
contracts in tests run against the dequant lowering; quality under the
native path is covered by the checkpoint smoke in
tests/test_quant_parity.py.
"""

from __future__ import annotations

import os
from typing import Any, Optional

import jax
import jax.numpy as jnp

from pilottai_tpu.models.quant import Q4Tensor, QTensor, dequant, unpack_int4


def native_quant_matmul_ok() -> bool:
    """Should quantized weights feed the integer dot natively?  Only when
    ``PILOTTAI_QMATMUL=native`` asks for it: the answer does not depend on
    the platform, so the arithmetic the tests check on the CPU is the
    arithmetic the chip runs."""
    return os.environ.get("PILOTTAI_QMATMUL", "").lower() == "native"


def _dense_matmul(
    x: jax.Array, w: jax.Array, spec: Optional[str],
    preferred_element_type: Optional[Any],
) -> jax.Array:
    if spec is not None:
        if preferred_element_type is not None:
            return jnp.einsum(
                spec, x, w, preferred_element_type=preferred_element_type
            )
        return jnp.einsum(spec, x, w)
    if preferred_element_type is not None:
        return jax.lax.dot_general(
            x, w, (((x.ndim - 1,), (0,)), ((), ())),
            preferred_element_type=preferred_element_type,
        )
    return x @ w


def _quantize_activation(x: jax.Array):
    """Dynamic symmetric per-row int8: returns (xq int8, sx fp32 with a
    keepdim contraction axis)."""
    xf = x.astype(jnp.float32)
    amax = jnp.max(jnp.abs(xf), axis=-1, keepdims=True)
    sx = jnp.maximum(amax, 1e-8) / 127.0
    xq = jnp.clip(jnp.round(xf / sx), -127, 127).astype(jnp.int8)
    return xq, sx


def _native_int8_matmul(
    x: jax.Array, w: Any, preferred_element_type: Optional[Any]
) -> jax.Array:
    """Integer-operand contraction for a 2D quantized weight: int8
    activation × int8 weight → int32 accumulate, scales folded in after
    (per output channel, or per contraction group for int4)."""
    out_dtype = (
        preferred_element_type if preferred_element_type is not None
        else x.dtype
    )
    xq, sx = _quantize_activation(x)
    if isinstance(w, QTensor):
        acc = jax.lax.dot_general(
            xq, w.q, (((x.ndim - 1,), (0,)), ((), ())),
            preferred_element_type=jnp.int32,
        )
        out = acc.astype(jnp.float32) * sx * w.s[0].astype(jnp.float32)
        return out.astype(out_dtype)
    # Q4Tensor: per-group scales need per-group accumulation — split the
    # contraction into [G, group] and run ONE batched integer dot whose
    # batch axis is the scale group; each group's int32 partial scales
    # before the cross-group sum (algebraically exact: within a group
    # the scale is constant, so it commutes with that group's dot).
    in_dim, group = w.in_dim, w.group
    n_groups = w.s.shape[-2]
    wq = unpack_int4(w.q, in_dim)                     # [in, out] int8
    pad_rows = n_groups * group - in_dim
    if pad_rows:
        wq = jnp.pad(wq, ((0, pad_rows), (0, 0)))
        xq = jnp.pad(xq, [(0, 0)] * (xq.ndim - 1) + [(0, pad_rows)])
    wq_g = wq.reshape(n_groups, group, wq.shape[-1])  # [G, group, out]
    xq_g = xq.reshape(xq.shape[:-1] + (n_groups, group))
    acc = jnp.einsum(
        "...gi,gio->...go", xq_g, wq_g, preferred_element_type=jnp.int32
    )
    out = jnp.sum(
        acc.astype(jnp.float32) * w.s.astype(jnp.float32), axis=-2
    ) * sx
    return out.astype(out_dtype)


def qmatmul(
    x: jax.Array,
    w: Any,
    spec: Optional[str] = None,
    preferred_element_type: Optional[Any] = None,
) -> jax.Array:
    """The quantized-operand matmul dispatch point.

    ``w`` may be a plain array, a ``QTensor`` (int8) or a ``Q4Tensor``
    (packed int4). Without ``spec`` the contraction is ``x``'s last
    axis against ``w``'s first (the 2D layer-matmul shape after stacked
    slicing); einsum-shaped weights (MoE experts, the logits
    projection) pass their ``spec`` and always take the fused-dequant
    form — their batched-operand layouts have no native integer
    lowering yet (the grouped-GEMM Pallas kernel is the planned
    upgrade path, models/moe.py).

    ``preferred_element_type`` matches the einsum/dot kwarg: the
    logits projection asks for fp32 accumulation and gets it on every
    arm.
    """
    if isinstance(w, (QTensor, Q4Tensor)):
        if spec is None and w.q.ndim == 2 and native_quant_matmul_ok():
            return _native_int8_matmul(x, w, preferred_element_type)
        return _dense_matmul(x, dequant(w), spec, preferred_element_type)
    return _dense_matmul(x, w, spec, preferred_element_type)


__all__ = ["qmatmul", "native_quant_matmul_ok"]
