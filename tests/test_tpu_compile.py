"""Compile the main path's kernels for a DESCRIBED v5e chip, at
Llama-3-8B shapes.

The suite runs on the CPU, where Pallas kernels run in interpret mode and
the quantized matmul takes whatever arm the host takes. The TPU's
compiler is installed anyway and compiles for a chip that is described,
not attached — it refuses what the real chip would refuse (misaligned
slices, too much VMEM, programs that do not fit HBM). Nothing runs, so
these say nothing about results or speed; a compile that passes is not a
chip run. ~1-2 s each, and they guard every later PR at no chip time.

The topology is described inside a fixture, never at import: only one
process may load the TPU library at a time, and every xdist worker
imports every test file.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

# Llama-3-8B attention/MLP shapes (models/llama.py:LLAMA3_8B).
N_HEADS, N_KV, HEAD = 32, 8, 128
HIDDEN, FFN = 4096, 14336


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # noqa: BLE001 — no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without a chip (the next one would warn and
    recompile): switch the cache off around these tests."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


@pytest.fixture
def spec(one_chip, no_persistent_cache):
    def make(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    return make


# ---------------------------------------------------------------------- #
# Paged decode attention (ops/pallas/paged_attention.py)
# ---------------------------------------------------------------------- #

@pytest.mark.parametrize(
    "name,kv_int8,q_blocks,ring,page,strip",
    [
        ("bf16-strip4", False, 1, 0, 128, 4),
        ("int8kv-strip4", True, 1, 0, 128, 4),
        ("int8kv-verify-block6", True, 6, 0, 128, 4),
        ("bf16-ring16-fused", False, 1, 16, 128, 8),
        ("int8kv-page64-strip1", True, 1, 0, 64, 1),
    ],
)
def test_paged_kernel_compiles_at_8b_shapes(
    spec, name, kv_int8, q_blocks, ring, page, strip
):
    from pilottai_tpu.ops.pallas.paged_attention import paged_decode_attention

    B, max_seq = 8, 4096
    n_blocks = max_seq // page
    num_pages = B * 2048 // page + 1
    pool_dtype = jnp.int8 if kv_int8 else jnp.bfloat16
    q = spec((B, N_HEADS * q_blocks, HEAD), jnp.bfloat16)
    pool = spec((N_KV, num_pages, page, HEAD), pool_dtype)
    table = spec((B, n_blocks), jnp.int32)
    last = spec((B,), jnp.int32)
    scales = spec((N_KV, num_pages, page), jnp.float32) if kv_int8 else None
    ring_kv = spec((B, N_KV, ring, HEAD), jnp.bfloat16) if ring else None
    ring_step = spec((), jnp.int32) if ring else None

    def run(q, k_pool, v_pool, table, last, ks, vs, rk, rv, rs):
        return paged_decode_attention(
            q, k_pool, v_pool, table, last,
            n_blocks=n_blocks, n_strip=strip, q_blocks=q_blocks,
            k_scales=ks, v_scales=vs, ring_k=rk, ring_v=rv, ring_step=rs,
        )

    compiled = jax.jit(run).lower(
        q, pool, pool, table, last, scales, scales, ring_kv, ring_kv, ring_step
    ).compile()
    assert "tpu_custom_call" in compiled.as_text(), name


@pytest.mark.parametrize("kv_int8,page", [(True, 128), (True, 64), (False, 128)])
def test_widest_strip_the_vmem_model_admits_compiles(spec, kv_int8, page):
    """The batcher's strip candidates come from ``max_safe_strip``: the
    widest it offers must be one the chip's compiler takes. (At int8 KV
    with pages of 128 it once offered 8, which needs 21 MB of VMEM.)"""
    from pilottai_tpu.ops.pallas.paged_attention import (
        max_safe_strip,
        paged_decode_attention,
    )

    B, n_blocks = 12, 4096 // page
    strip = max_safe_strip(
        8, n_blocks, page, N_KV, HEAD, 1 if kv_int8 else 2, kv_int8
    )
    assert strip == {(True, 128): 4, (True, 64): 8, (False, 128): 8}[
        (kv_int8, page)
    ]
    num_pages = B * 2048 // page + 1
    pool = spec(
        (N_KV, num_pages, page, HEAD), jnp.int8 if kv_int8 else jnp.bfloat16
    )
    scales = spec((N_KV, num_pages, page), jnp.float32) if kv_int8 else None

    def run(q, k_pool, v_pool, table, last, ks, vs):
        return paged_decode_attention(
            q, k_pool, v_pool, table, last, n_blocks=n_blocks,
            n_strip=strip, k_scales=ks, v_scales=vs,
        )

    jax.jit(run).lower(
        spec((B, N_HEADS, HEAD), jnp.bfloat16), pool, pool,
        spec((B, n_blocks), jnp.int32), spec((B,), jnp.int32), scales, scales,
    ).compile()


# ---------------------------------------------------------------------- #
# Flash attention (ops/pallas/flash_attention.py)
# ---------------------------------------------------------------------- #

def _flash_args(spec, B, T):
    return (
        spec((B, T, N_HEADS, HEAD), jnp.bfloat16),
        spec((B, T, N_KV, HEAD), jnp.bfloat16),
        spec((B, T, N_KV, HEAD), jnp.bfloat16),
        spec((B, T), jnp.int32),
        spec((B,), jnp.int32),
        spec((), jnp.int32),
    )


@pytest.mark.parametrize("T", [512, 2048])
def test_flash_forward_compiles_at_8b_shapes(spec, T):
    from pilottai_tpu.ops.pallas.flash_attention import flash_attention

    def fwd(q, k, v, pos, valid, window):
        return flash_attention(q, k, v, pos, pos, valid, window)

    compiled = jax.jit(fwd).lower(*_flash_args(spec, 2, T)).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_flash_backward_compiles_at_8b_shapes(spec):
    from pilottai_tpu.ops.pallas.flash_attention import flash_attention

    def loss(q, k, v, pos, valid, window):
        out = flash_attention(q, k, v, pos, pos, valid, window)
        return jnp.sum(out.astype(jnp.float32) ** 2)

    compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        *_flash_args(spec, 2, 512)
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()


# ---------------------------------------------------------------------- #
# Native quantized-operand matmul (models/qmatmul.py)
# ---------------------------------------------------------------------- #

@pytest.mark.parametrize("bits", [8, 4])
def test_native_qmatmul_compiles_at_8b_mlp_shape(spec, bits, monkeypatch):
    """The integer-operand arm (opt-in, ``PILOTTAI_QMATMUL=native``) at
    the 4096 x 14336 MLP projection: the chip's compiler must take the
    int8 x int8 -> int32 dot, and no dense float copy of the weight may
    appear in the program."""
    from pilottai_tpu.models.qmatmul import qmatmul
    from pilottai_tpu.models.quant import quantize_array

    monkeypatch.setenv("PILOTTAI_QMATMUL", "native")
    w_shape = jax.eval_shape(
        lambda: quantize_array(
            jnp.zeros((HIDDEN, FFN), jnp.bfloat16), jnp.bfloat16,
            bits=bits, group=128,
        )
    )
    w = jax.tree.map(lambda a: spec(a.shape, a.dtype), w_shape)
    x = spec((8, HIDDEN), jnp.bfloat16)
    compiled = jax.jit(lambda x, w: qmatmul(x, w)).lower(x, w).compile()
    text = compiled.as_text()
    assert "s32[" in text, "no int32 accumulation in the native lowering"
    for dense in (f"f32[{HIDDEN},{FFN}]", f"bf16[{HIDDEN},{FFN}]"):
        assert dense not in text, f"dense weight buffer {dense} materialised"


# ---------------------------------------------------------------------- #
# Mamba-2 decode update of the live slots (ops/pallas/ssm_update.py)
# ---------------------------------------------------------------------- #

def test_ssm_update_compiles_at_nemotron_shapes_in_place(spec):
    """The kernel at Nemotron-3-Super's widths (64 slots, 128 heads of 64 in
    8 groups, a state of 128): the chip's compiler takes it, the pool is
    the output's buffer, and no copy of the pool is made around the call."""
    from pilottai_tpu.ops.pallas.ssm_update import ssm_update, ssm_update_ok

    B, H, P, N, G = 64, 128, 64, 128, 8
    assert ssm_update_ok(H, P, N)
    compiled = jax.jit(
        lambda *a: ssm_update(*a, groups=G), donate_argnums=0
    ).lower(
        spec((B, H, P, N), jnp.float32), spec((B, H), jnp.float32),
        spec((B, H, P), jnp.float32), spec((B, G, N), jnp.float32),
        spec((B, G, N), jnp.float32), spec((B,), jnp.bool_),
    ).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    assert compiled.memory_analysis().alias_size_in_bytes >= B * H * P * N * 4
    pool = f"f32[{B},{H},{P},{N}]"
    assert not [line for line in text.splitlines() if pool in line and " copy(" in line]
