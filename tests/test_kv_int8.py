"""int8 KV cache: quantized panels + per-token-per-head scales.

VERDICT r3 next-step 7: the dormant ``scales`` field is now populated.
Panels store int8; every read path (dense slices, paged gathers, the
Pallas paged kernel, prefix-store export, tail-prefill gathers)
dequantizes with the matching scales. Quality bound: symmetric per-token
int8 holds relative K/V error around 1/254 per element, so attention
outputs stay within ~1e-2 of the full-precision path.
"""

import asyncio

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from pilottai_tpu.core.config import LLMConfig
from pilottai_tpu.engine.handler import LLMHandler
from pilottai_tpu.engine.types import ChatMessage, GenerationParams
from pilottai_tpu.ops.kvcache import (
    KVCache,
    dequantize_kv,
    quantize_kv,
    write_chunk_rows,
    write_prompts,
)

import kv_write_oracle as oracle


def test_quantize_roundtrip_is_lossless_fixpoint():
    """dequantize → requantize must be exact (same scale recomputed) —
    the invariant that lets the prefix store traffic in full-precision
    panels over an int8-resident cache."""
    x = jax.random.normal(jax.random.PRNGKey(0), (4, 8, 16), jnp.float32)
    q, s = quantize_kv(x)
    x2 = dequantize_kv(q, s, jnp.float32)
    q2, s2 = quantize_kv(x2)
    np.testing.assert_array_equal(np.asarray(q), np.asarray(q2))
    np.testing.assert_allclose(np.asarray(s), np.asarray(s2), rtol=1e-6)


def test_quantize_error_bound():
    x = jax.random.normal(jax.random.PRNGKey(1), (64, 128), jnp.float32)
    q, s = quantize_kv(x)
    err = np.abs(np.asarray(dequantize_kv(q, s, jnp.float32)) - np.asarray(x))
    amax = np.abs(np.asarray(x)).max(axis=-1, keepdims=True)
    assert (err <= amax / 127.0 * 0.51 + 1e-7).all()


def test_write_prompts_quantized_storage_accuracy():
    """Panels written through the quantizing path must dequantize back to
    the source values within the int8 bound."""
    L, A, T, K, H = 2, 2, 8, 2, 16
    ks = jax.random.normal(jax.random.PRNGKey(2), (L, A, T, K, H))
    vs = jax.random.normal(jax.random.PRNGKey(3), (L, A, T, K, H))
    lens = jnp.asarray([8, 5])
    cache = KVCache.create(L, 4, 16, K, H, dtype=jnp.float32, quantized=True)
    cache = write_prompts(cache, jnp.asarray([0, 2]), ks, vs, lens)
    assert cache.layers[0][0].dtype == jnp.int8
    got = np.asarray(dequantize_kv(
        cache.layers[1][0], cache.scales[1][0], jnp.float32
    ))
    want = np.asarray(ks[1]).swapaxes(1, 2)  # [A, K, T, H]
    np.testing.assert_allclose(got[0, :, :8], want[0, :, :8], atol=2e-2)
    np.testing.assert_allclose(got[2, :, :5], want[1, :, :5], atol=2e-2)
    # Ring write path too.
    rk = [jax.random.normal(jax.random.PRNGKey(4 + l), (4, K, 2, H))
          for l in range(L)]
    rv = [jax.random.normal(jax.random.PRNGKey(9 + l), (4, K, 2, H))
          for l in range(L)]
    cache = write_chunk_rows(
        cache, rk, rv, cache.lengths, jnp.asarray([2, 0, 2, 0])
    )
    got = np.asarray(dequantize_kv(
        cache.layers[0][0], cache.scales[0][0], jnp.float32
    ))
    np.testing.assert_allclose(got[0, :, 8:10], np.asarray(rk[0][0]),
                               atol=2e-2)


async def _gen(prompts, **cfg_kw):
    h = LLMHandler(LLMConfig(
        model_name="llama-tiny", provider="cpu", engine_slots=4,
        engine_max_seq=256, engine_chunk=4, dtype="float32", **cfg_kw,
    ))
    await h.start()
    try:
        outs = []
        for p in prompts:
            r = await h.generate_response(
                [ChatMessage(content=p)],
                params=GenerationParams(max_new_tokens=12, temperature=0.0),
            )
            outs.append(r.content)
        return outs
    finally:
        await h.stop()


PRE = ("You are the orchestrator. Analyze the task and respond with "
       "strict JSON as instructed by the rules preamble. Task: ")


@pytest.mark.parametrize("accepted", sorted(oracle.ACCEPTED))
@pytest.mark.parametrize("n", [1, 4, 6], ids=["chunk1", "chunk4", "spec2x3"])
@pytest.mark.parametrize("quantized", [True, False], ids=["int8", "bf16"])
def test_chunk_rows_dense_bit_identical_to_old_scatter(quantized, n, accepted):
    """The in-place chunk-end write of the dense panels against the scatter
    it replaced (PR 30), on panels full of random bytes: every panel,
    every scale and ``lengths`` come out the same, bit for bit; slot 1
    stands at the end of its panel, so its rows past S are dropped, and a
    row past ``accepted`` leaves the panel as it was."""
    B, K, S, H, L = 4, 2, 64, 32, 2
    rng = np.random.default_rng(n * 5 + len(accepted))
    shape = (B, K, S, H)
    if quantized:
        panel = lambda: jnp.asarray(rng.integers(-127, 128, shape), jnp.int8)
    else:
        panel = lambda: jnp.asarray(rng.standard_normal(shape), jnp.bfloat16)
    start = jnp.asarray([10, S - 2, 0, 33], jnp.int32)
    cache = KVCache(
        layers=tuple((panel(), panel()) for _ in range(L)),
        lengths=start,
        scales=tuple(
            (jnp.asarray(rng.random(shape[:-1]), jnp.float32),
             jnp.asarray(rng.random(shape[:-1]), jnp.float32))
            for _ in range(L)
        ) if quantized else None,
    )
    rings = [
        jnp.asarray(rng.standard_normal((B, K, n, H)) * 3, jnp.bfloat16)
        for _ in range(2 * L)
    ]
    acc = jnp.asarray(oracle.ACCEPTED[accepted](n), jnp.int32)
    new = jax.jit(write_chunk_rows)(cache, rings[:L], rings[L:], start, acc)
    old = jax.jit(oracle.write_chunk_rows)(cache, rings[:L], rings[L:], start, acc)
    np.testing.assert_array_equal(np.asarray(new.lengths), np.asarray(old.lengths))
    flat = lambda c: jax.tree.leaves((c.layers, c.scales))
    for got, want, was in zip(flat(new), flat(old), flat(cache)):
        np.testing.assert_array_equal(
            np.asarray(got.astype(jnp.float32)), np.asarray(want.astype(jnp.float32))
        )
        if accepted == "none":
            np.testing.assert_array_equal(
                np.asarray(got.astype(jnp.float32)), np.asarray(was.astype(jnp.float32))
            )


@pytest.mark.asyncio
@pytest.mark.parametrize("paged", [False, True])
async def test_engine_int8_kv_deterministic_and_composes(paged):
    """engine_kv_quantize='int8' serves deterministically (repeat ==
    repeat) with every fast path on: paged pool, speculation, prefix
    caching. Token-level parity with fp32 is NOT required (rounding may
    legitimately flip a greedy argmax on a random-weight model) — what
    is required is internal consistency."""
    prompts = [PRE + "alpha", PRE + "alpha", PRE + "beta"]
    outs = await _gen(
        prompts, engine_kv_quantize="int8", engine_paged_kv=paged,
        engine_page_size=16, engine_speculate=4, engine_prefix_cache=8,
    )
    assert outs[0] == outs[1], "int8 KV: exact repeat diverged"
    assert all(isinstance(o, str) for o in outs)


@pytest.mark.asyncio
async def test_engine_int8_kv_close_to_fp32():
    """The int8 engine's greedy stream should agree with fp32 for at
    least the first tokens of a short generation (the error bound is
    ~1e-2 on attention outputs; total drift over 12 byte-tokens on
    llama-tiny stays small)."""
    want = (await _gen([PRE + "gamma"]))[0]
    got = (await _gen([PRE + "gamma"], engine_kv_quantize="int8"))[0]
    agree = sum(a == b for a, b in zip(got[:6], want[:6]))
    assert agree >= 4, (want, got)
