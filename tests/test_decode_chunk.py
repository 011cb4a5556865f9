"""Fused multi-step decode (engine/decode.py) correctness.

The production serving path decodes N tokens per dispatch with on-device
sampling and EOS/budget tracking; these tests pin it to the dense
single-step reference (models/transformer.py:forward_decode) and check
the device-side termination semantics the batcher relies on.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from pilottai_tpu.engine.decode import (
    DecodeState,
    admit_decode,
    decode_chunk,
    release_decode,
    sample_prefill_tokens,
)
from pilottai_tpu.engine.sampling import SamplingState, admit_sampling, sample_core
from pilottai_tpu.models.common import init_params
from pilottai_tpu.models.registry import get_model_config
from pilottai_tpu.models.transformer import forward_decode, forward_prefill
from pilottai_tpu.ops.kvcache import KVCache, write_prompts


def _admit(cfg, params, temps, budgets, eos=-1, seed0=10):
    """Prefill two prompts into slots 0 and 2 of a 4-slot cache."""
    B, S, A, T = 4, 128, 4, 64
    rng = np.random.default_rng(0)
    lens = np.array([17, 33, 0, 0], np.int32)
    tokens = np.zeros((A, T), np.int32)
    for i in range(2):
        tokens[i, : lens[i]] = rng.integers(2, cfg.vocab_size, lens[i])
    slots = jnp.asarray([0, 2, B, B], jnp.int32)
    positions = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32)[None], (A, T))

    cache = KVCache.create(
        cfg.n_layers, B, S, cfg.n_kv_heads, cfg.head_dim, dtype=jnp.float32
    )
    sampling = SamplingState.create(B)
    dstate = DecodeState.create(B)
    logits, ks, vs = forward_prefill(
        params, cfg, jnp.asarray(tokens), positions, jnp.asarray(lens)
    )
    cache = write_prompts(cache, slots, ks, vs, jnp.asarray(lens))
    sampling = admit_sampling(
        sampling, slots, jnp.full((A,), float(temps)),
        jnp.zeros(A, jnp.int32), jnp.ones(A),
        jnp.arange(seed0, seed0 + A, dtype=jnp.int32),
        jnp.full((A,), eos, jnp.int32),
        jnp.zeros((A,), bool),
    )
    last = logits[jnp.arange(A), jnp.maximum(jnp.asarray(lens) - 1, 0)]
    first, sampling = sample_prefill_tokens(last, slots, sampling)
    dstate = admit_decode(
        dstate, slots, first, jnp.asarray(budgets, jnp.int32),
        jnp.asarray(lens > 0),
    )
    return cache, dstate, sampling


@pytest.mark.parametrize("cfg_name", ["llama-tiny", "gemma-tiny"])
def test_chunked_decode_matches_stepwise(cfg_name):
    """12 tokens via 3 fused chunks == 12 single steps, with temperature
    sampling (full-distribution sensitive) and shared PRNG evolution."""
    cfg = get_model_config(cfg_name)
    params = init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
    # Random-init logits are peaked; high temperature flattens them so the
    # sampled ids depend on the whole distribution, not just the argmax.
    cache, dstate, sampling = _admit(cfg, params, temps=30.0, budgets=[20, 20, 0, 0])

    ref_cache = KVCache(
        layers=tuple((k.copy(), v.copy()) for k, v in cache.layers),
        lengths=cache.lengths.copy(),
    )
    ref_sampling = SamplingState(*[a.copy() for a in sampling])
    cur = dstate.tokens.copy()
    active = jnp.asarray([True, False, True, False])
    ref = {0: [], 2: []}
    for _ in range(12):
        lg, ref_cache = forward_decode(params, cfg, cur, ref_cache, active)
        nxt, ref_sampling = sample_core(lg, ref_sampling)
        cur = jnp.where(active, nxt, cur)
        ref[0].append(int(nxt[0]))
        ref[2].append(int(nxt[2]))

    got = {0: [], 2: []}
    for _ in range(3):
        toks, valid, cache, dstate, sampling = decode_chunk(
            params, cfg, cache, dstate, sampling, 4, use_pallas=False
        )
        toks, valid = np.asarray(toks), np.asarray(valid)
        for b in (0, 2):
            got[b] += [int(toks[i, b]) for i in range(4) if valid[i, b]]

    assert got[0] == ref[0] and got[2] == ref[2]
    assert len(set(got[0])) > 2, "degenerate sequence makes this test vacuous"
    # Cache lengths advanced by exactly the generated tokens.
    np.testing.assert_array_equal(
        np.asarray(cache.lengths), [17 + 12, 0, 33 + 12, 0]
    )


def test_device_budget_stops_generation():
    cfg = get_model_config("llama-tiny")
    params = init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
    # Slot 0: 3 more tokens allowed; slot 2: 20.
    cache, dstate, sampling = _admit(cfg, params, temps=0.0, budgets=[3, 20, 0, 0])
    toks, valid, cache, dstate, sampling = decode_chunk(
        params, cfg, cache, dstate, sampling, 8, use_pallas=False
    )
    valid = np.asarray(valid)
    assert valid[:, 0].sum() == 3 and bool(np.asarray(dstate.done)[0])
    assert valid[:, 2].sum() == 8 and not bool(np.asarray(dstate.done)[2])
    np.testing.assert_array_equal(np.asarray(cache.lengths), [20, 0, 41, 0])


def test_device_eos_stops_generation():
    cfg = get_model_config("llama-tiny")
    params = init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
    cache, dstate, sampling = _admit(cfg, params, temps=0.0, budgets=[20, 20, 0, 0])
    # Find what greedy emits first, then rerun with that id as EOS: the
    # slot must stop after emitting it.
    toks, valid, *_ = decode_chunk(
        params, cfg, cache, dstate, sampling, 4, use_pallas=False
    )
    eos = int(np.asarray(toks)[0, 0])
    cache, dstate, sampling = _admit(cfg, params, temps=0.0,
                                     budgets=[20, 20, 0, 0], eos=eos)
    toks, valid, cache, dstate, sampling = decode_chunk(
        params, cfg, cache, dstate, sampling, 8, use_pallas=False
    )
    valid = np.asarray(valid)
    assert valid[:, 0].sum() == 1, "slot 0 should stop right after EOS"
    assert bool(np.asarray(dstate.done)[0])


def test_release_decode_stops_slot():
    cfg = get_model_config("llama-tiny")
    params = init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
    cache, dstate, sampling = _admit(cfg, params, temps=0.0, budgets=[20, 20, 0, 0])
    dstate = release_decode(dstate, jnp.asarray([0, 4, 4, 4], jnp.int32))
    toks, valid, cache, dstate, sampling = decode_chunk(
        params, cfg, cache, dstate, sampling, 4, use_pallas=False
    )
    valid = np.asarray(valid)
    assert valid[:, 0].sum() == 0 and valid[:, 2].sum() == 4


def test_pallas_decode_attention_interpret_matches_dense():
    """The Pallas prefix kernel (interpret mode on CPU) must agree with the
    dense stats fallback — same (acc, m, l) contract, same masking."""
    from pilottai_tpu.engine.decode import _prefix_stats_dense
    from pilottai_tpu.ops.pallas.decode_attention import decode_attention

    rng = np.random.default_rng(3)
    for (B, N, K, S, H, softcap, window) in [
        (3, 8, 2, 128, 64, 0.0, 0),
        (2, 8, 8, 64, 64, 30.0, 0),
        (2, 16, 4, 128, 64, 0.0, 48),
    ]:
        G = N // K
        q = jnp.asarray(rng.standard_normal((B, N, H)), jnp.float32)
        kc = jnp.asarray(rng.standard_normal((B, K, S, H)), jnp.float32)
        vc = jnp.asarray(rng.standard_normal((B, K, S, H)), jnp.float32)
        last = jnp.asarray(rng.integers(-1, S - 1, (B,)), jnp.int32)
        qpos = last + 5
        acc, m, l = decode_attention(
            q, kc, vc, last, q_positions=qpos, softcap=softcap, window=window,
            return_stats=True, interpret=True,
        )
        acc_r, m_r, l_r = _prefix_stats_dense(
            q.reshape(B, K, G, H), kc, vc, last, qpos,
            H ** -0.5, softcap, window,
        )
        np.testing.assert_allclose(np.asarray(m), np.asarray(m_r), rtol=1e-5)
        np.testing.assert_allclose(np.asarray(l), np.asarray(l_r), rtol=1e-4)
        np.testing.assert_allclose(
            np.asarray(acc), np.asarray(acc_r), rtol=1e-3, atol=1e-3
        )


def test_prefix_bound_parity():
    """A chunk reading only the first ``bound`` cache columns must produce
    bit-identical tokens when every live slot's length fits the bound —
    the contract the batcher's _decode_bucket relies on (the cache is 128
    wide here, prompts are 17/33 long, bound 64 covers both)."""
    cfg = get_model_config("llama-tiny")
    params = init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
    cache, dstate, sampling = _admit(cfg, params, temps=30.0, budgets=[20, 20, 0, 0])
    ref_cache = KVCache(
        layers=tuple((k.copy(), v.copy()) for k, v in cache.layers),
        lengths=cache.lengths.copy(),
    )
    ref_sampling = SamplingState(*[a.copy() for a in sampling])
    ref_dstate = DecodeState(*[a.copy() for a in dstate])

    t_full, v_full, cache, dstate, _ = decode_chunk(
        params, cfg, cache, dstate, sampling, 8, use_pallas=False
    )
    t_b, v_b, bcache, bdstate, _ = decode_chunk(
        params, cfg, ref_cache, ref_dstate, ref_sampling, 8,
        use_pallas=False, prefix_bound=64,
    )
    np.testing.assert_array_equal(np.asarray(t_full), np.asarray(t_b))
    np.testing.assert_array_equal(np.asarray(v_full), np.asarray(v_b))
    np.testing.assert_array_equal(
        np.asarray(cache.lengths), np.asarray(bcache.lengths)
    )
    # Written cache contents agree wherever tokens landed.
    for (k_f, v_f), (k_p, v_p) in zip(cache.layers, bcache.layers):
        np.testing.assert_allclose(
            np.asarray(k_f), np.asarray(k_p), atol=1e-6
        )
        np.testing.assert_allclose(
            np.asarray(v_f), np.asarray(v_p), atol=1e-6
        )
