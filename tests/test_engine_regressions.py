"""Regression tests for code-review findings in the engine layer."""

import asyncio

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from pilottai_tpu.core.config import LLMConfig
from pilottai_tpu.engine.batcher import ContinuousBatcher, GenRequest
from pilottai_tpu.engine.handler import LLMHandler
from pilottai_tpu.engine.types import ChatMessage, GenerationParams
from pilottai_tpu.models.common import init_params
from pilottai_tpu.models.registry import get_model_config
from pilottai_tpu.models.transformer import forward_prefill
from pilottai_tpu.parallel.mesh import MeshConfig, create_mesh
from pilottai_tpu.parallel.sharding import shard_params


def _tiny_batcher(max_seq=64, n_slots=2, **kw):
    cfg = get_model_config("llama-tiny")
    params = init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
    return ContinuousBatcher(cfg, params, n_slots=n_slots, max_seq_len=max_seq,
                             cache_dtype=jnp.float32, **kw), cfg


def test_submit_truncation_never_noop():
    # max_new_tokens >= max_seq_len - 1 used to produce a -0 slice that kept
    # the whole oversized prompt and crashed the device thread.
    batcher, _ = _tiny_batcher(max_seq=64)
    req = GenRequest(prompt_ids=list(range(3, 203)), max_new_tokens=63)
    batcher.submit(req)
    assert len(req.prompt_ids) <= 62
    req2 = GenRequest(prompt_ids=list(range(3, 203)), max_new_tokens=1000)
    batcher.submit(req2)
    assert 1 <= len(req2.prompt_ids) <= 62


def test_prefill_failure_fails_future_not_thread():
    batcher, cfg = _tiny_batcher()
    # Force failure via a monkeypatched admission prefill raising: the
    # affected requests' futures must fail, the device thread must not.
    def boom(*a, **k):
        raise RuntimeError("prefill exploded")

    batcher._dispatch_prefill = boom  # type: ignore[assignment]
    batcher.start()
    try:
        req = GenRequest(prompt_ids=[1, 2, 3], max_new_tokens=4)
        fut = batcher.submit(req)
        with pytest.raises(RuntimeError, match="prefill exploded"):
            fut.result(timeout=10)
        # Thread must survive and process the next (also failing) request.
        req2 = GenRequest(prompt_ids=[1], max_new_tokens=2)
        fut2 = batcher.submit(req2)
        with pytest.raises(RuntimeError):
            fut2.result(timeout=10)
        assert batcher._thread.is_alive()
    finally:
        batcher.stop()


def test_single_token_request_completes():
    # max_new_tokens=1 has zero decode budget, so no chunk is ever
    # dispatched for it: the prefill-sampled first token must still reach
    # the future via the idle-path drain (review finding: these hung).
    batcher, _ = _tiny_batcher(max_seq=64, n_slots=2)
    batcher.start()
    try:
        req = GenRequest(prompt_ids=[3, 4, 5], max_new_tokens=1)
        out = batcher.submit(req).result(timeout=60)
        assert len(out) <= 1
    finally:
        batcher.stop()


def test_short_generation_one_chunk_boundary():
    # max_new just past one chunk (review finding: a first-token drain on
    # the device thread could race the reader and drop a chunk's tokens,
    # hanging the request). Folding is now serialized on the reader.
    batcher, _ = _tiny_batcher(max_seq=64, n_slots=2)
    batcher.chunk_size = 8
    batcher.start()
    try:
        for _ in range(3):
            req = GenRequest(prompt_ids=[3, 4, 5], max_new_tokens=9)
            out = batcher.submit(req).result(timeout=60)
            assert len(out) <= 9
    finally:
        batcher.stop()


def test_empty_prompt_completes():
    # Review finding: an empty prompt looked like an admission padding row
    # and hung forever; it now decodes from a pad token.
    batcher, _ = _tiny_batcher(max_seq=64, n_slots=2)
    batcher.start()
    try:
        out = batcher.submit(
            GenRequest(prompt_ids=[], max_new_tokens=4)
        ).result(timeout=60)
        assert 1 <= len(out) <= 4
    finally:
        batcher.stop()


def test_cancelled_request_frees_slot():
    batcher, _ = _tiny_batcher(max_seq=64, n_slots=1)
    batcher.start()
    try:
        long_req = GenRequest(prompt_ids=[1, 2, 3], max_new_tokens=60)
        batcher.submit(long_req)
        import time
        time.sleep(0.2)
        long_req.cancelled = True
        # The single slot must free up for the next request.
        short = GenRequest(prompt_ids=[4, 5], max_new_tokens=2)
        fut = batcher.submit(short)
        out = fut.result(timeout=60)
        assert isinstance(out, list)
    finally:
        batcher.stop()


def test_first_token_sampling_honors_top_p():
    # The prefill-sampled first token goes through the same device sampler
    # as every later token (one sampling implementation — the host-side
    # duplicate was a review finding). p0 ~ 0.87, top_p=0.5 => always 0.
    from pilottai_tpu.engine.decode import sample_prefill_tokens
    from pilottai_tpu.engine.sampling import SamplingState, admit_sampling

    logits = jnp.asarray([[4.0, 2.0, 0.0, -1.0]], jnp.float32)  # [1, V]
    slots = jnp.asarray([0], jnp.int32)
    picks = set()
    for seed in range(30):
        sampling = SamplingState.create(1)
        sampling = admit_sampling(
            sampling, slots, jnp.asarray([1.0]), jnp.asarray([0], jnp.int32),
            jnp.asarray([0.5]), jnp.asarray([seed], jnp.int32),
            jnp.asarray([-1], jnp.int32), jnp.asarray([False]),
        )
        tok, _ = sample_prefill_tokens(logits, slots, sampling)
        picks.add(int(tok[0]))
    assert picks == {0}


@pytest.mark.asyncio
async def test_concurrent_start_single_batcher():
    from pilottai_tpu.engine.native import NativeEngine

    import threading

    engine = NativeEngine(
        LLMConfig(model_name="llama-tiny", provider="cpu", engine_max_seq=128),
        platform="cpu",
    )
    # Count only threads this test creates — a prior test's device loop may
    # still be winding down (stop() joins, but daemon threads can linger).
    before = {
        t for t in threading.enumerate() if t.name == "pilottai-device-loop"
    }
    try:
        await asyncio.gather(engine.start(), engine.start(), engine.start())
        assert engine.batcher is not None
        after = {
            t for t in threading.enumerate()
            if t.name == "pilottai-device-loop"
        }
        assert len(after - before) == 1
    finally:
        await engine.stop()


def test_prefill_mask_uses_absolute_positions():
    # Prefill at a nonzero offset: token i may only attend j with pos_j <=
    # pos_i. With the old arange-based mask this is indistinguishable; with
    # *decreasing* positions the two disagree.
    cfg = get_model_config("llama-tiny")
    params = init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
    tokens = jnp.asarray([[5, 6, 7, 8]])
    inc = jnp.asarray([[0, 1, 2, 3]])
    dec = jnp.asarray([[3, 2, 1, 0]])
    valid = jnp.asarray([4])
    logits_inc, _, _ = forward_prefill(params, cfg, tokens, inc, valid)
    logits_dec, _, _ = forward_prefill(params, cfg, tokens, dec, valid)
    # Row 0 under decreasing positions attends everything (pos 3 is max);
    # under increasing positions it attends only itself → logits differ.
    assert not np.allclose(np.asarray(logits_inc[0, 0]), np.asarray(logits_dec[0, 0]))


def test_shard_params_accepts_bare_none_leaf():
    mesh = create_mesh(MeshConfig(data=2, model=4))
    params = {"w": jnp.ones((8, 8)), "b": jnp.ones((8,))}
    logical = {"w": ("embed", "mlp"), "b": None}  # bare None = replicated
    placed = shard_params(params, logical, mesh)
    assert placed["b"].sharding.is_fully_replicated


def test_donated_admit_failure_rebuilds_state():
    """admit_group donates cache/dstate/sampling; a dispatch failure that
    consumed them must not leave the engine pointing at deleted buffers —
    in-flight work fails loudly, state is rebuilt, and the engine serves
    the next request (code-review finding, round 2). Recovery is OFF
    here so the ORIGINAL failure surfaces after one attempt and the
    rebuild machinery is tested surgically (recovery's own contract
    lives in tests/test_chaos.py)."""
    import pilottai_tpu.engine.batcher as bmod

    batcher, cfg = _tiny_batcher(recovery_max_attempts=0)
    real_admit = bmod.admit_group

    def poison(params, cfg_, cache, dstate, sampling, *a, **k):
        # Simulate the donated buffers being consumed before the failure.
        for k_, v_ in cache.layers:
            k_.delete()
            v_.delete()
        cache.lengths.delete()
        raise RuntimeError("device lost mid-dispatch")

    bmod.admit_group = poison
    try:
        batcher.start()
        req = GenRequest(prompt_ids=[1, 2, 3], max_new_tokens=4)
        fut = batcher.submit(req)
        with pytest.raises(RuntimeError, match="device lost"):
            fut.result(timeout=30)
        # State was rebuilt with live buffers.
        import time as _time

        deadline = _time.monotonic() + 10
        while batcher.cache.lengths.is_deleted():
            assert _time.monotonic() < deadline
        # With the real admission path back, the engine still serves.
        bmod.admit_group = real_admit
        req2 = GenRequest(prompt_ids=[1, 2, 3], max_new_tokens=3)
        out = batcher.submit(req2).result(timeout=60)
        assert len(out) == 3
    finally:
        bmod.admit_group = real_admit
        batcher.stop()


@pytest.mark.asyncio
async def test_stop_after_lazy_start_kills_device_threads():
    """generate() starts the backend lazily without flipping the handler's
    _started flag; stop() must still stop the backend, or live device
    threads outlast the handler and crash the process at exit (verify
    finding, round 2)."""
    import threading

    h = LLMHandler(LLMConfig(
        model_name="llama-tiny", provider="cpu", engine_slots=2,
        engine_max_seq=64, engine_chunk=4, dtype="float32",
    ))
    before = {
        t for t in threading.enumerate() if t.name == "pilottai-device-loop"
    }
    # No explicit start(): the engine boots inside the first generate.
    await h.apredict("hello", params=GenerationParams(max_new_tokens=3))
    await h.stop()
    after = {
        t for t in threading.enumerate()
        if t.name == "pilottai-device-loop" and t.is_alive() and t not in before
    }
    assert not after, f"device threads leaked past stop(): {after}"


def test_stop_releases_the_engines_device_arrays():
    """A stopped engine's weights and KV must leave the device with it,
    not whenever the cyclic collector next runs: on a 16 GB chip a second
    8B engine in the same process failed to allocate beside the first
    one's uncollected 10 GB (chip run, PR 21)."""
    import gc

    async def serve_once():
        handler = LLMHandler(LLMConfig(
            model_name="llama-tiny", provider="cpu", engine_slots=2,
            engine_max_seq=128, engine_chunk=4,
        ))
        try:
            await handler.apredict(
                "hello", params=GenerationParams(max_new_tokens=4)
            )
        finally:
            await handler.stop()

    gc.collect()
    before = len(jax.live_arrays())
    gc.disable()  # the engine must not lean on an incidental collection
    try:
        asyncio.run(serve_once())
        after = len(jax.live_arrays())
    finally:
        gc.enable()
    assert after <= before, f"{after - before} device arrays outlived stop()"
